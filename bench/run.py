#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up draws the weights and a pool of batches from ``--seed`` on the
device, compiles the cell's step once (jax's persistent compilation cache
lives in the checkout, see ``repro.core.compile_cache``) and runs the
first three steps, which the plain float32 reference then follows.  The
window dispatches steps back to back for ``--seconds`` and waits for the
last.  With ``--trace 1`` a profiler trace of the window (at most five
seconds of it) gives the per-layer metrics instead of the end-to-end ones.

The last line of standard output is the result as one JSON object; the
last lines of standard error are the compared numbers beside their limits.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2.  ``--policy`` replaces the cell's conv engine policy
(for comparing engines by hand; the benchmark's own runs never pass it).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--policy", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from bench import spec
    cell = spec.load_cell(args.workload)

    # The TPU runtime otherwise keeps its logs in a fixed directory
    # outside the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); jax "
              f"found {len(devices)} {devices[0].platform} device(s). "
              f"Nothing was run.", file=sys.stderr)
        return 2
    spec.peaks(devices[0].device_kind)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.core.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"bench: the program (src/repro) is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    # Cache every program, however fast it compiled, so that only the
    # first run of a cell in a checkout compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from bench import harness
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START, args.policy)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
