#!/usr/bin/env python3
"""The readings a cell's limits are set from (not part of a benchmark run).

    python bench/readings.py --workload <cell> --seeds 1,2,...,12 \\
        --control-seeds 3 --out <file.json>

In one process, with each step compiled once: for every seed, the compared
numbers of the program's first steps against the reference (the lower
readings); for the first ``--control-seeds`` seeds also those of the
control (the reference one precision below, put in the program's place)
and of the faults of ``bench/faults.py`` planted in the program's step:
half of every batch left out, and every conv output altered.  A state left
unchanged reads 1 on ``grad`` and ``update`` by construction and needs no
run.  Needs a TPU, like ``bench/run.py``.
"""

from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 2
    from repro.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    from bench import compare, faults, harness, spec

    cell = spec.load_cell(args.workload)
    kind = harness.build(cell)
    state, pool = harness.draw(kind, cell, seeds[0])
    steps = {"program": harness.compile_step(kind, cell, state, pool)[0]}
    half = types.SimpleNamespace(program=faults.half_batch(kind.program),
                                 passes=lambda: [])
    steps["half_batch"] = harness.compile_step(half, cell, state, pool)[0]
    with faults.altered_answers():
        steps["altered_answer"] = harness.compile_step(kind, cell, state,
                                                       pool)[0]
    rows = []
    for n, seed in enumerate(seeds):
        if n:
            state, pool = harness.draw(kind, cell, seed)
        runs = steps if n < args.control_seeds else {"program":
                                                     steps["program"]}
        obs = {}
        for name, step in runs.items():
            if obs:                  # the step before consumed the state
                state = harness.draw(kind, cell, seed)[0]
            _, states, outs = harness.first_steps(step, state, pool)
            obs[name] = kind.observe(states, outs)
        del state, pool, states, outs
        ref = harness.reference(kind, cell, seed)
        if n < args.control_seeds:
            obs["control"] = harness.reference(kind, cell, seed, "high")
        row = {"seed": seed}
        for name, o in obs.items():
            row[name] = compare.numbers(o, ref)
            row[name + "_items"] = {
                k: compare.item_gaps(o[k], ref[k]).tolist() for k in ref
                if k not in compare.ELEMENTWISE.values()}
        del obs, ref
        print(json.dumps(row), flush=True)
        rows.append(row)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(
        {"workload": args.workload,
         "device": jax.devices()[0].device_kind, "rows": rows}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
