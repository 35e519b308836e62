"""Serving launcher: batched request serving on a smoke-scale model.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --requests 8 --max-new 16 --engine continuous

``--engine static`` runs the wave-batched baseline
(``repro.serve.engine``); ``--engine continuous`` (default) runs the
slotted-cache continuous-batching engine (``repro.serve.continuous``).
``--deadline-s`` gives every request a wall-clock budget: overdue
requests finalize with partial output and ``status="timed_out"`` instead
of stalling the batch.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core.compile_cache import enable_compile_cache
from repro.configs import get_smoke_config
from repro.models import model as M
from repro.serve.continuous import ContinuousEngine
from repro.serve.engine import Engine
from repro.serve.request import Request

ENGINES = {"static": Engine, "continuous": ContinuousEngine}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--engine", choices=sorted(ENGINES), default="continuous",
                    help="wave-batched baseline or slotted continuous "
                         "batching (default)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock budget in seconds; "
                         "overdue requests finalize with partial output "
                         "and status='timed_out'")
    ap.add_argument("--conv-policy", default=None,
                    help="per-pass conv engine policy for the decode path "
                         "(e.g. 'auto', 'bp_phase', or "
                         "'fwd=...,dgrad=...,wgrad=...')")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch)
    model = M.build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    eng = ENGINES[args.engine](
        cfg, params, max_batch=args.max_batch,
        max_len=args.prompt_len + args.max_new + 2,
        temperature=args.temperature, seed=args.seed,
        conv_policy=args.conv_policy)
    rng = np.random.RandomState(args.seed)
    for rid in range(args.requests):
        eng.submit(Request(
            rid=rid,
            prompt=rng.randint(0, cfg.vocab, args.prompt_len).tolist(),
            max_new=args.max_new,
            deadline_s=args.deadline_s))
    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.out) for r in done)
    by_status = {}
    for r in done:
        by_status[r.status] = by_status.get(r.status, 0) + 1
    lat = sorted(r.t_done - r.t_submit for r in done
                 if r.t_done is not None)
    p50 = lat[len(lat) // 2] if lat else float("nan")
    print(f"[serve] arch={cfg.name} engine={args.engine} "
          f"requests={len(done)} tokens={n_tok} "
          f"wall={dt:.2f}s ({n_tok/dt:.1f} tok/s) "
          f"p50_latency={p50:.2f}s status={by_status}")
    for r in done[:3]:
        print(f"  req{r.rid}: {r.out[:10]}... [{r.status}]")
    return done


if __name__ == "__main__":
    main()
