"""Train a strided CNN classifier with a selectable conv-backprop engine
policy -- the paper's training scenario, end-to-end.

    PYTHONPATH=src python examples/train_cnn_bp.py --policy bp_phase
    PYTHONPATH=src python examples/train_cnn_bp.py \
        --policy fwd=lax,dgrad=pallas,wgrad=bp_phase --steps 200

Policies: a uniform engine name (lax | traditional | bp_im2col | bp_phase |
pallas), "auto" (per-pass shape-dependent selection), or an explicit
per-pass string fwd=...,dgrad=...,wgrad=...  All reach the same losses
(engines are exact); wall-clock differences on CPU echo the paper's
reorganization-elimination claim (traditional pays for the zero-space
copies; see benchmarks/bench_kernels.py for controlled numbers).

The model goes through ``repro.models.layers`` conv layers, so ``jax.grad``
dispatches every conv backward through the policy's per-pass engines via
the ``custom_vjp`` -- the same wiring the full training stack
(``repro.train.train_step``) uses.  The second conv is depthwise
(``groups=C``) to exercise the grouped datapath.
"""

import argparse
import sys
import time
import warnings

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compile_cache import enable_compile_cache
from repro.models import layers as L


def make_model(policy):
    def forward(params, x):
        h = L.conv2d_apply(params["c1"], x, stride=2, padding=1,
                           policy=policy)
        h = jax.nn.relu(h)                                # 16x16 -> 8x8
        h = L.conv2d_apply(params["dw"], h, stride=1, padding=1,
                           policy=policy, groups=16)      # depthwise 8x8
        h = jax.nn.relu(h)
        h = L.conv2d_apply(params["c2"], h, stride=2, padding=1,
                           policy=policy)
        h = jax.nn.relu(h)                                # 8x8 -> 4x4
        h = h.mean((2, 3))                                # GAP
        return h @ params["head"]

    def loss_fn(params, x, y):
        logits = forward(params, x)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], 1).mean()

    return forward, loss_fn


def init_params(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    rng = np.random.RandomState(seed)
    return {
        "c1": L.init_conv2d(ks[0], 3, 16, 3, jnp.float32),
        "dw": L.init_conv2d(ks[1], 16, 16, 3, jnp.float32, groups=16),
        "c2": L.init_conv2d(ks[2], 16, 32, 3, jnp.float32),
        "head": jnp.asarray(rng.randn(32, 4) * 0.1, jnp.float32),
    }


def synthetic_task(rng, n, classes=4):
    """Learnable synthetic vision task: class = dominant quadrant pattern."""
    x = rng.randn(n, 3, 16, 16).astype(np.float32)
    y = rng.randint(0, classes, n)
    for i in range(n):
        q = y[i]
        r0, c0 = (q // 2) * 8, (q % 2) * 8
        x[i, :, r0:r0 + 8, c0:c0 + 8] += 2.0
    return jnp.asarray(x), jnp.asarray(y)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default=None,
                    help="engine policy: a uniform engine name, 'auto' "
                         "(per-pass shape-dependent selection), or a "
                         "per-pass string fwd=...,dgrad=...,wgrad=... "
                         "(default bp_phase)")
    ap.add_argument("--mode", default=None,
                    choices=["lax", "traditional", "bp_im2col", "bp_phase",
                             "pallas"],
                    help="DEPRECATED compatibility alias: maps to a "
                         "uniform --policy and warns; use --policy")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--acc-floor", type=float, default=0.9)
    ap.add_argument("--autotune", default=None,
                    choices=["off", "measure", "cached"],
                    help="measured autotuning of the Pallas tile plans "
                         "(repro.config.autotune)")
    ap.add_argument("--plan-cache-dir", default=None,
                    help="persistent plan-cache directory "
                         "(repro.config.plan_cache_dir)")
    ap.add_argument("--fault-spec", default=None,
                    help="arm the fault injector (repro.config.fault_spec), "
                         "e.g. 'pallas.*:raise@step3' -- see "
                         "examples/train_chaos.py for the full chaos drill")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable telemetry and write a Perfetto trace_event "
                         "JSON (repro.obs) to PATH")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="enable telemetry and stream per-step metrics "
                         "JSONL to PATH")
    args = ap.parse_args()
    enable_compile_cache()
    if args.autotune is not None or args.plan_cache_dir is not None \
            or args.fault_spec is not None or args.trace is not None \
            or args.metrics is not None:
        from repro.core.config import config
        config.update(**{k: v for k, v in
                         (("autotune", args.autotune),
                          ("plan_cache_dir", args.plan_cache_dir),
                          ("fault_spec", args.fault_spec),
                          ("telemetry", bool(args.trace or args.metrics)
                           or None),
                          ("trace_path", args.trace),
                          ("metrics_path", args.metrics))
                         if v is not None})
    if args.mode is not None:
        warnings.warn("--mode is deprecated; use --policy",
                      DeprecationWarning)
        if args.policy is not None:
            raise SystemExit("pass either --policy or the deprecated "
                             "--mode, not both")
    policy = args.policy or args.mode or "bp_phase"

    from repro import obs

    rng = np.random.RandomState(0)
    _, loss_fn = make_model(policy)
    params = init_params()
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    t0 = time.perf_counter()
    for step in range(args.steps):
        ts = time.perf_counter()
        if args.fault_spec:
            from repro.ft import inject
            inject.set_step(step)
        x, y = synthetic_task(rng, args.batch)
        with obs.trace.span("train:step", step=step):
            loss, g = grad_fn(params, x, y)
            params = jax.tree.map(lambda p, gg: p - args.lr * gg, params, g)
        obs.metrics.train_step(step, {"loss": float(loss)},
                               step_s=time.perf_counter() - ts)
        if step % 20 == 0 or step == args.steps - 1:
            print(f"[{policy}] step={step:4d} loss={float(loss):.4f}")
    dt = time.perf_counter() - t0
    xe, ye = synthetic_task(np.random.RandomState(1), 256)
    fwd, _ = make_model(policy)
    acc = float((jnp.argmax(fwd(params, xe), -1) == ye).mean())
    print(f"[{policy}] done in {dt:.1f}s  eval_acc={acc:.3f}")
    assert acc > args.acc_floor, "training failed to learn the synthetic task"
    if obs.enabled():
        rep = obs.finalize()
        print(f"[{policy}] obs: {rep['events_total']} events "
              f"{rep['events_by_kind']} trace={rep['trace_file']} "
              f"metrics={rep['metrics']['lines']} lines")
        # The CI obs lane's divergence gate: every legacy counter must
        # agree with its bus-backed view.
        assert rep["consistent"], (
            "telemetry divergence: " + "; ".join(rep["divergences"]))
        if args.trace:
            assert rep["trace"]["spans_by_prefix"].get("conv", 0) > 0, \
                "telemetry on but no conv dispatch spans were traced"
        if args.metrics:
            assert rep["metrics"]["lines"] >= args.steps, rep["metrics"]


if __name__ == "__main__":
    main()
