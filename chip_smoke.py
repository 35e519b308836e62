#!/usr/bin/env python3
"""Prove that BP-im2col conv training runs on a TPU, compiled, end to end.

    python chip_smoke.py              # one chip: the three phases below
    python chip_smoke.py --chips 4    # four chips: mesh-parallel training only

One chip, in order, one output line per phase:

  1. ``table2``  -- every Table II layer of the paper at its published
     widths (batch 2, as in the paper's section IV): a jitted
     ``value_and_grad`` of ``conv2d(x, w, spec, "pallas")`` runs the
     forward, input-grad and weight-grad tap-GEMM kernels, compared with
     the ``lax`` conv at ``highest`` matmul precision.
  2. ``autoencoder`` -- the conv -> conv_transpose autoencoder trained
     through ``make_train_step`` with policy ``auto`` (256x256 images,
     widths (64, 128), batch 8, 3 steps); losses compared with the same
     steps under policy ``lax``.
  3. ``trainer`` -- mamba2-370m at full width through the normal launcher
     (``repro.launch.train.main``), batch 8 x seq 1024, 3 steps: losses and
     gradient norms must be finite.

``--chips 4`` runs only the autoencoder step under ``conv_mesh("spatial")``
and ``conv_mesh("tp")`` on a 2x2 (data, model) mesh, each compared with
the single-device step on the same batch.

Every check that fails raises, and the script exits non-zero.  It also
exits non-zero, printing no result, when jax finds no TPU or when the
``repro`` package is not next to it.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Everything runs in this one process, which holds the chip(s).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

#: max |pallas - lax| over max |lax|, per output of each Table II pass.
LAYER_TOL = 1e-4
#: relative loss difference allowed between two engines' (or two
#: shardings') runs of the same training steps.
LOSS_RTOL = 1e-4
PASSES = ("forward", "input_grad", "weight_grad")


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def report(phase: str, **fields) -> None:
    print(f"phase={phase} " + json.dumps(fields, sort_keys=True), flush=True)


def rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def _timed_compile(fn, *args):
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _time_calls(compiled, args, reps: int) -> list[float]:
    """Wall seconds of ``reps`` calls after one warm-up call."""
    import jax
    jax.block_until_ready(compiled(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        out.append(time.perf_counter() - t0)
    return out


def _check_dispatch(events: dict, passes, what: str) -> dict:
    """Every dispatch of ``passes`` went to pallas, each at least once."""
    counts = {}
    for p in passes:
        engines = {k.split(":", 1)[1]: v for k, v in events.items()
                   if k.split(":", 1)[0] == p}
        check(set(engines) == {"pallas"},
              f"{what}: {p} dispatched to {engines}, want only pallas")
        counts[p] = engines["pallas"]
    return counts


def _check_no_fallback(what: str) -> None:
    from repro.core import conv as C
    from repro.kernels import ops
    fails = C.runtime_failures()
    check(not fails, f"{what}: runtime engine failures {fails}")
    fallbacks = {k: v for k, v in ops.plan_events().items()
                 if k.endswith("_fallback")}
    check(not fallbacks, f"{what}: plan fallbacks {fallbacks}")


def _reset_counters() -> None:
    from repro.core import conv as C
    from repro.kernels import ops
    C.reset_dispatch_events()
    ops.reset_plan_events()


# ---------------------------------------------------------------------------
# Phase 1: Table II layers, forward + both gradients through pallas
# ---------------------------------------------------------------------------

def phase_table2(layers, batch: int, seed: int, reps: int = 5) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.configs import paper_cnn
    from repro.core import conv as C
    from repro.core.convspec import ConvSpec

    def loss(x, w, g, spec, policy):
        y = C.conv2d(x, w, spec, policy)
        return jnp.vdot(y, g), y

    rows = []
    for i, layer in enumerate(layers):
        d = paper_cnn.dims(layer, batch)
        spec = ConvSpec.make(stride=d.S, padding=d.P_h)
        kx, kw, kg = jax.random.split(jax.random.PRNGKey(seed + i), 3)
        x = jax.random.normal(kx, (d.B, d.C, d.H_i, d.W_i), jnp.float32)
        w = jax.random.normal(kw, (d.N, d.C, d.K_h, d.K_w), jnp.float32)
        w = w / (d.C * d.K_h * d.K_w) ** 0.5
        g = jax.random.normal(kg, (d.B, d.N, d.H_o, d.W_o), jnp.float32)

        def grad_fn(policy):
            return jax.jit(jax.value_and_grad(
                lambda x, w, g: loss(x, w, g, spec, policy),
                argnums=(0, 1), has_aux=True))

        _reset_counters()
        compiled, compile_s = _timed_compile(grad_fn("pallas"), x, w, g)
        tag = f"table2 {layer}"
        dispatch = _check_dispatch(C.dispatch_events(), PASSES, tag)
        _check_no_fallback(tag)
        step_s = _time_calls(compiled, (x, w, g), reps)
        (_, y), (dx, dw) = compiled(x, w, g)
        with jax.default_matmul_precision("highest"):
            (_, y_ref), (dx_ref, dw_ref) = grad_fn("lax")(x, w, g)
        errs = {"y": rel_err(y, y_ref), "dx": rel_err(dx, dx_ref),
                "dw": rel_err(dw, dw_ref)}
        check(all(e <= LAYER_TOL for e in errs.values()),
              f"{tag}: errors {errs} over {LAYER_TOL}")
        rows.append({"layer": list(layer), "compile_s": compile_s,
                     "step_s": sorted(step_s)[len(step_s) // 2],
                     "rel_err": errs, "dispatch": dispatch})
    return {"batch": batch, "tol": LAYER_TOL, "layers": rows}


# ---------------------------------------------------------------------------
# Phase 2 (and the four-chip phase): autoencoder through make_train_step
# ---------------------------------------------------------------------------

def _autoencoder(widths, hw: int, batch: int, seed: int):
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    from repro.optim import adamw
    cfg = M.AutoencoderConfig(c_in=3, widths=tuple(widths), k=3,
                              conv_policy="auto")
    params = M.init_autoencoder(jax.random.PRNGKey(seed), cfg)
    image = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (batch, 3, hw, hw), jnp.float32)
    return cfg, params, adamw.init_state(params), {"image": image}


def _train_steps(cfg, params, opt, batch, steps: int, policy=None,
                 conv_mesh=None, shardings=None) -> dict:
    """Compile one train step and run ``steps`` of it; losses, compile
    seconds and the seconds of each step after the first."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    from repro.optim import adamw
    from repro.train import train_step as TS
    step = TS.make_train_step(cfg, adamw.AdamWConfig(peak_lr=1e-3),
                              total_steps=10, warmup=1,
                              loss=M.autoencoder_loss, conv_policy=policy,
                              conv_mesh=conv_mesh)
    jitted = (jax.jit(step) if shardings is None else
              jax.jit(step, in_shardings=(*shardings, None),
                      out_shardings=(*shardings[:2], None)))
    compiled, compile_s = _timed_compile(jitted, params, opt, batch,
                                         jnp.int32(0))
    losses, step_s = [], []
    for s in range(steps):
        t0 = time.perf_counter()
        params, opt, metrics = compiled(params, opt, batch, jnp.int32(s))
        losses.append(float(metrics["loss"]))
        step_s.append(time.perf_counter() - t0)
    check(all(map(_finite, losses)), f"non-finite losses {losses}")
    return {"losses": losses, "compile_s": compile_s, "step_s": step_s[1:]}


def _finite(v: float) -> bool:
    return v == v and abs(v) != float("inf")


def _loss_diff(a, b) -> float:
    return max(abs(x - y) / max(abs(y), 1e-30) for x, y in zip(a, b))


def phase_autoencoder(widths, hw: int, batch: int, steps: int,
                      seed: int) -> dict:
    import jax
    from repro.core import conv as C
    cfg, params, opt, data = _autoencoder(widths, hw, batch, seed)
    with jax.default_matmul_precision("highest"):
        _reset_counters()
        run = _train_steps(cfg, params, opt, data, steps)
        events = C.dispatch_events()
        dispatch = _check_dispatch(
            events, PASSES + tuple(f"{p}_T" for p in PASSES), "autoencoder")
        _check_no_fallback("autoencoder")
        ref = _train_steps(cfg, params, opt, data, steps, policy="lax")
    diff = _loss_diff(run["losses"], ref["losses"])
    check(diff <= LOSS_RTOL,
          f"autoencoder: auto {run['losses']} vs lax {ref['losses']}")
    return {"widths": list(widths), "image": hw, "batch": batch,
            "auto": run, "lax": ref, "loss_rel_diff": diff,
            "tol": LOSS_RTOL, "dispatch": dispatch}


def phase_mesh(widths, hw: int, batch: int, steps: int, seed: int) -> dict:
    """The autoencoder step sharded over a 2x2 mesh, per conv mesh policy,
    against the single-device step on the same batch."""
    import jax
    from repro.core import conv as C
    from repro.dist import sharding as SH
    from repro.dist.constraints import set_activation_policy
    from repro.launch.mesh import auto_mesh
    check(len(jax.devices()) == 4,
          f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    cfg, params, opt, data = _autoencoder(widths, hw, batch, seed)
    out = {"widths": list(widths), "image": hw, "batch": batch,
           "tol": LOSS_RTOL}
    with jax.default_matmul_precision("highest"):
        single = _train_steps(cfg, params, opt, data, steps)
        out["single"] = single
        mesh = auto_mesh((2, 2), ("data", "model"))
        for policy in ("spatial", "tp"):
            set_activation_policy(SH.batch_axes(mesh, policy))
            sh = (SH.to_shardings(SH.param_specs(params, mesh, policy), mesh),
                  SH.to_shardings(SH.opt_state_specs(params, mesh, policy),
                                  mesh),
                  SH.to_shardings(SH.batch_specs(data, mesh, policy), mesh))
            _reset_counters()
            with mesh:
                p, o, b = (jax.device_put(t, s)
                           for t, s in zip((params, opt, data), sh))
                run = _train_steps(cfg, p, o, b, steps, conv_mesh=policy,
                                   shardings=sh)
            mesh_events = {k: v for k, v in C.dispatch_events().items()
                           if k.startswith("mesh:conv2d")}
            check(mesh_events, f"{policy}: no conv was sharded")
            _check_no_fallback(f"mesh {policy}")
            run["loss_rel_diff"] = _loss_diff(run["losses"], single["losses"])
            run["mesh_events"] = mesh_events
            check(run["loss_rel_diff"] <= LOSS_RTOL,
                  f"{policy}: sharded {run['losses']} vs single "
                  f"{single['losses']}")
            out[policy] = run
        set_activation_policy(None)
    return out


# ---------------------------------------------------------------------------
# Phase 3: a published model at full width through the normal launcher
# ---------------------------------------------------------------------------

def phase_trainer(arch: str, batch: int, seq: int, steps: int,
                  smoke: bool = False) -> dict:
    from repro.core import conv as C
    from repro.launch import train
    argv = ["--arch", arch, "--steps", str(steps), "--batch", str(batch),
            "--seq", str(seq), "--log-every", "1"]
    history: list[dict] = []
    _reset_counters()
    train.main(argv + (["--smoke"] if smoke else []), history=history)
    _check_no_fallback(arch)
    check(len(history) == steps, f"{arch}: ran {len(history)} steps")
    for h in history:
        check(_finite(h["loss"]) and _finite(h["grad_norm"]),
              f"{arch}: non-finite step {h}")
    return {"arch": arch, "batch": batch, "seq": seq,
            "losses": [h["loss"] for h in history],
            "grad_norms": [h["grad_norm"] for h in history],
            "first_step_s_with_compile": history[0]["step_s"],
            "step_s": [h["step_s"] for h in history[1:]],
            "dispatch": C.dispatch_events()}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-parallel autoencoder phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devices[0].platform} "
              f"devices); nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(REPO, "src"))
    try:
        from repro.core.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    enable_compile_cache()
    from repro.configs import paper_cnn
    from repro.core.config import config

    check(config.interpret is False, "config.interpret resolved to True")
    if args.chips == 4:
        report("mesh", **phase_mesh((64, 128), 256, 8, 3, args.seed))
    else:
        report("table2", interpret=config.interpret,
               **phase_table2(paper_cnn.TABLE2_LAYERS, paper_cnn.BATCH,
                              args.seed))
        report("autoencoder",
               **phase_autoencoder((64, 128), 256, 8, 3, args.seed))
        report("trainer", **phase_trainer("mamba2-370m", 8, 1024, 3))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
