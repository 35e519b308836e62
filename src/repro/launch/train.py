"""Training launcher: end-to-end driver wiring every substrate together.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Features exercised here (and by examples/ + tests):
  * deterministic restartable data pipeline (resume replays nothing);
  * jitted train step (loss + AdamW + schedule) with optional microbatch
    accumulation;
  * step-atomic checkpoints with rotation + async write;
  * straggler/heartbeat bookkeeping hooks (single-process here; the same
    objects drive the restart plan in the multi-worker deployment);
  * mesh-aware sharding when >1 device is visible (CPU: 1 device).
"""

from __future__ import annotations

import argparse
import contextlib
import time
import warnings

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataConfig, make_batch
from repro.ckpt import checkpoint as CKPT
from repro.ft import inject
from repro.ft.failures import (GuardState, HeartbeatTable, StragglerDetector,
                               make_guard_restart_plan)
from repro.models import model as M
from repro.optim import adamw
from repro.train import train_step as TS
from repro.core.compile_cache import enable_compile_cache
from repro import obs


def resolve_conv_policy_args(conv_policy: str | None,
                             conv_mode: str | None) -> str | None:
    """Map the CLI pair onto one policy string; --conv-mode is the
    deprecated uniform spelling and may not be combined with
    --conv-policy."""
    if conv_mode is not None:
        warnings.warn("--conv-mode is deprecated; use --conv-policy "
                      "(same engine names; per-pass via "
                      "fwd=...,dgrad=...,wgrad=...)", DeprecationWarning,
                      stacklevel=2)
        if conv_policy is not None:
            raise SystemExit(
                "pass either --conv-policy or the deprecated --conv-mode, "
                "not both")
        return conv_mode
    return conv_policy


def _with_guard_streak(opt_state, guard: bool):
    """The in-graph guard carries its bad-step streak in ``opt_state``.
    Starting it at zero gives the first step the same state pytree as
    every later one, so the step compiles once instead of twice."""
    if guard and "guard_streak" not in opt_state:
        opt_state = {**opt_state, "guard_streak": jnp.zeros((), jnp.int32)}
    return opt_state


def main(argv=None, history: list | None = None):
    """Run the launcher on ``argv``; returns the per-step losses.  A
    ``history`` list, when given, receives one dict per step (loss,
    grad_norm, step_s) for in-process callers that check more than the
    loss."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--stop-after", type=int, default=None,
                    help="simulate preemption: stop at this step while the "
                         "schedule still targets --steps")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--conv-policy", default=None,
                    help="per-pass conv engine policy, e.g. 'auto', "
                         "'pallas' (uniform) or "
                         "'fwd=pallas,dgrad=auto,wgrad=bp_phase' "
                         "(default: cfg.conv_policy)")
    ap.add_argument("--conv-mode", default=None,
                    choices=["lax", "traditional", "bp_im2col", "bp_phase",
                             "pallas"],
                    help="DEPRECATED: uniform spelling of --conv-policy")
    ap.add_argument("--conv-mesh", default=None,
                    choices=["tp", "dp_only", "spatial"],
                    help="mesh-parallel conv lowering over this host's "
                         "devices (repro.dist.conv_parallel): batch/"
                         "channel/spatial sharding with halo exchange; "
                         "layers the mesh cannot shard fall back with a "
                         "recorded reason")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fault-spec", default=None,
                    help="arm the fault injector (repro.config.fault_spec), "
                         "e.g. 'ckpt.write:raise@step3;grad.values:nan@step5'")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable telemetry and write a Chrome/Perfetto "
                         "trace_event JSON of the run (repro.obs.trace) "
                         "to PATH")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="enable telemetry and stream per-step metrics "
                         "JSONL (loss/grad_norm/guard/dispatch mix) to PATH")
    guard_group = ap.add_mutually_exclusive_group()
    guard_group.add_argument("--guard", dest="guard", action="store_true",
                             default=True,
                             help="in-graph numerical guard: skip non-finite "
                                  "steps, escalate to clip then rollback "
                                  "(default: on)")
    guard_group.add_argument("--no-guard", dest="guard",
                             action="store_false")
    ap.add_argument("--guard-clip-after", type=int, default=2,
                    help="consecutive bad steps before the tighter grad "
                         "clip engages")
    ap.add_argument("--guard-rollback-after", type=int, default=4,
                    help="consecutive bad steps before restoring the last "
                         "committed checkpoint")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.fault_spec is not None or args.trace is not None \
            or args.metrics is not None:
        from repro.core.config import config
        updates = {}
        if args.fault_spec is not None:
            updates["fault_spec"] = args.fault_spec
        if args.trace is not None:
            updates.update(telemetry=True, trace_path=args.trace)
        if args.metrics is not None:
            updates.update(telemetry=True, metrics_path=args.metrics)
        config.update(**updates)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family == "ssm":
        assert args.seq % 128 == 0 or args.seq <= 128, \
            "mamba2 chunking needs seq % 128 == 0 (or <= 128)"
    model = M.build_model(cfg)
    dcfg = DataConfig(seed=args.seed, seq_len=args.seq,
                      global_batch=args.batch, vocab=cfg.vocab)

    opt_cfg = adamw.AdamWConfig(peak_lr=args.lr)
    guard_cfg = TS.GuardConfig(clip_after=args.guard_clip_after) \
        if args.guard else None
    mesh_ctx = contextlib.nullcontext()
    if args.conv_mesh:
        from repro.dist import set_activation_policy, sharding
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh()
        set_activation_policy(sharding.batch_axes(mesh, args.conv_mesh))
        mesh_ctx = mesh                 # with mesh: the step traces sharded
    step_fn = jax.jit(TS.make_train_step(
        cfg, opt_cfg, total_steps=args.steps,
        warmup=max(1, args.steps // 20), accum_steps=args.accum,
        conv_policy=resolve_conv_policy_args(args.conv_policy,
                                             args.conv_mode),
        conv_mesh=args.conv_mesh,
        guard=guard_cfg))

    start_step = 0
    params = opt_state = None
    if args.ckpt_dir:
        start_step_, restored = CKPT.restore(args.ckpt_dir)
        if restored is not None:
            start_step = start_step_ + 1
            params = jax.tree.map(jnp.asarray, restored["params"])
            opt_state = jax.tree.map(jnp.asarray, restored["opt"])
            print(f"[train] resumed from step {start_step_}")
    if params is None:
        params = model.init(jax.random.PRNGKey(args.seed))
        opt_state = adamw.init_state(params)
    opt_state = _with_guard_streak(opt_state, args.guard)
    n_params = model.param_count(params)
    print(f"[train] arch={cfg.name} params={n_params:,} "
          f"active={model.active_param_count(params):,}")

    hb = HeartbeatTable(n_workers=1)
    straggler = StragglerDetector(n_workers=1)
    gs = GuardState(clip_after=args.guard_clip_after,
                    rollback_after=args.guard_rollback_after) \
        if args.guard else None
    losses = []
    end_step = min(args.steps, args.stop_after) if args.stop_after \
        else args.steps
    for step in range(start_step, end_step):
        t0 = time.perf_counter()
        inject.set_step(step)
        batch = jax.tree.map(jnp.asarray, make_batch(cfg, dcfg, step))
        with obs.trace.span("train:step", step=step):
            with mesh_ctx:              # ambient mesh for the sharded trace
                params, opt_state, metrics = step_fn(params, opt_state,
                                                     batch, jnp.int32(step))
            loss = float(metrics["loss"])
        losses.append(loss)
        dt = time.perf_counter() - t0
        if history is not None:
            history.append({"step": step, "loss": loss, "step_s": dt,
                            "grad_norm": float(metrics["grad_norm"])})
        obs.metrics.train_step(step, metrics, step_s=dt)
        hb.beat(0)
        straggler.observe([dt])
        if gs is not None and float(metrics.get("guard_bad", 0.0)):
            action = gs.observe(True)
            obs.events.emit("train", f"guard:{action or 'skip'}", step=step,
                            streak=gs.bad_streak)
            print(f"[train] step={step} non-finite step dropped "
                  f"(streak={gs.bad_streak}, action={action})", flush=True)
            if action == "rollback":
                # In-graph skip+clip did not stop the streak: restore the
                # last committed checkpoint (fresh init when none exists).
                CKPT.wait()
                ckpt_steps = CKPT.latest_steps(args.ckpt_dir) \
                    if args.ckpt_dir else []
                plan = make_guard_restart_plan(gs, ckpt_steps)
                print(f"[train] {plan.note}", flush=True)
                if ckpt_steps:
                    _, restored = CKPT.restore(args.ckpt_dir)
                    params = jax.tree.map(jnp.asarray, restored["params"])
                    opt_state = jax.tree.map(jnp.asarray, restored["opt"])
                else:
                    params = model.init(jax.random.PRNGKey(args.seed))
                    opt_state = adamw.init_state(params)
                opt_state = _with_guard_streak(opt_state, True)
                gs.rolled_back()
        elif gs is not None:
            gs.observe(False)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"[train] step={step} loss={loss:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} {dt*1e3:.0f}ms", flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            CKPT.save(args.ckpt_dir, step,
                      {"params": params, "opt": opt_state}, blocking=True)
    if args.ckpt_dir:
        CKPT.save(args.ckpt_dir, end_step - 1,
                  {"params": params, "opt": opt_state}, blocking=True)
    CKPT.wait()                       # join any async write before exit
    if gs is not None and gs.total_bad:
        print(f"[train] guard: {gs.total_bad} non-finite steps dropped, "
              f"{gs.rollbacks} rollbacks")
    if obs.enabled():
        rep = obs.finalize()
        print(f"[train] obs: {rep['events_total']} events "
              f"{rep['events_by_kind']} trace={rep['trace_file']} "
              f"metrics={rep['metrics']['lines']} lines")
        if not rep["consistent"]:
            raise SystemExit("[train] telemetry divergence: legacy counters "
                             "disagree with the bus-backed views: "
                             + "; ".join(rep["divergences"]))
    print(f"[train] done: first_loss={losses[0]:.4f} "
          f"last_loss={losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
