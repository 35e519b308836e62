"""Chaos drill: train the CNN while the fault injector kills the Pallas
engine mid-run and poisons one step's gradients -- and assert the stack
degrades EXACTLY as designed instead of merely surviving.

    PYTHONPATH=src python examples/train_chaos.py

Timeline (fault spec ``pallas.*:raise@step3;grad.values:nan@step5``, with
``QUARANTINE_PROBE_AFTER`` lowered to 2 so the whole arc fits a short run):

    step 3   every Pallas launch raises ``InjectedFault``; the dispatch
             layer re-runs each pass on the fallback chain
             (``pass:pallas->bp_phase`` events) and quarantines pallas for
             each failing (pass, geometry)
    steps 4-5  quarantined: pallas is skipped outright
             (``pass:pallas:quarantined``)
    step 5   the gradient VALUES are NaN-poisoned; the loop's numerical
             guard drops the update (params untouched)
    step 6   recovery probe: pallas is retried, succeeds, quarantine is
             lifted (``pass:pallas:probe`` + ``pass:pallas:recovered``)
    then     disarm and run two more steps -- zero faults may fire
             (the injector is config-gated, not baked into the trace)

The run must complete with a finite, decreasing loss; every expected event
count is asserted exactly (computed from ``resolve_engine``, so a planner
that routes a layer off pallas does not break the drill).  This is the CI
``chaos`` lane's workload.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, "src")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

from train_cnn_bp import init_params, make_model, synthetic_task

from repro import obs
from repro.core import conv
from repro.core.compile_cache import enable_compile_cache
from repro.core.config import config
from repro.core.convspec import ConvSpec
from repro.ft import inject
from repro.ft.failures import GuardState

FAULT_SPEC = "pallas.*:raise@step3;grad.values:nan@step5"
PASSES = ("forward", "input_grad", "weight_grad")


def expected_pallas_passes(batch):
    """How many (pass, layer) pairs resolve to pallas for the CNN's three
    conv layers -- computed through the real resolver so the drill's
    assertions track the planner, not a hardcoded guess."""
    layers = [
        ((batch, 3, 16, 16), (16, 3, 3, 3), ConvSpec.make(stride=2,
                                                          padding=1)),
        ((batch, 16, 8, 8), (16, 1, 3, 3), ConvSpec.make(stride=1, padding=1,
                                                         groups=16)),
        ((batch, 16, 8, 8), (32, 16, 3, 3), ConvSpec.make(stride=2,
                                                          padding=1)),
    ]
    n = {p: 0 for p in PASSES}
    for xs, ws, spec in layers:
        d = conv.spec_dims(xs, ws, spec)
        for p in PASSES:
            if conv.resolve_engine("pallas", p, d)[0] == "pallas":
                n[p] += 1
    return n


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=14)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable telemetry and write a Perfetto trace_event "
                         "JSON; the drill then also asserts the degradation "
                         "arc is on the obs bus and the conv spans carry "
                         "skip_ratio/bytes_moved annotations")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="enable telemetry and stream per-step metrics "
                         "JSONL to PATH")
    args = ap.parse_args()
    enable_compile_cache()
    assert args.steps >= 8, "the fault timeline needs at least 8 steps"

    conv.QUARANTINE_PROBE_AFTER = 2   # arc: fail@3, skip@4-5, probe@6
    config.update(fault_spec=FAULT_SPEC, fault_seed=0,
                  **{k: v for k, v in
                     (("telemetry", bool(args.trace or args.metrics) or None),
                      ("trace_path", args.trace),
                      ("metrics_path", args.metrics))
                     if v is not None})
    # One reset covering EVERY introspection surface (dispatch events,
    # policy decisions, quarantine, fired faults, the obs bus/trace).
    obs.reset_all()

    n_pallas = expected_pallas_passes(args.batch)
    n_total = sum(n_pallas.values())
    assert n_total > 0, "no layer resolves to pallas; the drill is vacuous"
    print(f"[chaos] armed: {FAULT_SPEC!r}; pallas passes per step: "
          f"{n_pallas}")

    rng = np.random.RandomState(0)
    _, loss_fn = make_model("pallas")
    params = init_params()
    # EAGER on purpose: dispatch happens at trace time, so a jitted step
    # would fault once at compile and never again -- eager re-dispatches
    # every step, which is what makes the quarantine/probe arc observable.
    grad_fn = jax.value_and_grad(loss_fn)
    gs = GuardState(clip_after=2, rollback_after=4)
    losses = []
    for step in range(args.steps):
        inject.set_step(step)
        x, y = synthetic_task(rng, args.batch)
        loss, g = grad_fn(params, x, y)
        g = inject.fault_point("grad.values", value=g)
        gnorm = float(jnp.sqrt(sum(jnp.sum(jnp.square(v))
                                   for v in jax.tree.leaves(g))))
        bad = not (np.isfinite(float(loss)) and np.isfinite(gnorm))
        action = gs.observe(bad)
        if bad:
            print(f"[chaos] step={step} non-finite gradients dropped "
                  f"(action={action})")
        else:
            params = jax.tree.map(lambda p, gg: p - args.lr * gg, params, g)
            losses.append(float(loss))
        obs.metrics.train_step(step, {"loss": float(loss),
                                      "grad_norm": gnorm,
                                      "guard_bad": float(bad)})
        if step % 2 == 0 or step == args.steps - 1:
            print(f"[chaos] step={step:3d} loss={float(loss):.4f}")

    ev = conv.dispatch_events()
    fired = inject.fired_events()

    # --- the degradation arc, exactly -------------------------------------
    degrade = {k: v for k, v in ev.items() if "->" in k}
    assert sum(degrade.values()) == n_total, \
        f"expected {n_total} runtime failure edges, got {degrade}"
    for p in PASSES:
        if n_pallas[p] == 0:
            continue
        q = ev.get(f"{p}:pallas:quarantined", 0)
        assert q == 2 * n_pallas[p], \
            f"{p}: expected {2 * n_pallas[p]} quarantined skips, got {q}"
        assert ev.get(f"{p}:pallas:probe", 0) == n_pallas[p], ev
        assert ev.get(f"{p}:pallas:recovered", 0) == n_pallas[p], ev
    assert not conv.quarantined_engines(), conv.quarantined_engines()
    raises = [f for f in fired if f["action"] == "raise"]
    nans = [f for f in fired if f["action"] == "nan"]
    assert len(raises) == n_total, (len(raises), n_total)
    assert len(nans) == 1 and nans[0]["site"] == "grad.values", nans
    assert gs.total_bad == 1 and gs.rollbacks == 0, vars(gs)
    rf = conv.runtime_failures()
    assert len(rf) == n_total and \
        all(f["exception"] == "InjectedFault" and f["survivor"] for f in rf)

    # --- the training outcome ---------------------------------------------
    assert all(np.isfinite(l) for l in losses), "non-finite loss leaked"
    half = len(losses) // 2
    assert np.mean(losses[half:]) < np.mean(losses[:half]), \
        "training made no progress through the faults"
    assert all(np.isfinite(np.asarray(v)).all()
               for v in jax.tree.leaves(params)), "non-finite params"

    # --- zero-leak when disarmed ------------------------------------------
    config.update(fault_spec=None)
    inject.reset_events()
    for step in range(2):
        x, y = synthetic_task(rng, args.batch)
        loss, g = grad_fn(params, x, y)
        params = jax.tree.map(lambda p, gg: p - args.lr * gg, params, g)
    assert inject.fired_events() == [], inject.fired_events()
    assert np.isfinite(float(loss))

    # --- the same arc must be on the obs bus ------------------------------
    if obs.enabled():
        rep = obs.finalize()
        # Every legacy counter agrees with its bus-backed view -- including
        # the degrade -> quarantined -> probe -> recovered sequence.
        assert rep["consistent"], (
            "telemetry divergence: " + "; ".join(rep["divergences"]))
        bus = obs.events.counters("dispatch")
        assert bus == conv.dispatch_events(), (bus, conv.dispatch_events())
        for p in PASSES:
            if n_pallas[p] == 0:
                continue
            for arc in (f"{p}:pallas->bp_phase", f"{p}:pallas:quarantined",
                        f"{p}:pallas:probe", f"{p}:pallas:recovered"):
                assert bus.get(arc, 0) > 0, (arc, bus)
        if args.trace:
            trace_doc = json.load(open(args.trace))
            conv_spans = [e for e in trace_doc["traceEvents"]
                          if e["ph"] == "B" and e["name"].startswith("conv:")]
            assert conv_spans, "no conv dispatch spans in the trace"
            for span in conv_spans:
                assert "skip_ratio" in span["args"] and \
                    "bytes_moved" in span["args"], span
        if args.metrics:
            lines = [json.loads(ln) for ln in open(args.metrics)]
            assert len(lines) >= args.steps and \
                all(ln["kind"] == "train_step" for ln in lines), len(lines)
        print(f"[chaos] obs ok: {rep['events_total']} bus events, "
              f"{rep['trace']['events']} trace events, "
              f"{rep['metrics']['lines']} metrics lines")

    print(f"[chaos] ok: {n_total} pallas passes degraded and recovered, "
          f"1 NaN step dropped, final loss {losses[-1]:.4f}, "
          f"zero faults when disarmed")


if __name__ == "__main__":
    main()
