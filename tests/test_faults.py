"""Fault-injection harness and the recovery code it drives.

Covers the config-armed injector (ft/inject.py), engine failures in the
conv dispatch (core/conv.py: they propagate, no engine stands in), the
in-graph numerical guard in train/train_step.py, the loop-side GuardState
escalation ladder, async-checkpoint exception capture,
restore-with-fallback over corrupt checkpoints, heartbeat grace, and serve
deadlines.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import conv
from repro.core.config import config
from repro.core.conv import conv2d, dispatch_events
from repro.ckpt import checkpoint as CKPT
from repro.ft import inject
from repro.ft.failures import (GuardState, HeartbeatTable,
                               make_guard_restart_plan)
from repro.ft.inject import InjectedFault, parse_fault_spec
from repro.optim import adamw
from repro.train import train_step as TS


@pytest.fixture(autouse=True)
def _disarmed():
    """Every test starts and ends with the injector disarmed and every
    introspection surface clean (``obs.reset_all`` covers the dispatch/
    plan/fault counters and the obs bus)."""
    from repro import obs
    saved = config.snapshot()
    config.update(fault_spec=None)
    obs.reset_all()
    yield
    config.update(**saved)
    config.update(fault_spec=None)
    obs.reset_all()


def _x(b=2):
    return jnp.asarray(np.random.RandomState(0).randn(b, 3, 16, 16),
                       jnp.float32)


def _w():
    return jnp.asarray(np.random.RandomState(1).randn(8, 3, 3, 3) * 0.1,
                       jnp.float32)


# ---------------------------------------------------------------------------
# Spec grammar
# ---------------------------------------------------------------------------

class TestFaultSpec:
    def test_parse_full_grammar(self):
        rules = parse_fault_spec(
            "serve.*:raise@step3;grad.values:nan@5;ckpt.write:raise~p0.5")
        assert [r.action for r in rules] == ["raise", "nan", "raise"]
        assert rules[0].step == 3 and rules[1].step == 5
        assert rules[2].step is None and rules[2].prob == 0.5

    def test_unknown_site_rejected(self):
        with pytest.raises(ValueError, match="match"):
            parse_fault_spec("nonexistent.site:raise")

    def test_unknown_action_rejected(self):
        with pytest.raises(ValueError, match="action"):
            parse_fault_spec("ckpt.*:explode")

    def test_config_validates_before_storing(self):
        with pytest.raises(ValueError):
            config.update(fault_spec="bogus.site:raise")
        assert config.fault_spec is None

    def test_config_arms_and_disarms_injector(self):
        config.update(fault_spec="ckpt.write:raise")
        assert inject.armed_rules()
        config.update(fault_spec=None)
        assert not inject.armed_rules()


# ---------------------------------------------------------------------------
# Zero overhead / zero leak when disarmed
# ---------------------------------------------------------------------------

class TestDisarmed:
    def test_fault_point_is_identity(self):
        tree = {"a": jnp.ones(3)}
        assert inject.fault_point("grad.values", value=tree) is tree
        assert inject.fault_point("ckpt.write") is None
        assert inject.fired_events() == []

    def test_unknown_site_only_checked_when_armed(self):
        # Disarmed: the first-line bailout means no validation cost at all.
        assert inject.fault_point("not.a.site", value=1) == 1
        config.update(fault_spec="ckpt.write:raise")
        with pytest.raises(ValueError, match="unregistered fault site"):
            inject.fault_point("not.a.site")


# ---------------------------------------------------------------------------
# An engine that raises is surfaced, never replaced
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pass_name", ["forward", "input_grad",
                                       "weight_grad"])
@pytest.mark.parametrize("engine", ["pallas", "bp_phase", "lax"])
def test_engine_failure_propagates(engine, pass_name, monkeypatch):
    """A pass whose engine raises propagates the exception out of
    ``conv2d`` (forward) or its VJP (the two gradients).  No other engine
    serves the pass: every dispatch key and every policy decision recorded
    names the requested engine, and the failed pass records neither."""
    def broken(*a, **k):
        raise RuntimeError(f"{engine} {pass_name} down")

    monkeypatch.setitem(conv.ENGINES, engine, dataclasses.replace(
        conv.ENGINES[engine], **{pass_name: broken}))
    x, w = _x(), _w()

    def loss(x, w):
        return jnp.sum(conv2d(x, w, stride=2, padding=1, policy=engine) ** 2)

    with pytest.raises(RuntimeError, match=f"{engine} {pass_name} down"):
        jax.grad(loss, argnums=(0, 1))(x, w)
    events = dispatch_events()
    assert all(k.split(":")[1] == engine for k in events), events
    assert not any(k.startswith(f"{pass_name}:") for k in events), events
    decisions = conv.policy_decisions()
    assert all(d["engine"] == engine for d in decisions), decisions
    assert pass_name not in {d["pass"] for d in decisions}, decisions
    assert conv.runtime_failures() == []


# ---------------------------------------------------------------------------
# Numerical guard in the train step
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _ToyCfg:
    name: str = "toy"
    conv_policy: str = None
    conv_mode: str = None


def _toy_loss(params, batch, cfg):
    loss = jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)
    return loss, {"loss": loss}


def _toy_setup():
    params = {"w": jnp.ones((4, 2))}
    opt = adamw.init_state(params)
    good = {"x": jnp.ones((8, 4)), "y": jnp.zeros((8, 2))}
    bad = {"x": jnp.full((8, 4), jnp.nan), "y": jnp.zeros((8, 2))}
    return params, opt, good, bad


class TestTrainGuard:
    def test_guarded_step_matches_unguarded_when_finite(self):
        cfg, opt_cfg = _ToyCfg(), adamw.AdamWConfig(peak_lr=0.1)
        params, opt, good, _ = _toy_setup()
        plain = TS.make_train_step(cfg, opt_cfg, loss=_toy_loss)
        guarded = TS.make_train_step(cfg, opt_cfg, loss=_toy_loss,
                                     guard=True)
        p1, _, m1 = plain(params, opt, good, 0)
        p2, _, m2 = guarded(params, opt, good, 0)
        np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]))
        assert float(m2["guard_bad"]) == 0.0

    def test_non_finite_step_skipped(self):
        cfg, opt_cfg = _ToyCfg(), adamw.AdamWConfig(peak_lr=0.1)
        params, opt, _, bad = _toy_setup()
        guarded = TS.make_train_step(cfg, opt_cfg, loss=_toy_loss,
                                     guard=True)
        p, o, m = guarded(params, opt, bad, 0)
        np.testing.assert_array_equal(np.asarray(p["w"]),
                                      np.asarray(params["w"]))
        assert float(m["guard_bad"]) == 1.0
        assert float(m["guard_streak"]) == 1.0
        assert int(o["step"]) == 0          # optimizer clock did not tick

    def test_streak_engages_clip_then_resets(self):
        cfg, opt_cfg = _ToyCfg(), adamw.AdamWConfig(peak_lr=0.1)
        params, opt, good, bad = _toy_setup()
        guarded = TS.make_train_step(
            cfg, opt_cfg, loss=_toy_loss,
            guard=TS.GuardConfig(clip_after=2, clip_norm=0.5))
        p, o = params, opt
        for step in range(2):
            p, o, m = guarded(p, o, bad, step)
        assert float(m["guard_streak"]) == 2.0
        p2, o2, m2 = guarded(p, o, good, 2)   # recovery step: clip engaged
        assert float(m2["guard_clipped"]) == 1.0
        assert float(m2["guard_streak"]) == 0.0
        assert np.isfinite(np.asarray(p2["w"])).all()

    def test_in_graph_nan_injection_under_jit(self):
        cfg, opt_cfg = _ToyCfg(), adamw.AdamWConfig(peak_lr=0.1)
        params, opt, good, _ = _toy_setup()
        config.update(fault_spec="grad.values:nan@step2")
        step_fn = jax.jit(TS.make_train_step(cfg, opt_cfg, loss=_toy_loss,
                                             guard=True))
        p, o = params, opt
        bad_mask = []
        for step in range(4):
            p, o, m = step_fn(p, o, good, step)
            bad_mask.append(int(m["guard_bad"]))
        assert bad_mask == [0, 0, 1, 0]
        assert np.isfinite(np.asarray(p["w"])).all()


# ---------------------------------------------------------------------------
# GuardState escalation ladder
# ---------------------------------------------------------------------------

class TestGuardState:
    def test_ladder(self):
        gs = GuardState(clip_after=2, rollback_after=4)
        assert gs.observe(False) == "ok"
        assert gs.observe(True) == "skip"
        assert gs.observe(True) == "clip"
        assert gs.observe(True) == "clip"
        assert gs.observe(True) == "rollback"
        gs.rolled_back()
        assert gs.bad_streak == 0 and gs.rollbacks == 1 and gs.total_bad == 4
        assert gs.observe(False) == "ok"

    def test_guard_restart_plan(self):
        gs = GuardState()
        for _ in range(4):
            gs.observe(True)
        plan = make_guard_restart_plan(gs, [10, 20, 30])
        assert plan.failed_workers == []
        assert plan.resume_step == 30
        assert "numerical guard" in plan.note
        assert make_guard_restart_plan(gs, []).resume_step == 0


# ---------------------------------------------------------------------------
# Checkpoint: async failure capture + corruption fallback
# ---------------------------------------------------------------------------

class TestCheckpointResilience:
    def test_async_write_failure_reraised_on_wait(self, tmp_path):
        config.update(fault_spec="ckpt.write:raise")
        CKPT.save(str(tmp_path), 1, {"x": np.ones(2)}, blocking=False)
        with pytest.raises(InjectedFault):
            CKPT.wait()
        config.update(fault_spec=None)
        CKPT.save(str(tmp_path), 2, {"x": np.ones(2)}, blocking=False)
        CKPT.wait()                   # clean second write, nothing pending
        assert CKPT.latest_steps(str(tmp_path)) == [2]

    def test_async_write_failure_reraised_on_next_save(self, tmp_path):
        config.update(fault_spec="ckpt.write:raise")
        CKPT.save(str(tmp_path), 1, {"x": np.ones(2)}, blocking=False)
        config.update(fault_spec=None)
        with pytest.raises(InjectedFault):
            CKPT.save(str(tmp_path), 2, {"x": np.ones(2)})
        CKPT.wait()

    def test_truncated_array_falls_back_to_older_step(self, tmp_path):
        CKPT.reset_skipped_checkpoints()
        CKPT.save(str(tmp_path), 1, {"x": np.full(2, 1.0)})
        CKPT.save(str(tmp_path), 2, {"x": np.full(2, 2.0)})
        (tmp_path / "step_00000002" / "arr_00000.npy").write_bytes(
            b"\x93NUMPY junk")
        step, tree = CKPT.restore(str(tmp_path))
        assert step == 1 and tree["x"][0] == 1.0
        assert any(s["checkpoint"] == "step_00000002"
                   for s in CKPT.skipped_checkpoints())

    def test_hash_mismatch_falls_back_with_reason(self, tmp_path):
        CKPT.reset_skipped_checkpoints()
        CKPT.save(str(tmp_path), 1, {"x": np.full(2, 1.0)})
        CKPT.save(str(tmp_path), 2, {"x": np.full(2, 2.0)})
        target = tmp_path / "step_00000002" / "arr_00000.npy"
        arr = np.load(target)
        arr[0] = 999.0
        np.save(target, arr)
        step, tree = CKPT.restore(str(tmp_path))
        assert step == 1
        assert any("corruption" in s["reason"]
                   for s in CKPT.skipped_checkpoints())

    def test_missing_commit_skipped_with_reason(self, tmp_path):
        CKPT.reset_skipped_checkpoints()
        CKPT.save(str(tmp_path), 1, {"x": np.ones(2)})
        torn = tmp_path / "step_00000002"
        torn.mkdir()
        (torn / "manifest.json").write_text("{}")
        assert CKPT.latest_steps(str(tmp_path)) == [1]
        assert any("COMMIT" in s["reason"]
                   for s in CKPT.skipped_checkpoints())

    def test_explicit_step_never_falls_back(self, tmp_path):
        CKPT.save(str(tmp_path), 1, {"x": np.ones(2)})
        CKPT.save(str(tmp_path), 2, {"x": np.ones(2)})
        (tmp_path / "step_00000002" / "arr_00000.npy").write_bytes(b"junk")
        with pytest.raises(IOError, match="not loadable"):
            CKPT.restore(str(tmp_path), step=2)

    def test_foreign_dir_names_ignored(self, tmp_path):
        CKPT.save(str(tmp_path), 1, {"x": np.ones(2)})
        (tmp_path / "step_00000009.tmp").mkdir()     # stale staging dir
        (tmp_path / "step_notanumber").mkdir()
        assert CKPT.latest_steps(str(tmp_path)) == [1]


# ---------------------------------------------------------------------------
# Heartbeat grace period
# ---------------------------------------------------------------------------

class TestHeartbeatGrace:
    def test_never_beaten_gets_grace_period(self):
        hb = HeartbeatTable(n_workers=2, timeout_s=5.0, t0=0.0)
        assert hb.dead(now=3.0) == []           # inside the grace window
        assert hb.dead(now=6.0) == [0, 1]       # grace expired, never beat

    def test_beat_extends_deadline(self):
        hb = HeartbeatTable(n_workers=2, timeout_s=5.0, t0=0.0)
        hb.beat(0, t=4.0)
        assert hb.dead(now=6.0) == [1]
        assert hb.dead(now=10.0) == [0, 1]


# ---------------------------------------------------------------------------
# Site coverage: every registered fault point is actually wired
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_all_known_sites_are_exercised(tmp_path):
    """Arm a never-firing rule (so every fault_point call registers its
    site without disturbing behaviour), drive every failure domain once,
    and require full coverage of KNOWN_SITES -- a new site that is
    registered but never wired (or wired but not registered) fails here."""
    saved = config.snapshot()
    try:
        config.update(fault_spec="*:raise@step999999")
        inject.reset_events()
        inject.set_step(0)
        # checkpoint write + read:
        CKPT.save(str(tmp_path / "ck"), 0, {"x": np.ones(2)})
        CKPT.restore(str(tmp_path / "ck"))
        # grad.values (in-graph, via the guarded train step):
        params, opt, good, _ = _toy_setup()
        TS.make_train_step(_ToyCfg(), adamw.AdamWConfig(),
                           loss=_toy_loss, guard=True)(params, opt, good, 0)
        # serve.prefill + serve.decode (continuous engine, one request):
        from repro.configs import get_smoke_config
        from repro.models import model as M
        from repro.serve.continuous import ContinuousEngine
        from repro.serve.request import Request as ServeRequest
        scfg = get_smoke_config("smollm_360m")
        eng = ContinuousEngine(
            scfg, M.build_model(scfg).init(jax.random.PRNGKey(0)),
            max_batch=1, max_len=8)
        eng.submit(ServeRequest(rid=0, prompt=[1, 2], max_new=2))
        eng.run()
        missing = set(inject.KNOWN_SITES) - inject.seen_sites()
        assert not missing, f"registered but never exercised: {missing}"
    finally:
        config.update(**saved)


# ---------------------------------------------------------------------------
# Serve deadlines
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serve_deadline_times_out_single_request():
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serve.engine import Engine, Request

    class Clock:
        t = 0.0

        def __call__(self):
            self.t += 1.0
            return self.t

    cfg = get_smoke_config("smollm_360m")
    params = M.build_model(cfg).init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_batch=2, max_len=24, clock=Clock())
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new=6, deadline_s=3.0))
    eng.submit(Request(rid=1, prompt=[1, 2, 3], max_new=6))
    done = {r.rid: r for r in eng.run()}
    assert done[0].status == "timed_out"
    assert len(done[0].out) < 6            # kept partial output
    assert done[1].status == "ok" and len(done[1].out) == 6
    summary = eng.run_summary()
    assert summary["completed"] == 1
    assert summary["timed_out"] == 1
    assert summary["waves"] == 1
