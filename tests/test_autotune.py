"""Measured autotuning + persistent plan cache (kernels/autotune.py).

The timing harness and cache run in interpret mode here -- the timings
are CPU-interpreter numbers, but every code path (candidate racing,
persistence, revalidation, annotation) is the one a TPU run takes.
"""

import json

import numpy as np
import pytest

from repro.core.config import config
from repro.core.im2col_ref import ConvDims, conv2d_lax, conv_grads_lax
from repro.kernels import autotune, ops

import jax.numpy as jnp

D = ConvDims(B=1, C=4, H_i=8, W_i=8, N=4, K_h=3, K_w=3, S=2, P_h=1, P_w=1)


@pytest.fixture(autouse=True)
def _tuned(tmp_path):
    """Every test runs with a private plan cache and autotune=measure;
    config (and the caches keyed on it) restored afterwards."""
    saved = config.snapshot()
    config.update(autotune="measure", autotune_top_k=3, autotune_reps=1,
                  plan_cache_dir=str(tmp_path))
    yield tmp_path
    config.update(**saved)


def _fresh():
    ops.clear_tile_plan_cache()
    autotune.clear_memo()
    ops.reset_plan_events()


# ---------------------------------------------------------------------------
# Candidate shortlist
# ---------------------------------------------------------------------------

def test_candidates_head_is_the_analytic_winner():
    with config.override(autotune="off"):
        analytic = ops.forward_plan(D)
    cands = ops.plan_candidates("forward", D, k=3)
    assert 1 <= len(cands) <= 3
    assert cands[0].tile_key == analytic.tile_key
    assert all(c.fits and c.bytes_needed <= config.vmem_budget_bytes
               for c in cands)
    keys = [c.tile_key for c in cands]
    assert len(set(keys)) == len(keys), f"duplicate candidates: {keys}"


def test_candidates_cover_all_roles():
    for role in ops.PLAN_ROLES:
        cands = ops.plan_candidates(role, D, k=2)
        assert cands, role
        if role == "input_grad":
            assert all(isinstance(c, ops.PhasePlan) for c in cands)
        else:
            assert all(isinstance(c, ops.TilePlan) for c in cands)


def test_unknown_role_raises():
    with pytest.raises(ValueError, match="unknown plan role"):
        ops.plan_candidates("sideways", D)
    with pytest.raises(ValueError, match="unknown plan role"):
        ops.plan_from_tile("sideways", D, None, (1, 1, 1, 1))


# ---------------------------------------------------------------------------
# Measurement picks a winner from the shortlist
# ---------------------------------------------------------------------------

def test_measure_picks_a_timed_candidate():
    _fresh()
    plan = ops.forward_plan(D)
    assert plan.autotuned and plan.cache == "miss"
    assert plan.measured_us > 0
    cands = ops.plan_candidates("forward", D)
    assert plan.candidates_timed == len(cands)
    assert plan.tile_key in {c.tile_key for c in cands}
    ev = ops.plan_events()
    assert ev.get("forward_autotune_miss") == 1
    assert ev.get("forward_pallas") == 1       # analytic accounting intact


def test_all_three_planners_route_through_the_tuner():
    _fresh()
    assert ops.forward_plan(D).autotuned
    assert ops.weight_grad_plan(D).autotuned
    ig = ops.input_grad_plan(D)
    assert ig is not None and ig.tile.autotuned and ig.tile.measured_us > 0


def test_infeasible_plans_never_tune():
    """fits=False (forward/wgrad) and None (input_grad) pass through the
    tuner untouched -- there is nothing to race."""
    _fresh()
    with config.override(vmem_budget_bytes=1):
        fp = ops.forward_plan(D)
        assert not fp.fits and not fp.autotuned and fp.cache == ""
        assert ops.input_grad_plan(D) is None
    assert not ops.plan_events().get("forward_autotune_miss")


def test_measure_plan_times_any_candidate():
    for role in ops.PLAN_ROLES:
        cand = ops.plan_candidates(role, D, k=1)[0]
        us = autotune.measure_plan(role, D, cand, reps=1)
        assert np.isfinite(us) and us > 0, (role, us)


# ---------------------------------------------------------------------------
# Persistent cache round-trip
# ---------------------------------------------------------------------------

def test_persistent_round_trip(tmp_path):
    _fresh()
    first = ops.forward_plan(D)
    assert first.cache == "miss"
    path = autotune.cache_path()
    assert path.startswith(str(tmp_path))
    store = json.load(open(path))
    assert store["schema"] == autotune.CACHE_SCHEMA
    assert len(store["entries"]) == 1
    # New process equivalent: drop the in-process caches, keep the disk.
    _fresh()
    second = ops.forward_plan(D)
    assert second.cache == "hit" and second.autotuned
    assert second.tile_key == first.tile_key
    assert second.measured_us == pytest.approx(first.measured_us)
    assert second.candidates_timed == first.candidates_timed
    assert ops.plan_events().get("forward_autotune_hit") == 1


def test_cached_mode_serves_winners_without_timing():
    _fresh()
    ops.forward_plan(D)                        # measure + persist
    _fresh()
    with config.override(autotune="cached"):
        hit = ops.forward_plan(D)
        assert hit.cache == "hit" and hit.autotuned
        # A shape never measured: analytic plan, annotated as a miss.
        other = ConvDims(B=1, C=4, H_i=10, W_i=10, N=4, K_h=3, K_w=3, S=2,
                         P_h=1, P_w=1)
        miss = ops.forward_plan(other)
        assert miss.cache == "miss" and not miss.autotuned
        assert not (ops.plan_events().get("forward_autotune_stale") or 0)
    # cached mode must not have grown the store.
    assert len(autotune._load_store()["entries"]) == 1


def test_off_mode_bypasses_the_tuner_entirely():
    _fresh()
    with config.override(autotune="off"):
        plan = ops.forward_plan(D)
        assert not plan.autotuned and plan.cache == ""
        assert "autotune" not in ops.plan_report(D)["forward"]


def test_cache_key_separates_roles_budgets_and_dims():
    k1 = autotune.plan_key("forward", D, 1 << 20)
    assert k1 != autotune.plan_key("weight_grad", D, 1 << 20)
    assert k1 != autotune.plan_key("forward", D, 1 << 21)
    d2 = ConvDims(B=1, C=4, H_i=10, W_i=8, N=4, K_h=3, K_w=3, S=2,
                  P_h=1, P_w=1)
    assert k1 != autotune.plan_key("forward", d2, 1 << 20)


# ---------------------------------------------------------------------------
# Corrupt / stale tolerance
# ---------------------------------------------------------------------------

def test_corrupt_cache_file_re_tunes():
    _fresh()
    ops.forward_plan(D)
    with open(autotune.cache_path(), "w") as f:
        f.write("{not json")
    _fresh()
    plan = ops.forward_plan(D)                 # no crash: treated as cold
    assert plan.autotuned and plan.cache == "miss"
    store = json.load(open(autotune.cache_path()))  # and re-persisted
    assert store["entries"]


def test_wrong_schema_is_a_cold_cache():
    _fresh()
    ops.forward_plan(D)
    store = json.load(open(autotune.cache_path()))
    store["schema"] = autotune.CACHE_SCHEMA + 1
    with open(autotune.cache_path(), "w") as f:
        json.dump(store, f)
    _fresh()
    assert ops.forward_plan(D).cache == "miss"


@pytest.mark.parametrize("bad_tile", [
    [999, 999, 3, 3],          # does not fit the geometry
    [0, 0, 0, 0],              # degenerate
    ["x", 1, 1, 1],            # garbage types
    [],                        # wrong arity
])
def test_stale_entry_re_tunes(bad_tile):
    _fresh()
    ops.forward_plan(D)
    store = json.load(open(autotune.cache_path()))
    (key,) = store["entries"]
    store["entries"][key]["tile"] = bad_tile
    with open(autotune.cache_path(), "w") as f:
        json.dump(store, f)
    _fresh()
    plan = ops.forward_plan(D)
    assert plan.autotuned and plan.cache == "stale"
    assert ops.plan_events().get("forward_autotune_stale") == 1
    # The re-tuned winner replaced the bad entry.
    healed = json.load(open(autotune.cache_path()))
    assert healed["entries"][key]["tile"] == list(plan.tile_key)


def test_budget_shrink_invalidates_persisted_plans(monkeypatch):
    """A winner tuned under a big budget must not be served under a small
    one: plan_from_tile revalidates bytes_needed <= budget.  The timer is
    fixed so that the candidate with the largest footprint wins; a budget
    one byte below it then always leaves a smaller candidate that fits,
    whichever tile a real timing would have picked."""
    monkeypatch.setattr(autotune, "measure_plan",
                        lambda role, d, plan, **_: 1e9 / plan.bytes_needed)
    _fresh()
    big = ops.forward_plan(D)
    assert big.bytes_needed == max(
        c.bytes_needed for c in ops.plan_candidates("forward", D))
    _fresh()
    with config.override(vmem_budget_bytes=big.bytes_needed - 1):
        plan = ops.forward_plan(D)
        assert plan.fits      # re-planned under the smaller budget
        assert plan.bytes_needed < big.bytes_needed


def test_plan_seconds_count_cold_plans_only():
    """The planner's clock rises when a plan is searched and tuned, not
    when the memo serves it, and resets with the plan events."""
    _fresh()
    assert ops.plan_seconds() == 0.0
    ops.forward_plan(D)
    cold = ops.plan_seconds()
    assert cold > 0
    ops.forward_plan(D)
    assert ops.plan_seconds() == cold
    ops.reset_plan_events()
    assert ops.plan_seconds() == 0.0


def test_plan_seconds_count_analytic_cache_misses():
    with config.override(autotune="off"):
        _fresh()
        ops.weight_grad_plan(D)
        cold = ops.plan_seconds()
        assert cold > 0
        ops.weight_grad_plan(D)                  # the lru_cache holds it
        assert ops.plan_seconds() == cold
        ops.clear_tile_plan_cache()
        ops.weight_grad_plan(D)                  # searched again
        assert ops.plan_seconds() > cold


# ---------------------------------------------------------------------------
# Reporting surface
# ---------------------------------------------------------------------------

def test_plan_report_carries_autotune_fields():
    _fresh()
    rep = ops.plan_report(D)
    for role in ("forward", "weight_grad", "input_grad"):
        at = rep[role]["autotune"]
        assert at["autotuned"] is True
        assert at["cache"] in ("hit", "miss", "stale")
        assert at["measured_us"] > 0
        assert at["candidates_timed"] >= 1
    # And through the shape-level wrapper (the public conv surface): after
    # dropping the in-process caches the persisted winners serve as hits.
    ops.clear_tile_plan_cache()
    autotune.clear_memo()
    from repro.core.conv import conv_plan_report
    rep2 = conv_plan_report((D.B, D.C, D.H_i, D.W_i),
                            (D.N, D.C, D.K_h, D.K_w), 2, 1)
    assert rep2["forward"]["autotune"]["cache"] == "hit"


def test_auto_engine_resolver_consults_tuned_plans():
    """resolve_engine sees the tuned planners exactly as the analytic
    ones: a tuned-fits shape resolves every pass to pallas."""
    from repro.core.conv import resolve_policy
    _fresh()
    res = resolve_policy(D, "auto")
    assert all(v["engine"] == "pallas" for v in res.values()), res
    ev = ops.plan_events()
    assert any("_autotune_" in k for k in ev), ev


# ---------------------------------------------------------------------------
# Gradient-equivalence oracle: tuned plans compute the same math
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [
    D,
    ConvDims(B=2, C=8, H_i=12, W_i=10, N=8, K_h=3, K_w=3, S=2, S_w=3,
             P_h=1, P_w=1),
    ConvDims(B=1, C=4, H_i=12, W_i=12, N=4, K_h=5, K_w=5, S=2,
             P_h=2, P_w=2, D_h=2, D_w=2),
])
def test_autotuned_plans_match_lax_gradients(d):
    _fresh()
    r = np.random.RandomState(7)
    x = jnp.asarray(r.randn(d.B, d.C, d.H_i, d.W_i), jnp.float32)
    w = jnp.asarray(r.randn(d.N, d.C, d.k_taps_h, d.k_taps_w), jnp.float32)
    dy = jnp.asarray(r.randn(d.B, d.N, d.H_o, d.W_o), jnp.float32)
    from repro.core.im2col_ref import zero_insert
    w_eff = zero_insert(w, (d.D_h, d.D_w)) if d.has_dilation else w
    want_y = conv2d_lax(x, w_eff, d)
    want_di, want_dw = conv_grads_lax(x, w_eff, dy, d)
    y = ops.conv2d_forward(x, w, d)
    di = ops.conv2d_input_grad(dy, w, d)
    dw = ops.conv2d_weight_grad(x, dy, d)
    assert ops.forward_plan(d).autotuned        # the tuned path really ran
    np.testing.assert_allclose(y, want_y, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(di, want_di, rtol=5e-4, atol=5e-4)
    if d.has_dilation:
        want_dw = want_dw[..., ::d.D_h, ::d.D_w]
    np.testing.assert_allclose(dw, want_dw, rtol=5e-3, atol=5e-3)


def test_every_candidate_computes_identical_results():
    """The racing itself is safe: every shortlisted plan produces the same
    numbers (only the dispatch geometry differs)."""
    r = np.random.RandomState(3)
    x = jnp.asarray(r.randn(D.B, D.C, D.H_i, D.W_i), jnp.float32)
    w = jnp.asarray(r.randn(D.N, D.C, D.K_h, D.K_w), jnp.float32)
    dy = jnp.asarray(r.randn(D.B, D.N, D.H_o, D.W_o), jnp.float32)
    ref_y = ref_di = ref_dw = None
    for fwd, ig, wg in zip(ops.plan_candidates("forward", D, k=3),
                           ops.plan_candidates("input_grad", D, k=3),
                           ops.plan_candidates("weight_grad", D, k=3)):
        y = ops.conv2d_forward(x, w, D, plan=fwd)
        di = ops.conv2d_input_grad(dy, w, D, plan=ig)
        dw = ops.conv2d_weight_grad(x, dy, D, plan=wg)
        if ref_y is None:
            ref_y, ref_di, ref_dw = y, di, dw
            continue
        np.testing.assert_allclose(y, ref_y, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(di, ref_di, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(dw, ref_dw, rtol=1e-4, atol=1e-4)
