"""Channel tiles sized to the VMEM budget (``ops._channel_tiles`` and the
tile search in ``ops._fitting_tiles``).

The planner chooses the channel tiles together with the spatial tile: any
multiple of 128 lanes that divides the padded channel dim, an output dim
of at most 128 lanes whole.  Of the candidates whose footprint fits the
budget it takes the one with the fewest grid steps.  The 128-lane tiles
stay among the candidates, so a layer whose channel dims are all within
128 lanes plans exactly as under the 128-lane rule, and no layer changes
route.  The kernel tests run in the Pallas interpreter on the CPU.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import paper_cnn
from repro.core import ConvTransposeSpec, transpose_dims
from repro.core import conv as C
from repro.core.config import config
from repro.core.im2col_ref import ConvDims
from repro.kernels import ops
from repro.kernels.tap_gemm import LANE

_HIGHEST = jax.lax.Precision.HIGHEST

#: ResNet-50 v1.5's stride-2 convs, (H_in, C_in, C_out, K, stride, pad).
RESNET50_STRIDED = {
    "stem": (224, 3, 64, 7, 2, 3),
    "56x56-1x1": (56, 256, 512, 1, 2, 0),
    "28x28-1x1": (28, 512, 1024, 1, 2, 0),
    "14x14-1x1": (14, 1024, 2048, 1, 2, 0),
    "56x56-3x3": (56, 128, 128, 3, 2, 1),
    "28x28-3x3": (28, 256, 256, 3, 2, 1),
    "14x14-3x3": (14, 512, 512, 3, 2, 1),
}

#: the strided layers whose channel dims all fit 128 lanes.
NARROW = {"stem", "56x56-3x3"}

#: ResNet-50's per-chip batch.
BATCH = 32

ROLES = ("forward", "weight_grad", "input_grad")


def _resnet(name: str) -> ConvDims:
    return paper_cnn.dims(RESNET50_STRIDED[name], BATCH)


def _autoencoder() -> dict[str, tuple[ConvDims, bool]]:
    """name -> (dims, transposed) of the two-level 256x256 autoencoder's
    convs at batch 8: widths 3 -> 64 -> 128 and back."""
    spec = ConvTransposeSpec.make(stride=2, padding=1, output_padding=1)
    return {
        "enc1": (C.make_dims((8, 3, 256, 256), (64, 3, 3, 3), 2, 1), False),
        "enc2": (C.make_dims((8, 64, 128, 128), (128, 64, 3, 3), 2, 1),
                 False),
        "dec1": (transpose_dims((8, 128, 64, 64), (128, 64, 3, 3), spec),
                 True),
        "dec2": (transpose_dims((8, 64, 128, 128), (64, 3, 3, 3), spec),
                 True),
    }


@pytest.fixture(autouse=True)
def _fresh_plans():
    ops.clear_tile_plan_cache()
    ops.reset_plan_events()
    yield
    ops.clear_tile_plan_cache()
    ops.reset_plan_events()


@contextlib.contextmanager
def _lane_rule(monkeypatch):
    """Plan under the 128-lane rule: each channel dim offers only its
    narrowest tile (128 lanes, or the whole dim up to 128)."""
    every = ops._channel_tiles

    def narrowest(c, contraction):
        pad, tiles = every(c, contraction)
        return pad, tiles[-1:]

    ops.clear_tile_plan_cache()
    monkeypatch.setattr(ops, "_channel_tiles", narrowest)
    try:
        yield
    finally:
        monkeypatch.undo()
        ops.clear_tile_plan_cache()


def _plan(role: str, d: ConvDims):
    return {"forward": ops.forward_plan, "weight_grad": ops.weight_grad_plan,
            "input_grad": ops.input_grad_plan}[role](d)


def _tile(plan) -> ops.TilePlan:
    return plan.tile if isinstance(plan, ops.PhasePlan) else plan


def _grid_steps(role: str, d: ConvDims, plan) -> int:
    t = _tile(plan)
    steps = (d.B * t.n_th * t.n_tw * (t.cin_pad // t.cin_tile)
             * (t.cout_pad // t.cout_tile))
    return steps * (d.s_h * d.s_w if role == "input_grad" else 1)


# ---------------------------------------------------------------------------
# The ResNet-50 strided layers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("role", ROLES)
@pytest.mark.parametrize("name", sorted(RESNET50_STRIDED))
def test_resnet_tiles_are_valid_and_cut_grid_steps(name, role, monkeypatch):
    d = _resnet(name)
    with _lane_rule(monkeypatch):
        narrow = _plan(role, d)
    plan = _plan(role, d)
    t = _tile(plan)
    assert t.fits and t.bytes_needed <= config.vmem_budget_bytes
    assert t.cin_pad % t.cin_tile == 0 and t.cin_tile % LANE == 0
    assert t.cout_pad % t.cout_tile == 0
    assert t.cout_tile % LANE == 0 or t.cout_tile == t.cout_pad <= LANE
    steps, before = _grid_steps(role, d, plan), _grid_steps(role, d, narrow)
    if name in NARROW:
        assert plan == narrow
    else:
        assert max(t.cin_tile, t.cout_tile) > LANE
        assert steps < before, (steps, before)


@pytest.mark.parametrize("name", sorted(RESNET50_STRIDED))
def test_wide_events_count_the_wide_passes(name):
    d = _resnet(name)
    for role in ROLES:
        _plan(role, d)
    events = ops.plan_events()
    for role in ROLES:
        assert events.get(f"{role}_wide", 0) == (name not in NARROW), (
            role, events)


def test_five_strided_layers_plan_wide_in_every_pass():
    for name in RESNET50_STRIDED:
        for role in ROLES:
            _plan(role, _resnet(name))
    events = ops.plan_events()
    assert {r: events.get(f"{r}_wide") for r in ROLES} == {
        "forward": 5, "weight_grad": 5, "input_grad": 5}


# ---------------------------------------------------------------------------
# Layers the change must leave alone, and routing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("role", ROLES)
@pytest.mark.parametrize("name", ["enc1", "enc2", "dec1", "dec2"])
def test_autoencoder_plans_equal_the_lane_rule(name, role, monkeypatch):
    d, _ = _autoencoder()[name]
    with _lane_rule(monkeypatch):
        narrow = _plan(role, d)
    assert _plan(role, d) == narrow
    assert not any(k.endswith("_wide") for k in ops.plan_events())


@pytest.mark.parametrize("name", [*sorted(RESNET50_STRIDED), "enc1", "enc2",
                                  "dec1", "dec2"])
def test_auto_routes_every_pass_as_under_the_lane_rule(name, monkeypatch):
    d, transposed = (_autoencoder()[name] if name in _autoencoder()
                     else (_resnet(name), False))

    def engines():
        return {p: r["engine"]
                for p, r in C.resolve_policy(d, "auto", transposed).items()}

    with _lane_rule(monkeypatch):
        before = engines()
    assert engines() == before


# ---------------------------------------------------------------------------
# Wide tiles compute the same convolution (Pallas interpreter)
# ---------------------------------------------------------------------------

#: strided layers with channel dims of 2 and 3 lane tiles.
WIDE_LAYERS = {
    "1x1s2": ConvDims(B=2, C=256, H_i=8, W_i=8, N=384, K_h=1, K_w=1, S=2,
                      P_h=0, P_w=0),
    "3x3s2": ConvDims(B=2, C=256, H_i=9, W_i=9, N=384, K_h=3, K_w=3, S=2,
                      P_h=1, P_w=1),
}


def _data(d: ConvDims, seed: int = 0):
    r = np.random.RandomState(seed)
    x = jnp.asarray(r.randn(d.B, d.C, d.H_i, d.W_i), jnp.float32)
    w = jnp.asarray(r.randn(d.N, d.C, d.K_h, d.K_w), jnp.float32)
    dy = jnp.asarray(r.randn(d.B, d.N, d.H_o, d.W_o), jnp.float32)
    return x, w, dy


def _lax(x, w, dy, d: ConvDims):
    """(y, dI, dW) from XLA's conv at HIGHEST."""
    def f(x_, w_):
        return jax.lax.conv_general_dilated(
            x_, w_, (d.s_h, d.s_w), [(d.P_h, d.p_h_hi), (d.P_w, d.p_w_hi)],
            precision=_HIGHEST, dimension_numbers=("NCHW", "OIHW", "NCHW"))
    y, vjp = jax.vjp(f, x, w)
    return (y, *vjp(dy))


def _channel_variants(role: str, d: ConvDims):
    """The planner's own plan, then the same spatial tile with one
    contraction step and the narrowest output tiles, and with the
    narrowest contraction tiles and one output step."""
    own = _plan(role, d)
    th, tw, *_ = _tile(own).tile_key
    t = _tile(own)
    narrow_in, narrow_out = LANE, (LANE if t.cout_pad > LANE
                                   else t.cout_pad)
    return [own,
            ops.plan_from_tile(role, d, None, (th, tw, t.cin_pad,
                                               narrow_out)),
            ops.plan_from_tile(role, d, None, (th, tw, narrow_in,
                                               t.cout_pad))]


@pytest.mark.parametrize("name", sorted(WIDE_LAYERS))
def test_wide_tiles_match_lax(name):
    d = WIDE_LAYERS[name]
    x, w, dy = _data(d)
    want_y, want_di, want_dw = _lax(x, w, dy, d)
    run = {"forward": lambda p: ops.conv2d_forward(x, w, d, plan=p),
           "input_grad": lambda p: ops.conv2d_input_grad(dy, w, d, plan=p),
           "weight_grad": lambda p: ops.conv2d_weight_grad(x, dy, d,
                                                           plan=p)}
    want = {"forward": want_y, "input_grad": want_di, "weight_grad": want_dw}
    split = []
    with config.override(interpret=True):
        for role in ROLES:
            plans = _channel_variants(role, d)
            assert all(p is not None for p in plans), role
            assert max(_tile(plans[0]).cin_tile,
                       _tile(plans[0]).cout_tile) > LANE, role
            for p in plans:
                t = _tile(p)
                split.append((t.cin_pad // t.cin_tile,
                              t.cout_pad // t.cout_tile))
                got = run[role](p)
                scale = float(jnp.max(jnp.abs(want[role])))
                np.testing.assert_allclose(
                    got, want[role], rtol=0, atol=2e-5 * scale,
                    err_msg=f"{role} {t.tile_key}")
    assert any(cin == 1 and cout > 1 for cin, cout in split), split
    assert any(cin > 1 for cin, _ in split), split


# ---------------------------------------------------------------------------
# Persisted tiles (the autotuner's plan cache)
# ---------------------------------------------------------------------------

def test_plan_from_tile_accepts_wide_and_rejects_non_dividing_tiles():
    d = _resnet("14x14-1x1")
    for role in ROLES:
        plan = _plan(role, d)
        key = _tile(plan).tile_key
        assert max(key[2:]) > LANE
        assert ops.plan_from_tile(role, d, None, key) == plan, role
    d = WIDE_LAYERS["3x3s2"]                  # C = 256, N = 384
    th, tw, *_ = ops.forward_plan(d).tile_key
    assert ops.plan_from_tile("forward", d, None, (th, tw, 256, 384))
    assert ops.plan_from_tile("forward", d, None, (th, tw, 128, 128))
    for cit, cot in ((256, 256), (192, 384), (384, 384), (256, 64)):
        assert ops.plan_from_tile("forward", d, None,
                                  (th, tw, cit, cot)) is None, (cit, cot)
