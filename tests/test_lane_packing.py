"""Lane packing of narrow contractions (``ops.LanePack``).

A forward or weight-grad pass that contracts over ``C <= 64`` channels
packs several taps into one 128-lane contraction tile: each slot is a
fixed shift of a phase plane, and the kernel runs a shorter tap table
over the packed source.  The same products are summed, only grouped into
fewer dots, so packed plans, unpacked plans and ``lax`` at HIGHEST agree.
Everything runs in the Pallas interpreter on the CPU.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ConvTransposeSpec, conv2d_transpose, transpose_dims
from repro.core.im2col_ref import ConvDims
from repro.kernels import ops
from repro.kernels import tap_gemm as tg

_HIGHEST = jax.lax.Precision.HIGHEST

#: name -> ConvDims keyword arguments without C: small planes of each
#: geometry the planner packs.
GEOMETRIES = {
    "stem7x7s2": dict(H_i=14, W_i=14, K_h=7, K_w=7, S=2, P_h=3, P_w=3),
    "3x3s2": dict(H_i=12, W_i=12, K_h=3, K_w=3, S=2, P_h=1, P_w=1),
    "3x3s1": dict(H_i=10, W_i=10, K_h=3, K_w=3, S=1, P_h=1, P_w=1),
    "3x3s2x1": dict(H_i=12, W_i=11, K_h=3, K_w=3, S=2, S_w=1, P_h=1,
                    P_w=1),
    "3x3d2": dict(H_i=12, W_i=12, K_h=5, K_w=5, S=1, P_h=2, P_w=2, D_h=2,
                  D_w=2),
}


def _dims(geometry: str, c: int) -> ConvDims:
    return ConvDims(B=2, C=c, N=5, **GEOMETRIES[geometry])


def _data(d: ConvDims, seed=0):
    r = np.random.RandomState(seed)
    x = jnp.asarray(r.randn(d.B, d.C, d.H_i, d.W_i), jnp.float32)
    w = jnp.asarray(r.randn(d.N, d.C, d.k_taps_h, d.k_taps_w), jnp.float32)
    dy = jnp.asarray(r.randn(d.B, d.N, d.H_o, d.W_o), jnp.float32)
    return x, w, dy


def _lax_pair(x, w, dy, d: ConvDims):
    """(y, dW) from XLA's conv at HIGHEST; ``w`` is the compact kernel,
    dilated by ``rhs_dilation``."""
    def f(x_, w_):
        return jax.lax.conv_general_dilated(
            x_, w_, (d.s_h, d.s_w), [(d.P_h, d.p_h_hi), (d.P_w, d.p_w_hi)],
            rhs_dilation=(d.D_h, d.D_w), precision=_HIGHEST,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
    y, vjp = jax.vjp(f, x, w)
    return y, vjp(dy)[1]


def _source_bytes(d: ConvDims, plan: ops.TilePlan, planes: int,
                  taps) -> int:
    """Bytes of the window-fit source a kernel launch reads: ``planes``
    planes of 128 lanes over the plane the tile grid and ``taps``' halo
    need."""
    halo_h, halo_w = tg._taps_halo(taps)
    *_, rows, cols = tg._tiling(d.H_o, d.W_o, plan.oh_tile, plan.ow_tile,
                                halo_h, halo_w)
    return planes * d.B * rows * cols * plan.cin_pad * 4


# ---------------------------------------------------------------------------
# Results: packed == unpacked == lax
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c", [1, 3, 4, 16, 64])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_packed_matches_unpacked_and_lax(geometry, c):
    d = _dims(geometry, c)
    x, w, dy = _data(d, seed=c)
    want_y, want_dw = _lax_pair(x, w, dy, d)
    fp, wp = ops.forward_plan(d), ops.weight_grad_plan(d)
    assert fp.pack is not None and wp.pack is not None, (geometry, c)
    for plan, run, want in (
            (fp, lambda p: ops.conv2d_forward(x, w, d, plan=p), want_y),
            (wp, lambda p: ops.conv2d_weight_grad(x, dy, d, plan=p),
             want_dw)):
        packed = run(plan)
        unpacked = run(dataclasses.replace(plan, pack=None))
        np.testing.assert_allclose(packed, unpacked, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(packed, want, rtol=1e-4, atol=1e-4)


def test_transposed_conv_with_three_mirror_channels_packs():
    """A decoder's last layer (64 -> 3 channels, transposed): its dX is
    the mirror forward and its dW the mirror weight grad, both contracting
    over the 3 output channels, so both pack."""
    spec = ConvTransposeSpec.make(stride=2, padding=1, output_padding=1)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 6, 6), jnp.float32)
    w = jnp.asarray(np.random.RandomState(1).randn(8, 3, 3, 3) * 0.5,
                    jnp.float32)
    mirror = transpose_dims(x.shape, w.shape, spec)
    assert mirror.C == 3
    ops.clear_tile_plan_cache()
    ops.reset_plan_events()

    def loss(policy):
        return lambda x_, w_: jnp.sum(
            conv2d_transpose(x_, w_, spec, policy) ** 2)

    got = jax.grad(loss("pallas"), argnums=(0, 1))(x, w)
    events = ops.plan_events()
    assert events.get("forward_packed") == 1, events
    assert events.get("weight_grad_packed") == 1, events
    want = jax.grad(loss("lax"), argnums=(0, 1))(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Planner properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c", [1, 3, 4, 16, 64])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_packed_plan_properties(geometry, c):
    d = _dims(geometry, c)
    real = ops._forward_taps(d)
    for plan in (ops.forward_plan(d), ops.weight_grad_plan(d)):
        pack = plan.pack
        assert plan.taps == real                  # the REAL tap table
        assert plan.kernel_taps == pack.taps
        assert len(pack.taps) < len(real)
        assert pack.width * c <= tg.LANE          # one lane tile
        assert plan.cin_tile == plan.cin_pad == tg.LANE
        assert (_source_bytes(d, plan, len(pack.slots), pack.taps)
                <= _source_bytes(d, plan, d.s_h * d.s_w, real))
        # Every real tap lands on a distinct (packed tap, slot) pair whose
        # shift and packed offset add back up to the tap.
        assert len(set(pack.where)) == len(real)
        for (p, du, dv), (t, j) in zip(real, pack.where):
            i, du2, dv2 = pack.taps[t]
            p2, a, b = pack.slots[i][j]
            assert (p2, du2 + a, dv2 + b) == (p, du, dv)


def test_stem_packs_into_two_taps_of_96_lanes():
    """ResNet's 7x7 stride-2 stem on 3 channels at its real size: 4 phase
    planes x 4 W shifts x 2 H shifts = 32 slots of 3 lanes, and 2 packed
    taps (49 GEMMs per tile before), each slice sublane-aligned."""
    d = ConvDims(B=32, C=3, H_i=224, W_i=224, N=64, K_h=7, K_w=7, S=2,
                 P_h=3, P_w=3)
    plan = ops.forward_plan(d)
    assert len(plan.taps) == 49
    assert plan.pack.planes == ((0, 1, 2, 3),)
    assert plan.pack.shifts == tuple((a, b) for a in range(2)
                                     for b in range(4))
    assert plan.pack.slots == (tuple((p, a, b) for a in range(2)
                                     for b in range(4) for p in range(4)),)
    assert plan.pack.taps == ((0, 0, 0), (0, 2, 0))
    assert plan.pack.lane_fill == 96 / 128
    packed = _source_bytes(d, plan, 1, plan.pack.taps)
    unpacked = _source_bytes(d, plan, 4, plan.taps)
    assert packed * 4 <= unpacked, (packed, unpacked)
    rep = ops.plan_report(d)
    assert rep["forward"]["taps"] == 49
    assert rep["forward"]["pack"] == {"slots": 32, "packed_taps": 2,
                                      "lane_fill": 0.75}
    assert rep["weight_grad"]["pack"] == rep["forward"]["pack"]
    assert "pack" not in rep["input_grad"]


@pytest.mark.parametrize("c", [65, 128])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_wide_contractions_keep_the_unpacked_plan(geometry, c):
    d = _dims(geometry, c)
    taps = ops._forward_taps(d)
    halo_h, halo_w = tg._taps_halo(taps)
    for plan, vmem in ((ops.forward_plan(d), tg.tap_gemm_vmem),
                       (ops.weight_grad_plan(d), tg.tap_wgrad_vmem)):
        assert plan.pack is None and plan.kernel_taps == taps
        assert (plan.halo_h, plan.halo_w) == (halo_h, halo_w)
        assert plan.bytes_needed == vmem(
            d.s_h * d.s_w, len(taps), plan.oh_tile, plan.ow_tile, halo_h,
            halo_w, plan.cin_tile, plan.cout_tile)
    assert "pack" not in ops.plan_report(d)["forward"]


def test_no_packing_where_it_would_not_shorten_the_table():
    """One real tap (a 1x1 conv) has nothing to share a lane tile with."""
    for s in (1, 2):
        d = ConvDims(B=1, C=3, H_i=8, W_i=8, N=4, K_h=1, K_w=1, S=s,
                     P_h=0, P_w=0)
        assert ops.forward_plan(d).pack is None


@pytest.mark.parametrize("c", [3, 64, 65])
def test_packed_counters_fire_exactly_when_packing_is_used(c):
    d = _dims("3x3s2", c)
    ops.clear_tile_plan_cache()
    ops.reset_plan_events()
    fp, wp = ops.forward_plan(d), ops.weight_grad_plan(d)
    ops.forward_plan(d)                           # memoized: not recounted
    events = ops.plan_events()
    assert events.get("forward_packed", 0) == (fp.pack is not None)
    assert events.get("weight_grad_packed", 0) == (wp.pack is not None)
    assert (fp.pack is not None) == (c <= 64)
    assert "input_grad_packed" not in events


def test_stem_candidates_all_share_the_planned_pack():
    """Packing is fixed by the geometry, not by the tile: every candidate
    plan of the stem carries the planner's own pack."""
    d = _dims("stem7x7s2", 3)
    for role, plan in (("forward", ops.forward_plan(d)),
                       ("weight_grad", ops.weight_grad_plan(d))):
        cands = ops.plan_candidates(role, d, k=3)
        assert plan.pack is not None and cands[0] == plan, role
        assert all(c.pack == plan.pack for c in cands), role
