"""Shared Pallas kernel bodies: spatially-tiled tap-loop GEMMs over
phase-split operands.

This is the TPU-native datapath of BP-im2col.  The paper's RTL address
generators turn a virtual zero-spaced lowered matrix into fetches of compact
data; here the same mapping is resolved *statically* into a list of "taps"
``(plane, du, dv)`` over a phase-split compact tensor, and the kernel is a
dense multi-tap GEMM (the tap tables are built per axis by ``ops.py``, so
the list is exactly the REAL taps: asymmetric strides and dilated kernels
change the table, never the kernel bodies below):

    out[b, oh, ow, :COUT] += src[plane, b, oh+du, ow+dv, :CIN] @ w[tap]

Every load is a static VMEM slice of a fetched source window -- no gathers,
no zero-space bytes ever enter VMEM.  Three ops share the kernel bodies:

  * forward conv         -> ``tap_gemm``        (src = phase-split padded input)
  * input grad (transposed mode, ALL output phases fused into one launch)
                         -> ``tap_gemm_phased`` (src = padded compact dY)
  * weight grad (dilated mode)
                         -> ``tap_wgrad``       (contraction over batch x space)

Spatial tiling: every builder takes ``oh_tile``/``ow_tile`` and adds
output-row/col block dimensions to the grid.  Consecutive source windows
overlap by the tap halo ``(max du, max dv)``, which no blocked index map can
express, so the source stays in HBM (``memory_space=pl.ANY``) and each grid
step copies its ``(tile + halo)`` window into a VMEM scratch with one DMA.
The window layout follows the TPU's (8, 128) vreg tiling, which is what
Mosaic accepts: the W tile is a multiple of 8 and the window width is
rounded up to a multiple of 8, so the DMA's W offset ``c * tw`` is
sublane-aligned; rows (H) are an untiled leading dim and take any offset;
a channel tile is the whole channel dim or a multiple of 128 lanes.  A
window is fetched only when it changes: with one contraction step it is
reused across the output-channel tiles (and, for the fused input grad,
across the stride phases).

Grid conventions (contraction dims INNERMOST so f32 scratch accumulates):
  tap_gemm        grid = (B, n_th, n_tw, cout_steps, cin_steps)
  tap_gemm_phased grid = (B, n_th, n_tw, PH, cout_steps, cin_steps) with
                  PH = s_h*s_w output stride phases (per-axis, so
                  asymmetric strides just change PH); the phase dim selects
                  the per-phase weight block and tap table, nothing else --
                  one pallas_call per conv.
  tap_wgrad       grid = (cin_steps, cout_steps, B, n_th, n_tw); batch and
                  space are contraction dims, accumulated in an f32 VMEM
                  scratch and flushed to the output block exactly once.

All shapes entering ``pl.pallas_call`` are static; tile sizes are chosen by
``ops.py`` under an explicit VMEM budget, which the builders hand to Mosaic
as ``vmem_limit_bytes``.  The ``*_vmem`` functions below are the footprint
model both sides share: every buffer padded to (8, 128) tiles, pipelined
blocks counted twice (double buffering), plus one tap's GEMM temporaries,
which a wide f32 dot at HIGHEST makes several times its operand slice.
The model assumes every operand in HBM: where XLA places a weight or
output block in VMEM instead, Mosaic needs less scoped VMEM, never more.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.config import config
from repro.obs.trace import GLUE_SCOPE

SUBLANE, LANE = 8, 128      # f32 vreg tile: (sublanes, lanes)
_F32 = 4
_HIGHEST = jax.lax.Precision.HIGHEST


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(a: int, m: int) -> int:
    return _cdiv(a, m) * m


def _taps_halo(taps) -> tuple[int, int]:
    if not taps:
        return 0, 0
    return max(t[-2] for t in taps), max(t[-1] for t in taps)


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` or, when None, ``config.interpret``.  Interpreting on a
    TPU backend is refused: a chip run that silently exercised the Pallas
    interpreter is worse than one that fails."""
    if interpret is None:
        interpret = config.interpret
    if interpret and jax.default_backend() == "tpu":
        raise RuntimeError(
            "Pallas kernels would run in interpret mode on a TPU backend; "
            "leave config.interpret unset (it resolves to False on a TPU)")
    return interpret


# ---------------------------------------------------------------------------
# VMEM footprint model (shared with the planner in ops.py)
# ---------------------------------------------------------------------------

def vmem_bytes(shape: Sequence[int], buffers: int = 1) -> int:
    """Bytes Mosaic allocates for ``buffers`` f32 VMEM buffers of
    ``shape``: the two minor dims padded to the (8, 128) tile."""
    *lead, sub, lane = shape
    return (buffers * math.prod(lead) * _round_up(sub, SUBLANE)
            * _round_up(lane, LANE) * _F32)


def window_w(tw: int, halo_w: int) -> int:
    """Source window width for an output tile ``tw`` wide: tile plus halo,
    rounded up to whole sublanes."""
    return _round_up(tw + halo_w, SUBLANE)


#: scoped VMEM Mosaic takes beyond the buffers and dot temporaries the
#: model names: internal scratch and the relayout copies of tap slices at
#: sublane offsets.  The model fell short by up to 0.61 MiB without it, in
#: compiles of every candidate tile of ResNet-50's strided convs for a v5e.
_MOSAIC_SLACK = 768 * 1024


def _gemm_temps(th: int, tw: int, cit: int, cot: int) -> int:
    """One tap's live GEMM values: the (th*tw, cit) operand slice and the
    (th*tw, cot) product.  An f32 dot at ``Precision.HIGHEST`` wider than
    128 output lanes also keeps the operand slice's bf16 splits on
    Mosaic's stack: up to four f32 copies of the slice plus two 128-lane
    columns, measured by compiling single kernels for a v5e with the
    operands in HBM at 224 and 896 rows and 128 to 1024 lanes each way.
    With 128 output lanes the slice is not split."""
    rows = th * tw
    temps = vmem_bytes((rows, cit)) + vmem_bytes((rows, cot))
    if cot > LANE:
        temps = max(temps, vmem_bytes((rows, 4 * cit + 2 * LANE)))
    return temps


def tap_gemm_vmem(planes: int, n_taps: int, th: int, tw: int, halo_h: int,
                  halo_w: int, cit: int, cot: int) -> int:
    return (_MOSAIC_SLACK
            + vmem_bytes((planes, th + halo_h, window_w(tw, halo_w), cit))
            + vmem_bytes((n_taps, cit, cot), 2)
            + vmem_bytes((th, tw, cot), 2)
            + vmem_bytes((th * tw, cot))
            + _gemm_temps(th, tw, cit, cot))


def tap_wgrad_vmem(planes: int, n_taps: int, th: int, tw: int, halo_h: int,
                   halo_w: int, cit: int, cot: int) -> int:
    return (_MOSAIC_SLACK
            + vmem_bytes((planes, th + halo_h, window_w(tw, halo_w), cit))
            + vmem_bytes((th, tw, cot), 2)
            + vmem_bytes((n_taps, cit, cot), 3)     # out block x2 + acc
            + _gemm_temps(th, tw, cit, cot) + vmem_bytes((cit, cot))
            # the dot contracts the slice's rows: Mosaic transposes it,
            # about 2 KiB per contraction lane in compiles at 56-224 rows
            + vmem_bytes((cit, 2 * LANE), 2))


# ---------------------------------------------------------------------------
# Tiling + source-window DMA
# ---------------------------------------------------------------------------

def _tiling(oh: int, ow: int, oh_tile, ow_tile, halo_h: int, halo_w: int):
    """(th, tw, n_th, n_tw, rows, cols): the output tile (W rounded up to
    whole sublanes), the grid extent, and the exact source extent every
    window of the grid reads."""
    th = min(oh_tile or oh, oh)
    tw = _round_up(min(ow_tile or ow, ow), SUBLANE)
    n_th, n_tw = _cdiv(oh, th), _cdiv(ow, tw)
    return (th, tw, n_th, n_tw, n_th * th + halo_h,
            (n_tw - 1) * tw + window_w(tw, halo_w))


def _fit_hw(x: jax.Array, h_axis: int, rows: int, cols: int) -> jax.Array:
    """Zero-pad or crop two adjacent spatial axes to exactly (rows, cols):
    layout glue before the launch, under the ``glue`` named scope."""
    with jax.named_scope(GLUE_SCOPE):
        for axis, n in ((h_axis, rows), (h_axis + 1, cols)):
            x = jax.lax.slice_in_dim(x, 0, min(n, x.shape[axis]), axis=axis)
            pads = [(0, 0)] * x.ndim
            pads[axis] = (0, n - x.shape[axis])
            x = jnp.pad(x, pads)
        return x


def _fetch_window(src, win, sem, lead, h0, w0, c0) -> None:
    """DMA ``src[*lead, h0:h0+wh, w0:w0+ww, c0:c0+ct]`` into ``win``
    (shape ``(..., wh, ww, ct)``).  A dim the window spans whole is taken
    with a static full slice; otherwise the W offset is a multiple of 8 and
    the C offset a multiple of 128, so the copy stays tile-aligned."""
    wh, ww, ct = win.shape[-3:]
    cols, chans = src.shape[-2:]
    idx = [*lead, pl.ds(h0, wh)]
    if ww != cols or ct != chans:
        idx.append(slice(None) if ww == cols
                   else pl.ds(pl.multiple_of(w0, SUBLANE), ww))
    if ct != chans:
        idx.append(pl.ds(pl.multiple_of(c0, LANE), ct))
    copy = pltpu.make_async_copy(src.at[tuple(idx)], win, sem)
    copy.start()
    copy.wait()


def _fetch_when_changed(fetch, cin_steps: int, first_reuse_step) -> None:
    """Fetch every step when the contraction is tiled; with one cin step
    the window only changes when ``first_reuse_step`` is true."""
    if cin_steps == 1:
        pl.when(first_reuse_step)(fetch)
    else:
        fetch()


def _dot(a, b):
    return jax.lax.dot(a, b, precision=_HIGHEST,
                       preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------

def _tap_gemm_kernel(src_hbm, w_ref, out_ref, win, acc_ref, sem, *,
                     taps: tuple[tuple[int, int, int], ...],
                     th: int, tw: int, cin_steps: int):
    """out tile = sum_t win[p_t, du_t:du_t+th, dv_t:dv_t+tw, :] @ w[t]."""
    b, r, c, co, ci = (pl.program_id(i) for i in range(5))
    cit = win.shape[-1]

    def fetch():
        _fetch_window(src_hbm, win, sem, (slice(None), b), r * th, c * tw,
                      ci * cit)

    _fetch_when_changed(fetch, cin_steps, co == 0)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for t, (p, du, dv) in enumerate(taps):
        xs = win[p, du:du + th, dv:dv + tw, :].reshape(th * tw, cit)
        acc_ref[...] += _dot(xs, w_ref[t])

    @pl.when(ci == cin_steps - 1)
    def _flush():
        out_ref[...] = acc_ref[...].reshape(
            1, th, tw, out_ref.shape[-1]).astype(out_ref.dtype)


def _tap_gemm_phased_kernel(src_hbm, w_ref, out_ref, win, acc_ref, sem, *,
                            phase_taps: tuple[tuple[tuple[int, int, int], ...],
                                              ...],
                            th: int, tw: int, cin_steps: int):
    """Fused input-grad body: the phase grid dim selects which tap table
    runs and which weight block was loaded.  Phases with an empty tap table
    write a zero tile (those rows of dI receive no contribution)."""
    b, r, c, phase, co, ci = (pl.program_id(i) for i in range(6))
    cit = win.shape[-1]

    def fetch():
        _fetch_window(src_hbm, win, sem, (b,), r * th, c * tw, ci * cit)

    _fetch_when_changed(fetch, cin_steps, (phase == 0) & (co == 0))

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for p, taps in enumerate(phase_taps):
        if not taps:
            continue

        @pl.when(phase == p)
        def _run(taps=taps):
            for (j, du, dv) in taps:
                xs = win[du:du + th, dv:dv + tw, :].reshape(th * tw, cit)
                acc_ref[...] += _dot(xs, w_ref[0, j])

    @pl.when(ci == cin_steps - 1)
    def _flush():
        out_ref[...] = acc_ref[...].reshape(
            1, 1, th, tw, out_ref.shape[-1]).astype(out_ref.dtype)


def _tap_wgrad_kernel(src_hbm, dy_ref, out_ref, win, acc_ref, sem, *,
                      taps: tuple[tuple[int, int, int], ...],
                      th: int, tw: int, contraction_steps: int):
    """acc[t, :, :] += win[p_t, du:du+th, dv:dv+tw, :].T @ dy tile.

    Batch AND spatial tiles are contraction dims; partial sums live in the
    f32 VMEM scratch and the output block is written exactly once, so it is
    never round-tripped through HBM between contraction steps."""
    ci, _, b, r, c = (pl.program_id(i) for i in range(5))
    step = (b * pl.num_programs(3) + r) * pl.num_programs(4) + c
    cit = win.shape[-1]
    _fetch_window(src_hbm, win, sem, (slice(None), b), r * th, c * tw,
                  ci * cit)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dyr = dy_ref[0].reshape(th * tw, dy_ref.shape[-1])
    for t, (p, du, dv) in enumerate(taps):
        xs = win[p, du:du + th, dv:dv + tw, :].reshape(th * tw, cit)
        # (CIN, th*tw) @ (th*tw, COUT) via dot_general contraction on dim 0.
        acc_ref[t, :, :] += jax.lax.dot_general(
            xs, dyr, (((0,), (0,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)

    @pl.when(step == contraction_steps - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call builders
# ---------------------------------------------------------------------------

def tap_gemm(src: jax.Array, w: jax.Array,
             taps: Sequence[tuple[int, int, int]],
             oh: int, ow: int, *,
             cin_tile: int, cout_tile: int,
             oh_tile: int | None = None, ow_tile: int | None = None,
             out_dtype=None, interpret: bool | None = None,
             vmem_limit_bytes: int | None = None) -> jax.Array:
    """Spatially-tiled multi-tap GEMM.

    src : (P, B, Hs, Ws, CIN)   phase-split compact source
    w   : (T, CIN, COUT)        per-tap weight slices, T == len(taps)
    out : (B, oh, ow, COUT)

    ``oh_tile``/``ow_tile`` block the output spatial plane (the W tile is
    rounded up to a multiple of 8); each grid step DMAs the matching source
    window plus the tap halo, so consecutive windows overlap.
    """
    p_, b_, _, _, cin = src.shape
    t_, cin2, cout = w.shape
    assert cin == cin2 and t_ == len(taps)
    assert cin % cin_tile == 0 and cout % cout_tile == 0
    halo_h, halo_w = _taps_halo(taps)
    th, tw, n_th, n_tw, rows, cols = _tiling(oh, ow, oh_tile, ow_tile,
                                             halo_h, halo_w)
    src = _fit_hw(src, 2, rows, cols)
    cin_steps = cin // cin_tile
    out_dtype = out_dtype or src.dtype

    kernel = functools.partial(
        _tap_gemm_kernel, taps=tuple(taps), th=th, tw=tw,
        cin_steps=cin_steps)
    out = pl.pallas_call(
        kernel,
        grid=(b_, n_th, n_tw, cout // cout_tile, cin_steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec((t_, cin_tile, cout_tile),
                         lambda b, r, c, co, ci: (0, ci, co)),
        ],
        out_specs=pl.BlockSpec((1, th, tw, cout_tile),
                               lambda b, r, c, co, ci: (b, r, c, co)),
        out_shape=jax.ShapeDtypeStruct((b_, n_th * th, n_tw * tw, cout),
                                       out_dtype),
        scratch_shapes=[
            pltpu.VMEM((p_, th + halo_h, window_w(tw, halo_w), cin_tile),
                       src.dtype),
            pltpu.VMEM((th * tw, cout_tile), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=resolve_interpret(interpret),
        name="tap_gemm",
    )(src, w)
    return out[:, :oh, :ow, :]


def tap_gemm_phased(src: jax.Array, w: jax.Array,
                    phase_taps: Sequence[Sequence[tuple[int, int, int]]],
                    oh: int, ow: int, *,
                    cin_tile: int, cout_tile: int,
                    oh_tile: int | None = None, ow_tile: int | None = None,
                    out_dtype=None, interpret: bool | None = None,
                    vmem_limit_bytes: int | None = None) -> jax.Array:
    """All-phases input-grad tap GEMM in ONE pallas_call.

    src : (B, Hs, Ws, CIN)      globally padded compact dY (shared by every
                                phase -- tap offsets are pre-shifted so all
                                phases read it at a uniform base)
    w   : (PH, T, CIN, COUT)    per-phase stacked tap weights, zero-padded to
                                the widest tap table T
    out : (PH, B, oh, ow, COUT) phase-major planes, un-phase-split by the
                                caller with a pure reshape/transpose

    phase_taps[p] is a tuple of ``(j, du, dv)``: tap j of phase p reads the
    source window at halo offset (du, dv).
    """
    b_, _, _, cin = src.shape
    ph_, t_, cin2, cout = w.shape
    assert cin == cin2 and ph_ == len(phase_taps)
    assert all(j < t_ for taps in phase_taps for (j, _, _) in taps)
    assert cin % cin_tile == 0 and cout % cout_tile == 0
    halo_h, halo_w = _taps_halo([t for taps in phase_taps for t in taps])
    th, tw, n_th, n_tw, rows, cols = _tiling(oh, ow, oh_tile, ow_tile,
                                             halo_h, halo_w)
    src = _fit_hw(src, 1, rows, cols)
    cin_steps = cin // cin_tile
    out_dtype = out_dtype or src.dtype

    kernel = functools.partial(
        _tap_gemm_phased_kernel,
        phase_taps=tuple(tuple(taps) for taps in phase_taps),
        th=th, tw=tw, cin_steps=cin_steps)
    out = pl.pallas_call(
        kernel,
        grid=(b_, n_th, n_tw, ph_, cout // cout_tile, cin_steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec((1, t_, cin_tile, cout_tile),
                         lambda b, r, c, p, co, ci: (p, 0, ci, co)),
        ],
        out_specs=pl.BlockSpec((1, 1, th, tw, cout_tile),
                               lambda b, r, c, p, co, ci: (p, b, r, c, co)),
        out_shape=jax.ShapeDtypeStruct(
            (ph_, b_, n_th * th, n_tw * tw, cout), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((th + halo_h, window_w(tw, halo_w), cin_tile),
                       src.dtype),
            pltpu.VMEM((th * tw, cout_tile), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=resolve_interpret(interpret),
        name="tap_gemm_phased",
    )(src, w)
    return out[:, :, :oh, :ow, :]


def tap_wgrad(src: jax.Array, dy: jax.Array,
              taps: Sequence[tuple[int, int, int]],
              oh: int, ow: int, *,
              cin_tile: int, cout_tile: int,
              oh_tile: int | None = None, ow_tile: int | None = None,
              interpret: bool | None = None,
              vmem_limit_bytes: int | None = None) -> jax.Array:
    """Weight gradient: out (T, CIN, COUT) summed over batch and space.

    src : (P, B, Hs, Ws, CIN)   phase-split padded input
    dy  : (B, oh, ow, COUT)     compact output loss

    Batch and spatial tiles are contraction grid dims; the partial sums
    accumulate in an f32 VMEM scratch (never through HBM).
    """
    p_, b_, _, _, cin = src.shape
    b2, oh2, ow2, cout = dy.shape
    assert b2 == b_ and oh2 == oh and ow2 == ow
    assert cin % cin_tile == 0 and cout % cout_tile == 0
    t_ = len(taps)
    halo_h, halo_w = _taps_halo(taps)
    th, tw, n_th, n_tw, rows, cols = _tiling(oh, ow, oh_tile, ow_tile,
                                             halo_h, halo_w)
    src = _fit_hw(src, 2, rows, cols)
    dy = _fit_hw(dy, 1, n_th * th, n_tw * tw)     # zero rows add nothing

    kernel = functools.partial(
        _tap_wgrad_kernel, taps=tuple(taps), th=th, tw=tw,
        contraction_steps=b_ * n_th * n_tw)
    return pl.pallas_call(
        kernel,
        grid=(cin // cin_tile, cout // cout_tile, b_, n_th, n_tw),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec((1, th, tw, cout_tile),
                         lambda ci, co, b, r, c: (b, r, c, co)),
        ],
        out_specs=pl.BlockSpec((t_, cin_tile, cout_tile),
                               lambda ci, co, b, r, c: (0, ci, co)),
        out_shape=jax.ShapeDtypeStruct((t_, cin, cout), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((p_, th + halo_h, window_w(tw, halo_w), cin_tile),
                       src.dtype),
            pltpu.VMEM((t_, cin_tile, cout_tile), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=resolve_interpret(interpret),
        name="tap_wgrad",
    )(src, dy)
