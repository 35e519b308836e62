"""Public jit'd wrappers around the Pallas kernels.

Responsibilities:
  * layout prep (NCHW -> NHWC, padding, phase-splitting) -- pure reshapes /
    slices on COMPACT data, done once at trace time;
  * static tap-table construction (the BP-im2col address mapping, resolved
    per stride phase).  Tap tables are built INDEPENDENTLY per axis: the
    phase grid is ``s_h x s_w`` (asymmetric strides included), and a kernel
    dilation (``ConvDims.D_h``/``D_w``) drops the zero taps from the table
    outright -- only the ``k_taps_h * k_taps_w`` real taps are ever
    enumerated, multiplied or planned for, never the ``K_h * K_w``
    zero-dilated extent.  Callers pass the COMPACT (undilated) kernel;
  * lane packing of narrow contractions (:class:`LanePack`): a forward or
    weight-grad pass over ``C <= 64`` channels packs shifted phase planes
    side by side into one 128-lane tile, so the kernel runs a shorter tap
    table instead of one dot per tap over mostly zero lanes;
  * tile-plan SEARCH under an explicit VMEM budget: the planners walk
    every (spatial tile, cin tile, cout tile) candidate -- spatial tiles
    from the full plane down, channel tiles from the whole padded dim down
    to 128 lanes -- and take the one whose per-grid-step VMEM footprint
    fits with the fewest grid steps (:func:`_search_tiles`).  A shape only
    falls back to the jnp phase decomposition when even the minimal
    1x1-spatial / 128-lane tiling exceeds the budget (genuinely degenerate
    geometry or an absurdly small budget), never merely because the full
    spatial plane is large.

Tap tables and tile choices depend only on the static ``ConvDims`` and the
budget, so they are memoized (``functools.lru_cache``) with the budget as an
explicit cache-key argument.  The budget itself lives on the global config
(``repro.config.vmem_budget_bytes``): ``config.update(...)`` both changes
the default budget every planner resolves AND invalidates these lru caches,
so there is no way to be served a stale plan.  Repeated layer shapes --
every step of a training run retraces the same convs -- skip the search
entirely.  ``tile_plan_cache_info()`` exposes hit counts;
``clear_tile_plan_cache()`` resets; ``plan_events()`` counts planned-vs-
fallback outcomes (one event per unique shape/budget) for benchmarks & CI,
and ``plan_seconds()`` the host seconds those unique resolutions took.

``repro.config.interpret`` is worked out from the backend on first use:
the kernels are compiled with Mosaic on a TPU and run in the Pallas
interpreter on the CPU (where the test-suite runs them).  On a TPU an
interpreted launch is an error (``tap_gemm.resolve_interpret``).  The
pre-config module globals ``INTERPRET`` / ``VMEM_BUDGET_BYTES`` remain
readable and assignable as deprecated aliases of the config fields.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import sys
import time
import types
import warnings
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.config import config
from repro.core.im2col_ref import ConvDims, rot180, zero_insert, zero_pad
from repro.core import phase_decomp
from repro.kernels import tap_gemm as tg
from repro.obs import events as obs_events
from repro.obs.trace import GLUE_SCOPE
from repro.kernels.tap_gemm import LANE, SUBLANE, _cdiv, _round_up, _taps_halo

#: most GEMM rows (``th * tw``) one grid step may hold.  Mosaic unrolls each
#: tap's dot over the tile's vregs, so this bounds compile time and the
#: live values of the tap loop; VMEM alone would allow far larger tiles.
MAX_TILE_ROWS = 1024

#: planned-vs-fallback outcomes, one event per unique (ConvDims, budget)
#: planner invocation (memoized calls do not re-count).
PLAN_EVENTS: dict[str, int] = {}


def _count_event(name: str) -> None:
    PLAN_EVENTS[name] = PLAN_EVENTS.get(name, 0) + 1
    obs_events.emit("plan", name)


#: host seconds spent resolving tile plans that no cache held.
_PLAN_CLOCK = {"seconds": 0.0}


@contextlib.contextmanager
def _plan_clock():
    """Add the host time of one plan resolution to :func:`plan_seconds`."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        _PLAN_CLOCK["seconds"] += time.perf_counter() - t0


def plan_events() -> dict[str, int]:
    return dict(PLAN_EVENTS)


def plan_seconds() -> float:
    """Host seconds spent resolving tile plans since the last
    :func:`reset_plan_events`: the analytic search, on a miss of its
    caches only (a cached plan costs nothing).  Planning runs while jax
    traces a program, so this is set-up time, never a step's."""
    return _PLAN_CLOCK["seconds"]


def reset_plan_events() -> None:
    PLAN_EVENTS.clear()
    _PLAN_CLOCK["seconds"] = 0.0
    # Keep the bus-backed view in lockstep with the legacy dict (no-op off).
    obs_events.drop("plan")


def _canonical(d: ConvDims) -> ConvDims:
    """Resolve the P_*_hi = -1 'symmetric' sentinel to explicit high-side
    pads and normalize the S_w stride sentinel so geometrically identical
    layers share one plan-cache entry (and one plan event) no matter how
    the caller spelled the padding/stride."""
    sw = -1 if d.s_w == d.S else d.s_w
    if d.P_h_hi == d.p_h_hi and d.P_w_hi == d.p_w_hi and d.S_w == sw:
        return d
    return dataclasses.replace(d, P_h_hi=d.p_h_hi, P_w_hi=d.p_w_hi, S_w=sw)


# ---------------------------------------------------------------------------
# Layout helpers
# ---------------------------------------------------------------------------

def _to_nhwc(x):
    return x.transpose(0, 2, 3, 1)


def _from_nhwc(x):
    return x.transpose(0, 3, 1, 2)


def _pad_to(x, n: int, axis: int = -1):
    """Zero-pad one axis of ``x`` up to exactly ``n`` (no-op when already
    there).  Every engine uses this to bring channel dims to the plan's
    padded sizes before entering a kernel."""
    c = x.shape[axis]
    if c == n:
        return x
    assert c < n, f"cannot pad axis {axis} from {c} down to {n}"
    pads = [(0, 0)] * x.ndim
    pads[axis % x.ndim] = (0, n - c)
    return jnp.pad(x, pads)


def _channel_tiles(c: int, contraction: bool) -> tuple[int, tuple[int, ...]]:
    """(padded_c, tiles) of one channel dim, the widest tile first.

    A contraction dim is the lane dim of the DMA'd source window, which
    Mosaic copies in whole 128-lane tiles, so it is padded to a multiple of
    128 (zero lanes add nothing to the GEMM); an output dim up to 128 stays
    whole, a wider one is padded to a multiple of 128.  A tile is any
    multiple of 128 that divides the padded dim, so a window's channel
    offset stays lane-aligned and every weight and output block is whole.
    The planner picks one together with the spatial tile
    (:func:`_search_tiles`)."""
    if c <= LANE and not contraction:
        return c, (c,)
    c_pad = _round_up(c, LANE)
    return c_pad, tuple(t for t in range(c_pad, 0, -LANE) if c_pad % t == 0)


def _phase_split(xp: jax.Array, s: tuple[int, int]) -> jax.Array:
    """(B, Hp, Wp, C) -> (s_h*s_w, B, ceil(Hp/s_h), ceil(Wp/s_w), C) phase
    planes; plane index = (h % s_h) * s_w + (w % s_w)."""
    s_h, s_w = s
    b, hp, wp, c = xp.shape
    hp2 = -(-hp // s_h) * s_h
    wp2 = -(-wp // s_w) * s_w
    xp = jnp.pad(xp, ((0, 0), (0, hp2 - hp), (0, wp2 - wp), (0, 0)))
    xp = xp.reshape(b, hp2 // s_h, s_h, wp2 // s_w, s_w, c)
    return xp.transpose(2, 4, 0, 1, 3, 5).reshape(
        s_h * s_w, b, hp2 // s_h, wp2 // s_w, c)


def _phase_unsplit(planes: jax.Array, s: tuple[int, int],
                   h: int, w: int) -> jax.Array:
    """(s_h*s_w, B, Hq, Wq, C) -> (B, h, w, C): the exact inverse of
    ``_phase_split`` -- a pure reshape/transpose/crop, no scatter."""
    s_h, s_w = s
    s2, b, hq, wq, c = planes.shape
    assert s2 == s_h * s_w
    x = planes.reshape(s_h, s_w, b, hq, wq, c).transpose(2, 3, 0, 4, 1, 5)
    return x.reshape(b, hq * s_h, wq * s_w, c)[:, :h, :w, :]


def _pack_source(xp: jax.Array, s: tuple[int, int],
                 pack: LanePack) -> jax.Array:
    """(B, C, Hp, Wp) padded input -> (len(pack.planes), B, Hq, Wq,
    width*C) lane-packed planes, zeros past each plane's end.

    W stays the last dim, and the shifts are stacked on a leading axis,
    until one transpose into the packed layout.  Built as NHWC pieces and
    concatenated along the lanes, XLA laid out each C-lane piece padded
    to 128 lanes in HBM: 7 GiB of temporaries for the stem's 32 pieces,
    in a compile for a TPU v5e."""
    s_h, s_w = s
    bsz, c, hp, wp = xp.shape
    hq, wq = _cdiv(hp, s_h), _cdiv(wp, s_w)
    a_hi = max(a for a, _ in pack.shifts)
    b_hi = max(b for _, b in pack.shifts)
    rows, cols = hq + a_hi, wq + b_hi
    xp = jnp.pad(xp, ((0, 0), (0, 0), (0, rows * s_h - hp),
                      (0, cols * s_w - wp)))
    planes = xp.reshape(bsz, c, rows, s_h, cols, s_w).transpose(
        0, 2, 3, 5, 1, 4).reshape(bsz, rows, s_h * s_w, c, cols)
    out = []
    for group in pack.planes:
        dense = jnp.stack([planes[:, :, p] for p in group], axis=2)
        dense = dense.reshape(bsz, rows, len(group) * c, cols)
        block = jnp.stack([dense[:, a:a + hq, :, b:b + wq]
                           for a, b in pack.shifts])
        block = block.transpose(1, 2, 4, 0, 3).reshape(
            bsz, hq, wq, len(pack.shifts) * len(group) * c)
        out.append(_pad_to(block, pack.width * c))
    return jnp.stack(out)


def _pack_weights(wt: jax.Array, pack: LanePack) -> jax.Array:
    """(T, C, N) real-tap weights -> (len(pack.taps), width*C, N): real
    tap k fills slot ``where[k][1]`` of packed tap ``where[k][0]``; every
    other (packed tap, slot) pair gets zero rows."""
    t, c, n = wt.shape
    idx = [[t] * pack.width for _ in pack.taps]       # t: the zero block
    for k, (pt, j) in enumerate(pack.where):
        idx[pt][j] = k
    wz = jnp.concatenate([wt, jnp.zeros((1, c, n), wt.dtype)])
    return wz[jnp.asarray(idx, jnp.int32)].reshape(len(pack.taps),
                                                   pack.width * c, n)


def _unpack_wgrad(dw: jax.Array, pack: LanePack) -> jax.Array:
    """(len(pack.taps), >= width*C, N) packed weight grad -> (T, C, N) of
    the real taps; the pairs no real tap maps to are dropped."""
    n = dw.shape[-1]
    dw = dw[:, :pack.width * pack.c].reshape(-1, pack.c, n)
    rows = [pt * pack.width + j for pt, j in pack.where]
    return dw[jnp.asarray(rows, jnp.int32)]


# ---------------------------------------------------------------------------
# Tile search: (spatial tile, cin tile, cout tile) under the VMEM budget
# ---------------------------------------------------------------------------

def _spatial_candidates(oh: int, ow: int):
    """Output tiles in search order: the whole (8-padded) width first,
    halving rows -- an untiled leading dim -- down to one, then halving
    the W tile in whole sublanes.  Tiles over :data:`MAX_TILE_ROWS` GEMM
    rows are skipped."""
    th, tw = oh, _round_up(ow, SUBLANE)
    while True:
        if th * tw <= MAX_TILE_ROWS:
            yield th, tw
        if th > 1:
            th = _cdiv(th, 2)
        elif tw > SUBLANE:
            tw = _round_up(_cdiv(tw, 2), SUBLANE)
        else:
            return


def _fitting_tiles(g: _Geom, budget: int):
    """Every candidate ``(th, tw, cit, cot, bytes)`` whose footprint fits,
    best first: the fewest grid steps, then the fewest operand bytes
    streamed, then one contraction step (the window is then fetched once
    per spatial tile), then the larger spatial tile.  Along one channel
    pair the spatial candidates only add grid steps, so the ranking of a
    layer with one channel pair is the spatial search order."""
    ranked = []
    for cit in g.cin_tiles:
        for cot in g.cout_tiles:
            for i, (th, tw) in enumerate(_spatial_candidates(g.oh, g.ow)):
                bytes_needed = g.cost(th, tw, cit, cot)
                if bytes_needed <= budget:
                    key = (*g.rank(th, tw, cit, cot), g.cin_pad // cit,
                           -th * tw, i)
                    ranked.append((key, (th, tw, cit, cot, bytes_needed)))
    ranked.sort()
    return [tile for _, tile in ranked]


def _search_tiles(g: _Geom, budget: int):
    """The best fitting ``(th, tw, cit, cot, bytes, fits)``; when nothing
    fits, the smallest candidate (1-row tile, narrowest channel tiles) with
    ``fits=False``."""
    fitting = _fitting_tiles(g, budget)
    if fitting:
        return (*fitting[0], True)
    cit, cot = g.cin_tiles[-1], g.cout_tiles[-1]
    *_, (th, tw) = _spatial_candidates(g.oh, g.ow)
    return th, tw, cit, cot, g.cost(th, tw, cit, cot), False


# ---------------------------------------------------------------------------
# Memoized tile plans (static per ConvDims x budget)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LanePack:
    """Lane packing of a narrow contraction: several taps share one
    128-lane contraction tile instead of each padding ``c`` channels to
    128 on its own.

    Packed plane ``i`` holds the phase planes ``planes[i]`` side by side
    in its lanes, repeated once per shift ``(a, b)`` of ``shifts``: slot
    ``j = k * len(planes[i]) + m`` is shift ``shifts[k]`` of phase plane
    ``planes[i][m]`` and fills lanes ``[j*c, (j+1)*c)``, so
    ``packed[i, b_, h, w, j*c + ch] = src[plane, b_, h + a, w + b, ch]``.
    ``taps`` is the kernel's tap table over the packed planes, and
    ``where[k] = (t, j)`` places real tap ``k``: packed tap ``t``, slot
    ``j``.  A (packed tap, slot) pair that no real tap maps to gets zero
    forward weights, and its weight-grad rows are dropped."""
    c: int
    planes: tuple[tuple[int, ...], ...]
    shifts: tuple[tuple[int, int], ...]
    taps: tuple[tuple[int, int, int], ...]
    where: tuple[tuple[int, int], ...]

    @property
    def slots(self) -> tuple[tuple[tuple[int, int, int], ...], ...]:
        """Per packed plane, ``(plane, a, b)`` of each slot in lane order."""
        return tuple(tuple((p, a, b) for a, b in self.shifts for p in group)
                     for group in self.planes)

    @property
    def width(self) -> int:
        """Slots of the widest packed plane."""
        return len(self.shifts) * max(len(g) for g in self.planes)

    @property
    def lane_fill(self) -> float:
        return self.width * self.c / LANE


def _shifts(span: int, most: int) -> int:
    """Fewest shifts, at most ``most``, that cut ``span`` offsets to as
    few packed offsets as ``most`` shifts would."""
    return _cdiv(span, _cdiv(span, min(span, most)))


def _lane_pack(taps, c: int) -> LanePack | None:
    """Pack the taps of a ``c``-channel contraction into 128 lanes, or
    None when ``c > 64`` or packing would not shorten the tap table.

    Slots are taken in this order while they fit ``g = 128 // c``: phase
    planes first (disjoint data: no byte is added), then W shifts (with
    every W offset covered, each packed slice is sublane-aligned,
    ``dv' = 0``), then H shifts.  A shift count is cut to the fewest that
    give the same packed offsets.  Offsets step by the gcd of the real
    ones, so a dilated table's gaps take no slot.  The packed source
    never outgrows the unpacked one: a packed plane holds at least one
    phase plane's worth of lanes in place of a whole 128-lane plane, and
    a shift only shortens the halo."""
    g = LANE // c
    if g < 2:
        return None
    planes = sorted({p for p, _, _ in taps})
    step_h = math.gcd(*(du for _, du, _ in taps)) or 1
    step_w = math.gcd(*(dv for _, _, dv in taps)) or 1
    k = min(len(planes), g)
    nb = _shifts(1 + max(dv for *_, dv in taps) // step_w, g // k)
    na = _shifts(1 + max(du for _, du, _ in taps) // step_h, g // (k * nb))
    groups = tuple(tuple(planes[i:i + k]) for i in range(0, len(planes), k))
    shifts = tuple((a * step_h, b * step_w)
                   for a in range(na) for b in range(nb))
    packed: dict[tuple[int, int, int], int] = {}
    where = []
    for p, du, dv in taps:
        i = planes.index(p) // k
        a = du // step_h % na
        b = dv // step_w % nb
        t = packed.setdefault(
            (i, du - a * step_h, dv - b * step_w), len(packed))
        where.append((t, (a * nb + b) * len(groups[i])
                      + groups[i].index(p)))
    if len(packed) >= len(taps):
        return None
    return LanePack(c, groups, shifts, tuple(packed), tuple(where))


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """One Pallas dispatch: channel + spatial tiling, tap table, footprint.

    ``taps`` is always the REAL tap table.  ``pack`` is set when the
    contraction is lane-packed (:class:`LanePack`); the kernel then runs
    ``pack.taps`` over the packed source, and ``halo_*`` and
    ``bytes_needed`` describe that packed window.
    """
    fits: bool
    cin_pad: int
    cin_tile: int
    cout_pad: int
    cout_tile: int
    taps: tuple[tuple[int, int, int], ...]
    oh_tile: int
    ow_tile: int
    n_th: int
    n_tw: int
    halo_h: int
    halo_w: int
    bytes_needed: int
    pack: LanePack | None = None

    @property
    def spatial_splits(self) -> int:
        return self.n_th * self.n_tw

    @property
    def kernel_taps(self) -> tuple[tuple[int, int, int], ...]:
        """The tap table the kernel runs: packed when ``pack`` is set."""
        return self.taps if self.pack is None else self.pack.taps


@dataclasses.dataclass(frozen=True)
class PhasePlan:
    """Fused input-grad dispatch: uniform geometry for ALL S*S output stride
    phases, realized as ONE ``tap_gemm_phased`` launch.

    Per-phase tap offsets are pre-shifted by ``off_phase - min(off)`` so
    every phase reads the same globally padded dY at a uniform base; the
    output planes are un-phase-split by the inverse of ``_phase_split``.
    """
    n_qh: int            # uniform per-phase output rows = ceil(H_i / s_h)
    n_qw: int
    g_lo_h: int          # global low-side dY padding (covers min offset)
    g_lo_w: int
    t_max: int           # widest per-phase tap table (stack padded to this)
    phase_specs: tuple   # per plane r_h*s_w+r_w: (row idxs, col idxs) into
                         # rot180(compact kernel), or None (phase gets zero)
    phase_taps: tuple    # per plane: tuple[(j, du, dv), ...]
    tile: TilePlan


def _forward_taps(d: ConvDims) -> tuple[tuple[int, int, int], ...]:
    """Real kernel tap (kh, kw) -> (phase plane, du, dv) over the split
    input.  Per-axis phases (``s_h x s_w`` planes) and dilation-native:
    only effective positions that hold a real tap (multiples of D_h/D_w)
    are enumerated, so a dilated kernel contributes ``k_taps_h * k_taps_w``
    GEMMs instead of ``K_h * K_w`` -- the zero taps are skipped at plan
    time, not multiplied at run time."""
    return tuple(((kh % d.s_h) * d.s_w + (kw % d.s_w),
                  kh // d.s_h, kw // d.s_w)
                 for kh in range(0, d.K_h, d.D_h)
                 for kw in range(0, d.K_w, d.D_w))


def _budget_or_default(budget: int | None) -> int:
    return config.vmem_budget_bytes if budget is None else budget


@dataclasses.dataclass(frozen=True)
class _Geom:
    """Everything one planning problem fixes before a tile is chosen: the
    output plane the tiles cover, the channel padding and the channel
    tiles to choose from, the tap table and halo, and per candidate tile
    its VMEM footprint and its grid cost."""
    oh: int
    ow: int
    cin_pad: int
    cin_tiles: tuple[int, ...]
    cout_pad: int
    cout_tiles: tuple[int, ...]
    taps: tuple
    halo_h: int
    halo_w: int
    cost: Callable[[int, int, int, int], int]   # (th, tw, cit, cot) -> bytes
    rank: Callable[[int, int, int, int], tuple[int, int]]   # _grid_rank
    phase: tuple | None = None            # _input_grad_geom, input_grad only
    pack: LanePack | None = None          # forward / weight_grad only

    def plan(self, th, tw, cit, cot, bytes_needed, fits=True) -> TilePlan:
        return TilePlan(fits, self.cin_pad, cit, self.cout_pad, cot,
                        self.taps, th, tw, _cdiv(self.oh, th),
                        _cdiv(self.ow, tw), self.halo_h, self.halo_w,
                        bytes_needed, self.pack)


def _grid_rank(b: int, oh: int, ow: int, cin_pad: int, cout_pad: int,
               phases: int, weight_bytes: int | None):
    """``(th, tw, cit, cot) -> (grid steps, operand bytes streamed)`` of
    one pass: ``b * n_th * n_tw`` tiles, each in ``phases * cout_steps *
    cin_steps`` steps.  A tap GEMM streams one weight block a step, so
    all ``weight_bytes`` once per tile, or once in all when the grid holds
    a single block; the weight grad (``weight_bytes=None``) streams one dY
    block a step, so dY once per contraction step."""
    def rank(th, tw, cit, cot):
        tiles = b * _cdiv(oh, th) * _cdiv(ow, tw)
        cout_steps = cout_pad // cot
        blocks = phases * (cin_pad // cit) * cout_steps
        if weight_bytes is not None:
            streamed = weight_bytes * (tiles if blocks > 1 else 1)
        else:
            streamed = tiles * th * tw * cout_pad * tg._F32 * (
                cin_pad // cit if tiles * cout_steps > 1 else 1)
        return tiles * blocks, streamed
    return rank


def _geom(role: str, d: ConvDims) -> _Geom:
    """The tiling problem of one pass.  Forward and weight grad contract
    over C (input channels) into N; the fused input grad contracts over N
    into C across all stride phases."""
    if role in ("forward", "weight_grad"):
        taps = _forward_taps(d)
        pack = _lane_pack(taps, d.C)
        planes, kernel_taps = ((d.s_h * d.s_w, taps) if pack is None
                               else (len(pack.planes), pack.taps))
        halo_h, halo_w = _taps_halo(kernel_taps)
        cin_p, cits = _channel_tiles(d.C, contraction=True)
        cout_p, cots = _channel_tiles(d.N, contraction=False)
        vmem = (tg.tap_gemm_vmem if role == "forward"
                else tg.tap_wgrad_vmem)
        t = len(kernel_taps)
        weights = (t * cin_p * cout_p * tg._F32 if role == "forward"
                   else None)
        return _Geom(d.H_o, d.W_o, cin_p, cits, cout_p, cots, taps,
                     halo_h, halo_w,
                     lambda th, tw, cit, cot: vmem(planes, t, th, tw, halo_h,
                                                   halo_w, cit, cot),
                     _grid_rank(d.B, d.H_o, d.W_o, cin_p, cout_p, 1,
                                weights),
                     pack=pack)
    if role == "input_grad":
        phase = _input_grad_geom(d)
        n_qh, n_qw, _, _, t_max, _, _, halo_h, halo_w = phase
        cin_p, cits = _channel_tiles(d.N, contraction=True)
        cout_p, cots = _channel_tiles(d.C, contraction=False)
        return _Geom(n_qh, n_qw, cin_p, cits, cout_p, cots, (), halo_h,
                     halo_w,
                     lambda th, tw, cit, cot: tg.tap_gemm_vmem(
                         1, t_max, th, tw, halo_h, halo_w, cit, cot),
                     _grid_rank(d.B, n_qh, n_qw, cin_p, cout_p,
                                d.s_h * d.s_w, d.s_h * d.s_w * t_max
                                * cin_p * cout_p * tg._F32),
                     phase)
    raise ValueError(f"unknown plan role {role!r}; roles: {PLAN_ROLES}")


@functools.lru_cache(maxsize=4096)
def _input_grad_geom(d: ConvDims):
    """The fused-phase geometry shared by every input-grad tile candidate:
    (n_qh, n_qw, g_lo_h, g_lo_w, t_max, specs, taps_all, halo_h, halo_w).

    Row and column tap tables are independent: each axis runs its own
    ``phase_geometry`` under its own stride, and a kernel dilation drops
    the phase taps that land on a zero row/col of the dilated kernel
    (effective tap ``c + m*s`` is real iff it is a multiple of ``D``)."""
    s_h, s_w = d.s_h, d.s_w
    a_h, a_w = d.K_h - 1 - d.P_h, d.K_w - 1 - d.P_w
    n_qh, n_qw = _cdiv(d.H_i, s_h), _cdiv(d.W_i, s_w)
    geo_h = [phase_decomp.phase_geometry(r, a_h, s_h, d.K_h, d.H_i, d.H_o)
             for r in range(s_h)]
    geo_w = [phase_decomp.phase_geometry(r, a_w, s_w, d.K_w, d.W_i, d.W_o)
             for r in range(s_w)]
    # Per-axis (source offset m, compact-kernel index) lists, zero taps
    # dropped: rot180 commutes with dilation, so effective position
    # c + m*s of the rotated kernel is real iff divisible by D, and its
    # compact index is (c + m*s) // D.
    taps_h = [tuple((m, (geo_h[r][0] + m * s_h) // d.D_h)
                    for m in range(geo_h[r][1])
                    if (geo_h[r][0] + m * s_h) % d.D_h == 0)
              for r in range(s_h)]
    taps_w = [tuple((m, (geo_w[r][0] + m * s_w) // d.D_w)
                    for m in range(geo_w[r][1])
                    if (geo_w[r][0] + m * s_w) % d.D_w == 0)
              for r in range(s_w)]
    active = {(r_h, r_w) for r_h in range(s_h) for r_w in range(s_w)
              if r_h < d.H_i and r_w < d.W_i
              and taps_h[r_h] and taps_w[r_w]}
    if active:
        min_off_h = min(geo_h[r][2] for r, _ in active)
        min_off_w = min(geo_w[c][2] for _, c in active)
    else:                                  # dI identically zero; still plan
        min_off_h = min_off_w = 0
    base_h, g_lo_h = max(0, min_off_h), max(0, -min_off_h)
    base_w, g_lo_w = max(0, min_off_w), max(0, -min_off_w)

    specs, taps_all, t_max = [], [], 1
    halo_h = halo_w = 0
    for r_h in range(s_h):
        _, _, off_h, _ = geo_h[r_h]
        for r_w in range(s_w):
            _, _, off_w, _ = geo_w[r_w]
            if (r_h, r_w) not in active:
                specs.append(None)
                taps_all.append(())
                continue
            sh = base_h + (off_h - min_off_h)
            sw = base_w + (off_w - min_off_w)
            th_, tw_ = taps_h[r_h], taps_w[r_w]
            taps_all.append(tuple(
                (ih * len(tw_) + iw, sh + mh, sw + mw)
                for ih, (mh, _) in enumerate(th_)
                for iw, (mw, _) in enumerate(tw_)))
            specs.append((tuple(kh for _, kh in th_),
                          tuple(kw for _, kw in tw_)))
            t_max = max(t_max, len(th_) * len(tw_))
            halo_h = max(halo_h, sh + th_[-1][0])
            halo_w = max(halo_w, sw + tw_[-1][0])
    return (n_qh, n_qw, g_lo_h, g_lo_w, t_max, tuple(specs),
            tuple(taps_all), halo_h, halo_w)


def _phase_plan_of(d: ConvDims, geom, tile: TilePlan) -> PhasePlan:
    n_qh, n_qw, g_lo_h, g_lo_w, t_max, specs, taps_all, _, _ = geom
    return PhasePlan(n_qh, n_qw, g_lo_h, g_lo_w, t_max, specs, taps_all,
                     tile)


def _analytic_plan(role: str, d: ConvDims, budget: int) -> TilePlan:
    with _plan_clock():
        g = _geom(role, d)
        *tile, fits = _search_tiles(g, budget)
        plan = g.plan(*tile, fits=fits)
        _count_event(f"{role}_pallas" if fits else f"{role}_fallback")
        if fits and g.pack is not None:
            _count_event(f"{role}_packed")
        if fits and max(plan.cin_tile, plan.cout_tile) > LANE:
            _count_event(f"{role}_wide")
        return plan


def forward_plan(d: ConvDims, budget: int | None = None) -> TilePlan:
    d, budget = _canonical(d), _budget_or_default(budget)
    return _forward_plan(d, budget)


@functools.lru_cache(maxsize=4096)
def _forward_plan(d: ConvDims, budget: int) -> TilePlan:
    return _analytic_plan("forward", d, budget)


def weight_grad_plan(d: ConvDims, budget: int | None = None) -> TilePlan:
    d, budget = _canonical(d), _budget_or_default(budget)
    return _weight_grad_plan(d, budget)


@functools.lru_cache(maxsize=4096)
def _weight_grad_plan(d: ConvDims, budget: int) -> TilePlan:
    return _analytic_plan("weight_grad", d, budget)


def input_grad_plan(d: ConvDims,
                    budget: int | None = None) -> PhasePlan | None:
    d, budget = _canonical(d), _budget_or_default(budget)
    return _input_grad_plan(d, budget)


@functools.lru_cache(maxsize=4096)
def _input_grad_plan(d: ConvDims, budget: int) -> PhasePlan | None:
    """Single fused dispatch plan for all s_h*s_w output stride phases, or
    None only when even the minimal tiling exceeds the budget (the op then
    falls back to the jnp phase decomposition)."""
    tile = _analytic_plan("input_grad", d, budget)
    if not tile.fits:
        return None
    return _phase_plan_of(d, _input_grad_geom(d), tile)


#: the three tap-GEMM pass roles the planners speak.
PLAN_ROLES = ("forward", "weight_grad", "input_grad")


# ---------------------------------------------------------------------------
# Halo export for mesh-parallel spatial sharding (repro.dist.conv_parallel)
# ---------------------------------------------------------------------------

def tap_span(d: ConvDims) -> tuple[int, int]:
    """Per-axis extent of the KEPT (real) kernel taps.

    Recovered from the same tap table the tile planners dispatch with
    (:func:`_forward_taps`): a tap ``(plane, du, dv)`` sits at effective
    kernel position ``(du*s_h + plane//s_w, dv*s_w + plane%s_w)``.  Zero
    taps dropped at plan time (dilation) never enter the table, so the
    span is the real footprint -- the quantity a spatial halo exchange
    must cover, with no zero-space counted."""
    taps = _forward_taps(_canonical(d))
    span_h = 1 + max(du * d.s_h + p // d.s_w for p, du, dv in taps)
    span_w = 1 + max(dv * d.s_w + p % d.s_w for p, du, dv in taps)
    return span_h, span_w


def shard_halo(d: ConvDims) -> tuple[tuple[int, int], tuple[int, int]]:
    """Per-axis ``((lo_h, hi_h), (lo_w, hi_w))`` halo rows/cols a spatial
    shard must exchange with its neighbors, in INPUT-plane units.

    Adjacent stride windows overlap by exactly ``span - stride`` rows
    (window ``o`` ends at ``o*s - P + span - 1``; window ``o+1`` starts at
    ``(o+1)*s - P``), so that is the total exchanged per boundary -- the
    tap-table counterpart of the planners' per-tile ``halo_h``/``halo_w``
    (:func:`_taps_halo` measures the same kept taps in phase-split rows).
    The split puts the low padding on the low side: an edge shard's
    ``ppermute`` then receives exactly the zero rows the global padding
    would have provided, and no zero-space ever crosses the wire.  A
    negative ``hi`` means adjacent windows do not even touch the last
    ``-hi`` local rows (e.g. 1x1 stride-2): the shard crops instead of
    exchanging."""
    d = _canonical(d)
    span_h, span_w = tap_span(d)
    return ((d.P_h, span_h - d.s_h - d.P_h),
            (d.P_w, span_w - d.s_w - d.P_w))


def plan_candidates(role: str, d: ConvDims, budget: int | None = None,
                    k: int = 4):
    """Up to ``k`` FITTING plans, best ranked first (first element == the
    planner's own plan), across spatial and channel tiles alike.
    ``input_grad`` candidates are full :class:`PhasePlan` objects sharing
    one geometry.  Pure and unmemoized; records no plan events.  Tests
    pass these through the kernels' ``plan=`` argument to check that every
    tiling computes the same answer."""
    d, budget = _canonical(d), _budget_or_default(budget)
    g = _geom(role, d)
    plans = [g.plan(*t) for t in _fitting_tiles(g, budget)[:k]]
    if role == "input_grad":
        return [_phase_plan_of(d, g.phase, t) for t in plans]
    return plans


_PLANNERS = {"forward_plan": _forward_plan,
             "weight_grad_plan": _weight_grad_plan,
             "input_grad_plan": _input_grad_plan}


def tile_plan_cache_info() -> dict[str, object]:
    """lru_cache stats per planner (hits prove trace-time memoization)."""
    return {name: fn.cache_info() for name, fn in _PLANNERS.items()}


def clear_tile_plan_cache() -> None:
    for fn in _PLANNERS.values():
        fn.cache_clear()


def plan_report(d: ConvDims, budget: int | None = None) -> dict[str, object]:
    """Static per-shape dispatch summary (used by benchmarks and tests).

    ``kernel_taps`` records the zero-skipping: ``real`` is the number of
    taps the Pallas GEMMs actually run (``k_taps_h * k_taps_w``);
    ``materialized`` is what the kernel-materialization lowering would run
    (``K_h * K_w``, the zero-dilated extent).  They differ exactly when the
    layer is dilated."""
    def _tile(p: TilePlan) -> dict[str, object]:
        t = {"fits": p.fits, "spatial_splits": p.spatial_splits,
             "spatial_tile": [p.oh_tile, p.ow_tile],
             "chan_tile": [p.cin_tile, p.cout_tile],
             "halo": [p.halo_h, p.halo_w],
             "taps": len(p.taps),
             "bytes_needed": p.bytes_needed}
        if p.pack is not None:
            t["pack"] = {"slots": sum(len(s) for s in p.pack.slots),
                         "packed_taps": len(p.pack.taps),
                         "lane_fill": p.pack.lane_fill}
        return t
    f = forward_plan(d, budget)
    wg = weight_grad_plan(d, budget)
    ig = input_grad_plan(d, budget)
    report = {
        "phases": d.s_h * d.s_w,
        "kernel_taps": {"real": d.k_taps_h * d.k_taps_w,
                        "materialized": d.K_h * d.K_w},
        "forward": _tile(f),
        "weight_grad": _tile(wg),
        "input_grad": ({"fused": True, "t_max": ig.t_max,
                        "taps_total": sum(len(t) for t in ig.phase_taps),
                        **_tile(ig.tile)}
                       if ig is not None else {"fused": False, "fits": False}),
        "pallas_path": bool(f.fits and wg.fits and ig is not None),
    }
    return report


# ---------------------------------------------------------------------------
# Forward convolution (implicit im2col, phase-split tap GEMM)
# ---------------------------------------------------------------------------

def conv2d_forward(x: jax.Array, w: jax.Array, d: ConvDims,
                   plan: TilePlan | None = None) -> jax.Array:
    """Forward conv through the tap-GEMM kernel.  ``w`` is the COMPACT
    kernel (``k_taps_h x k_taps_w`` spatial extent); when ``d`` carries a
    dilation the tap table skips the zero positions instead of the kernel
    being materialized to ``K_h x K_w``.  ``plan`` overrides the planner
    (:func:`plan_candidates`)."""
    assert w.shape[-2:] == (d.k_taps_h, d.k_taps_w), (w.shape, d)
    if plan is None:
        plan = forward_plan(d)
    if not plan.fits:
        return jax.lax.conv_general_dilated(
            x, w, (d.s_h, d.s_w), [(d.P_h, d.p_h_hi), (d.P_w, d.p_w_hi)],
            rhs_dilation=(d.D_h, d.D_w),
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
    with jax.named_scope(GLUE_SCOPE):
        xp = zero_pad(x, d.P_h, d.P_w, d.p_h_hi, d.p_w_hi)
        wt = w.transpose(2, 3, 1, 0).reshape(d.k_taps_h * d.k_taps_w, d.C,
                                             d.N)
        if plan.pack is None:                         # (sh*sw,B,Hq,Wq,C)
            src = _phase_split(_to_nhwc(xp), (d.s_h, d.s_w))
        else:
            src = _pack_source(xp, (d.s_h, d.s_w), plan.pack)
            wt = _pack_weights(wt, plan.pack)
        src = _pad_to(src, plan.cin_pad)
        wt = _pad_to(wt, plan.cin_pad, axis=1)
        wt = _pad_to(wt, plan.cout_pad, axis=2)
    y = tg.tap_gemm(src, wt, plan.kernel_taps, d.H_o, d.W_o,
                    cin_tile=plan.cin_tile, cout_tile=plan.cout_tile,
                    oh_tile=plan.oh_tile, ow_tile=plan.ow_tile,
                    out_dtype=x.dtype,
                    vmem_limit_bytes=config.vmem_budget_bytes)
    with jax.named_scope(GLUE_SCOPE):
        return _from_nhwc(y[..., :d.N])


# ---------------------------------------------------------------------------
# Input gradient (transposed mode): ALL stride phases in one fused launch
# ---------------------------------------------------------------------------

def conv2d_input_grad(dy: jax.Array, w: jax.Array, d: ConvDims,
                      plan: PhasePlan | None = None) -> jax.Array:
    """Input grad through ONE fused tap-GEMM launch.  ``w`` is the COMPACT
    kernel; the per-phase tap tables index straight into ``rot180(w)``
    (dilation's zero taps were dropped at plan time).  ``plan`` overrides
    the planner (:func:`plan_candidates`)."""
    assert w.shape[-2:] == (d.k_taps_h, d.k_taps_w), (w.shape, d)
    pp = input_grad_plan(d) if plan is None else plan
    if pp is None:
        w_eff = zero_insert(w, (d.D_h, d.D_w)) if d.has_dilation else w
        return phase_decomp.input_grad_phase(dy, w_eff, d)
    tile = pp.tile
    with jax.named_scope(GLUE_SCOPE):
        wf = rot180(w)                             # (N, C, k_taps, k_taps)
        blocks = []
        for spec in pp.phase_specs:
            if spec is None:                             # phase gets no taps
                blocks.append(jnp.zeros((pp.t_max, d.N, d.C), wf.dtype))
                continue
            rows, cols = spec
            wk = jnp.take(jnp.take(wf, jnp.asarray(rows, jnp.int32), axis=2),
                          jnp.asarray(cols, jnp.int32), axis=3)
            wk = wk.transpose(2, 3, 0, 1).reshape(len(rows) * len(cols),
                                                  d.N, d.C)
            blocks.append(_pad_to(wk, pp.t_max, axis=0))
        wk_stack = jnp.stack(blocks)                     # (sh*sw, T, N, C)
        wk_stack = _pad_to(wk_stack, tile.cin_pad, axis=2)
        wk_stack = _pad_to(wk_stack, tile.cout_pad, axis=3)
        src = jnp.pad(_to_nhwc(dy),                      # (B, Ho+lo, Wo+lo, N)
                      ((0, 0), (pp.g_lo_h, 0), (pp.g_lo_w, 0), (0, 0)))
        src = _pad_to(src, tile.cin_pad)
    out = tg.tap_gemm_phased(
        src, wk_stack, pp.phase_taps, pp.n_qh, pp.n_qw,
        cin_tile=tile.cin_tile, cout_tile=tile.cout_tile,
        oh_tile=tile.oh_tile, ow_tile=tile.ow_tile,
        out_dtype=dy.dtype,
        vmem_limit_bytes=config.vmem_budget_bytes)    # (sh*sw, B, qh, qw, C)
    with jax.named_scope(GLUE_SCOPE):
        di = _phase_unsplit(out[..., :d.C], (d.s_h, d.s_w), d.H_i, d.W_i)
        return _from_nhwc(di)


# ---------------------------------------------------------------------------
# Weight gradient (dilated mode): strided-view tap GEMM, batch-accumulated
# ---------------------------------------------------------------------------

def conv2d_weight_grad(x: jax.Array, dy: jax.Array, d: ConvDims,
                       plan: TilePlan | None = None) -> jax.Array:
    """Weight grad through the tap-wgrad kernel: one accumulated GEMM per
    REAL kernel tap, returned at the compact ``k_taps_h x k_taps_w``
    extent (a dilated kernel's zero taps get no gradient computed at
    all -- they would be discarded anyway).  ``plan`` overrides the
    planner (:func:`plan_candidates`)."""
    if plan is None:
        plan = weight_grad_plan(d)
    if not plan.fits:
        dw = phase_decomp.weight_grad_phase(x, dy, d)   # effective extent
        return dw[..., ::d.D_h, ::d.D_w] if d.has_dilation else dw
    with jax.named_scope(GLUE_SCOPE):
        xp = zero_pad(x, d.P_h, d.P_w, d.p_h_hi, d.p_w_hi)
        src = (_phase_split(_to_nhwc(xp), (d.s_h, d.s_w))
               if plan.pack is None
               else _pack_source(xp, (d.s_h, d.s_w), plan.pack))
        src = _pad_to(src, plan.cin_pad)
        dyn = _pad_to(_to_nhwc(dy), plan.cout_pad)
    dw = tg.tap_wgrad(src, dyn, plan.kernel_taps, d.H_o, d.W_o,
                      cin_tile=plan.cin_tile, cout_tile=plan.cout_tile,
                      oh_tile=plan.oh_tile, ow_tile=plan.ow_tile,
                      vmem_limit_bytes=config.vmem_budget_bytes)
    with jax.named_scope(GLUE_SCOPE):
        dw = dw[..., :d.N]
        dw = (dw[:, :d.C] if plan.pack is None
              else _unpack_wgrad(dw, plan.pack))
        dw = dw.reshape(d.k_taps_h, d.k_taps_w, d.C, d.N)
        return dw.transpose(3, 2, 0, 1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Deprecated module-global aliases (INTERPRET / VMEM_BUDGET_BYTES)
# ---------------------------------------------------------------------------
# The knobs moved to ``repro.config``.  Reads keep working silently (too
# many innocuous introspection sites); ASSIGNMENT -- the old footgun of
# mutating a module global -- forwards to ``config.update`` (which does the
# plan-cache invalidation the global never did) and warns.

_LEGACY_GLOBALS = {"INTERPRET": "interpret",
                   "VMEM_BUDGET_BYTES": "vmem_budget_bytes"}


class _OpsModule(types.ModuleType):
    def __getattr__(self, name):
        field = _LEGACY_GLOBALS.get(name)
        if field is None:
            raise AttributeError(
                f"module {self.__name__!r} has no attribute {name!r}")
        return getattr(config, field)

    def __setattr__(self, name, value):
        field = _LEGACY_GLOBALS.get(name)
        if field is None:
            super().__setattr__(name, value)
            return
        warnings.warn(
            f"setting repro.kernels.ops.{name} is deprecated; use "
            f"repro.config.update({field}=...)",
            DeprecationWarning, stacklevel=2)
        config.update(**{field: value})


sys.modules[__name__].__class__ = _OpsModule
