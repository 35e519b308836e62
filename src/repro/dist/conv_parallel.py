"""Mesh-parallel conv lowerings: ``shard_map`` + tap-derived halo exchange.

The tap-GEMM engines are single-device programs; this module makes them run
*sharded* without touching them.  A :class:`ConvParallel` policy names which
mesh axes shard which conv role -- batch, spatial H/W, Cin, Cout -- and
:func:`conv_mesh` installs a lowering hook on ``repro.core.conv`` that
intercepts every ``conv2d`` / ``conv2d_transpose`` in its dynamic extent.
Intercepted calls that pass :func:`plan_conv_sharding`'s divisibility and
geometry checks lower onto explicit per-pass ``shard_map`` bodies (wrapped in
their own ``custom_vjp``), everything else falls back to the single-device
custom_vjp with the reason recorded in ``dispatch_events`` /
``policy_decisions`` -- parallelism is a policy, never a crash.

Spatial sharding exchanges exactly the planner's tap-derived halos
(:func:`repro.kernels.ops.shard_halo`): ``lo = P_lo`` and
``hi = span - s - P_lo`` rows/cols per boundary, where ``span`` is the extent
of the KEPT kernel taps.  Dilation zeros are dropped from the tap table at
plan time, so no zero-space ever crosses the wire -- the paper's bandwidth
argument applied to the collective fabric.  ``ppermute`` destinations that
name nobody receive zeros, so edge shards get exactly the zero rows the
global padding would have provided: the halo exchange *is* the padding.

Reduction placement per pass (EcoFlow's observation that fwd/dgrad/wgrad
reduce over different axes):

    ==============  ===============  ===============  ==================
    pass            regular conv     transposed conv  psum axis
    ==============  ===============  ===============  ==================
    forward         contracts Cin    contracts Cin    ``cin`` shards
    input grad      contracts Cout   contracts Cout   ``cout`` shards
    weight grad     contracts B,H,W  contracts B,H,W  ``batch`` + spatial
    ==============  ===============  ===============  ==================

Transposed convs ride the mirror-conv identity end to end: the mirror input
plane (= the transposed layer's OUTPUT) is the halo-exchanged plane; the
transposed forward scatter-adds halo contributions (the transpose of the
regular gather), the transposed input grad gathers them.
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import math

from repro.core import conv as C
from repro.core.convspec import ConvSpec, ConvTransposeSpec
from repro.kernels.ops import shard_halo
from repro.dist.constraints import _active_mesh
from repro.obs import events as obs_events
from repro.obs import trace as obs_trace

#: conv-role names a plan can shard (event tags join them with "+").
ROLES = ("data", "h", "w", "cin", "cout")


def _mesh_axes(mesh) -> dict:
    return dict(mesh.shape)


def _size(mesh, axes) -> int:
    if not axes:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    shape = _mesh_axes(mesh)
    total = 1
    for a in axes:
        total *= shape.get(a, 1)
    return total


# ---------------------------------------------------------------------------
# Policy: which mesh axes shard which conv role
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ConvParallel:
    """Mesh-axis assignment per conv role.

    ``batch`` is a tuple of axis names carrying the batch dim; ``h``/``w``
    spatially partition the activation planes with halo exchange;
    ``cin``/``cout`` partition the channel contractions.  Hashable (rides
    inside the custom_vjp's nondiff plan argument).
    """

    batch: tuple[str, ...] = ()
    h: str | None = None
    w: str | None = None
    cin: str | None = None
    cout: str | None = None

    @classmethod
    def from_policy(cls, policy, mesh) -> "ConvParallel":
        """Resolve a ``dist.sharding`` policy name against a concrete mesh.

        ``tp``      -- batch over ("pod", "data"); Cout over "model" (the
                       conv analogue of the linear d_out="model" rule; Cin
                       stays replicated so it cannot collide with the batch
                       axes).
        ``dp_only`` -- pure data parallelism: batch over every axis.
        ``tp_rep``  -- batch over ("pod", "data"), params replicated.
        ``spatial`` -- batch over ("pod", "data"); H over "model" with halo
                       exchange (activation-heavy layers where channel
                       sharding starves the MXU).
        """
        if isinstance(policy, cls):
            return policy
        names = tuple(_mesh_axes(mesh))
        dp = tuple(a for a in ("pod", "data") if a in names)
        if policy == "dp_only":
            return cls(batch=tuple(a for a in ("pod", "data", "model")
                                   if a in names))
        if policy in ("tp", "tensor_parallel"):
            return cls(batch=dp, cout="model" if "model" in names else None)
        if policy == "tp_rep":
            return cls(batch=dp)
        if policy == "spatial":
            return cls(batch=dp, h="model" if "model" in names else None)
        raise ValueError(
            f"unknown conv mesh policy {policy!r}; expected a ConvParallel "
            f"or one of 'tp', 'dp_only', 'tp_rep', 'spatial'")

    @classmethod
    def coerce(cls, value, mesh) -> "ConvParallel":
        if isinstance(value, cls):
            return value
        return cls.from_policy(value, mesh)


# ---------------------------------------------------------------------------
# Plan: the checked, per-layer shard assignment
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ConvShardPlan:
    """One conv layer's mesh assignment after every divisibility / geometry
    check: the roles that survived, the tap-derived halos for the spatial
    ones, and the roles that were dropped with WHY (surfaced through
    ``dispatch_events`` / ``policy_decisions`` by the lowering hook)."""

    mesh: object
    batch: tuple[str, ...] = ()
    h: str | None = None
    w: str | None = None
    cin: str | None = None
    cout: str | None = None
    halo_h: tuple[int, int] = (0, 0)
    halo_w: tuple[int, int] = (0, 0)
    transposed: bool = False
    dropped: tuple[tuple[str, str], ...] = ()

    @property
    def roles(self) -> tuple[str, ...]:
        out = []
        if self.batch:
            out.append("data")
        for role in ("h", "w", "cin", "cout"):
            if getattr(self, role):
                out.append(role)
        return tuple(out)

    @property
    def tag(self) -> str:
        return "+".join(self.roles) or "replicated"

    def size(self, axes) -> int:
        return _size(self.mesh, axes)

    @property
    def batch_spec(self):
        if not self.batch:
            return None
        return self.batch if len(self.batch) > 1 else self.batch[0]


def _check_spatial(name: str, n: int, h_i: int, h_o: int, s: int,
                   lo: int, hi: int) -> str | None:
    """None if an input plane of ``h_i`` rows (output ``h_o``) can be cut
    into ``n`` uniform blocks whose stride windows tile exactly, else the
    reason it cannot."""
    if h_i % n:
        return f"{name}: input extent {h_i} % {n} shards != 0"
    if h_o % n:
        return f"{name}: output extent {h_o} % {n} shards != 0"
    if h_i != s * h_o:
        return (f"{name}: non-uniform geometry (input {h_i} != stride {s} x "
                f"output {h_o}); spatial sharding needs SAME-style padding")
    blk = h_i // n
    if lo > blk or hi > blk:
        return (f"{name}: halo ({lo}, {hi}) exceeds the {blk}-row shard "
                f"block (single-hop exchange)")
    return None


def plan_conv_sharding(x_shape, w_shape, spec, par: ConvParallel,
                       mesh) -> ConvShardPlan:
    """Validate a :class:`ConvParallel` request against one layer's geometry.

    Degrades per role, never whole-or-nothing: an indivisible batch drops
    only the batch sharding, a non-uniform plane drops only that spatial
    axis, a grouped conv drops only the channel roles -- each with a
    recorded reason.  Size-1 / absent-from-the-mesh axes are dropped
    silently (sharding over them is the identity).  ``mesh`` only needs a
    ``.shape`` mapping, so plans are testable without devices.
    """
    transposed = isinstance(spec, ConvTransposeSpec)
    d = (C.transpose_dims if transposed else C.spec_dims)(
        x_shape, w_shape, spec)
    shape = _mesh_axes(mesh)
    dropped: list[tuple[str, str]] = []
    used: set[str] = set()

    def usable(role: str, axes) -> tuple[str, ...]:
        """The present, size>1, not-yet-claimed axes of a role request."""
        keep = []
        for a in (axes if isinstance(axes, tuple) else (axes,)):
            if a is None:
                continue
            if a not in shape:
                dropped.append((role, f"axis {a!r} not in mesh "
                                      f"{tuple(shape)}"))
            elif a in used:
                dropped.append((role, f"axis {a!r} already claimed by "
                                      f"another role"))
            elif shape[a] > 1:
                keep.append(a)
        return tuple(keep)

    # batch ----------------------------------------------------------------
    batch = usable("data", par.batch)
    if batch:
        n = _size(mesh, batch)
        if d.B % n:
            dropped.append(("data", f"batch {d.B} % {n} shards != 0"))
            batch = ()
        else:
            used.update(batch)

    # spatial (regular: the input plane; transposed: the MIRROR input
    # plane, i.e. the transposed layer's output) --------------------------
    (lo_h, hi_h), (lo_w, hi_w) = shard_halo(d)
    h_axis = w_axis = None
    for role, axis, h_i, h_o, s, lo, hi in (
            ("h", par.h, d.H_i, d.H_o, d.s_h, lo_h, hi_h),
            ("w", par.w, d.W_i, d.W_o, d.s_w, lo_w, hi_w)):
        ax = usable(role, axis)
        if not ax:
            continue
        why = _check_spatial(role, shape[ax[0]], h_i, h_o, s, lo, hi)
        if why:
            dropped.append((role, why))
            continue
        used.add(ax[0])
        if role == "h":
            h_axis = ax[0]
        else:
            w_axis = ax[0]

    # channels (x_shape[1] is Cin for both layouts; Cout is w dim 0 for
    # regular OIHW, dim 1 x groups for transposed (C_in, C_out/g, ...)) ----
    cin_n = x_shape[1]
    cout_n = w_shape[1] * spec.groups if transposed else w_shape[0]
    cin_axis = cout_axis = None
    for role, axis, count in (("cin", par.cin, cin_n),
                              ("cout", par.cout, cout_n)):
        ax = usable(role, axis)
        if not ax:
            continue
        if spec.groups > 1:
            dropped.append((role, f"grouped conv (groups={spec.groups}): "
                                  f"channel sharding would split groups"))
            continue
        n = shape[ax[0]]
        if count % n:
            dropped.append((role, f"{role} {count} % {n} shards != 0"))
            continue
        used.add(ax[0])
        if role == "cin":
            cin_axis = ax[0]
        else:
            cout_axis = ax[0]

    return ConvShardPlan(
        mesh=mesh, batch=batch, h=h_axis, w=w_axis,
        cin=cin_axis, cout=cout_axis,
        halo_h=(lo_h, hi_h), halo_w=(lo_w, hi_w),
        transposed=transposed, dropped=tuple(dropped))


# ---------------------------------------------------------------------------
# Halo exchange: gather (fwd/wgrad) and its transpose, scatter-add (dgrad)
# ---------------------------------------------------------------------------

def _record_halo(op: str, axis_name: str, dim: int, send) -> None:
    """One bus event per halo ``ppermute`` send.  Runs at TRACE time, where
    shape/dtype are static, so the per-exchange byte count is exact for
    the lowered collective (per shard) and costs nothing at run time."""
    if obs_events.enabled():
        nbytes = int(math.prod(send.shape)) * send.dtype.itemsize
        obs_events.emit("halo", f"{op}:{axis_name}:dim{dim}",
                        bytes=nbytes, shape=[int(s) for s in send.shape])


def _halo_gather(x, axis_name: str, n: int, lo: int, hi: int, dim: int):
    """Extend a local block with ``lo`` rows from the low neighbor and
    ``hi`` from the high neighbor along ``dim``.  Unnamed ``ppermute``
    destinations receive zeros, so edge shards are extended with exactly
    the zero rows the global padding supplies -- no separate pad path.
    ``hi < 0`` crops instead (adjacent windows do not reach those rows)."""
    with obs_trace.span("halo:gather", axis=axis_name, dim=dim,
                        lo=lo, hi=hi, shards=n):
        parts = []
        if lo > 0:
            send = jax.lax.slice_in_dim(x, x.shape[dim] - lo, x.shape[dim],
                                        axis=dim)
            _record_halo("gather", axis_name, dim, send)
            parts.append(jax.lax.ppermute(
                send, axis_name, [(j, j + 1) for j in range(n - 1)]))
        parts.append(x)
        if hi > 0:
            send = jax.lax.slice_in_dim(x, 0, hi, axis=dim)
            _record_halo("gather", axis_name, dim, send)
            parts.append(jax.lax.ppermute(
                send, axis_name, [(j, j - 1) for j in range(1, n)]))
        out = jnp.concatenate(parts, axis=dim) if len(parts) > 1 else x
        if hi < 0:
            out = jax.lax.slice_in_dim(out, 0, out.shape[dim] + hi, axis=dim)
        return out


def _halo_scatter(x_ext, axis_name: str, n: int, lo: int, hi: int,
                  dim: int, block: int):
    """The exact transpose of :func:`_halo_gather`: fold an extended
    block's overhang rows back onto the neighbors that own them (summing,
    since seam outputs accumulate contributions from both sides).  Edge
    overhang that ``ppermute`` sends to nobody is dropped -- those are
    gradients of padding zeros."""
    with obs_trace.span("halo:scatter", axis=axis_name, dim=dim,
                        lo=lo, hi=hi, shards=n):
        if hi < 0:
            pad = [(0, 0)] * x_ext.ndim
            pad[dim] = (0, -hi)
            x_ext = jnp.pad(x_ext, pad)
            hi = 0
        x = jax.lax.slice_in_dim(x_ext, lo, lo + block, axis=dim)
        if lo > 0:
            send = jax.lax.slice_in_dim(x_ext, 0, lo, axis=dim)
            _record_halo("scatter", axis_name, dim, send)
            recv = jax.lax.ppermute(
                send, axis_name, [(j, j - 1) for j in range(1, n)])
            pad = [(0, 0)] * x.ndim
            pad[dim] = (block - lo, 0)
            x = x + jnp.pad(recv, pad)
        if hi > 0:
            send = jax.lax.slice_in_dim(x_ext, lo + block, lo + block + hi,
                                        axis=dim)
            _record_halo("scatter", axis_name, dim, send)
            recv = jax.lax.ppermute(
                send, axis_name, [(j, j + 1) for j in range(n - 1)])
            pad = [(0, 0)] * x.ndim
            pad[dim] = (0, block - hi)
            x = x + jnp.pad(recv, pad)
        return x


def _gather_spatial(x, plan: ConvShardPlan):
    if plan.h:
        x = _halo_gather(x, plan.h, plan.size(plan.h), *plan.halo_h, dim=2)
    if plan.w:
        x = _halo_gather(x, plan.w, plan.size(plan.w), *plan.halo_w, dim=3)
    return x


def _scatter_spatial(x_ext, plan: ConvShardPlan, blk_h: int, blk_w: int):
    # reverse order of _gather_spatial: scatter is its exact transpose,
    # corner halos retrace their two hops.
    if plan.w:
        x_ext = _halo_scatter(x_ext, plan.w, plan.size(plan.w),
                              *plan.halo_w, dim=3, block=blk_w)
    if plan.h:
        x_ext = _halo_scatter(x_ext, plan.h, plan.size(plan.h),
                              *plan.halo_h, dim=2, block=blk_h)
    return x_ext


def _ext(extent: int, n_shards: int, halo: tuple[int, int],
         sharded: bool) -> int:
    """Local gathered extent of one spatial axis."""
    if not sharded:
        return extent
    return extent // n_shards + halo[0] + halo[1]


def _local_spec(spec: ConvSpec, plan: ConvShardPlan) -> ConvSpec:
    """The per-shard geometry: padding zeroed on sharded axes (the halo
    exchange delivers the edge zeros), untouched elsewhere."""
    ph, pw = spec.padding
    if plan.h:
        ph = (0, 0)
    if plan.w:
        pw = (0, 0)
    return dataclasses.replace(spec, padding=(ph, pw))


def _local_tspec(spec: ConvTransposeSpec,
                 plan: ConvShardPlan) -> ConvTransposeSpec:
    """Transposed mirror of :func:`_local_spec`: padding AND
    output_padding zeroed on sharded axes, so each shard produces the full
    extended mirror plane and the scatter crops/folds the seams."""
    ph, pw = spec.padding
    oh, ow = spec.output_padding
    if plan.h:
        ph, oh = (0, 0), 0
    if plan.w:
        pw, ow = (0, 0), 0
    return dataclasses.replace(spec, padding=(ph, pw),
                               output_padding=(oh, ow))


def _wgrad_axes(plan: ConvShardPlan) -> tuple[str, ...]:
    """weight grad contracts batch x spatial: psum over all three."""
    return plan.batch + tuple(a for a in (plan.h, plan.w) if a)


# ---------------------------------------------------------------------------
# Regular conv: three shard_map lowerings
# ---------------------------------------------------------------------------

def _fwd_regular(x, w, spec: ConvSpec, policy, plan: ConvShardPlan):
    ls = _local_spec(spec, plan)

    def body(xb, wb):
        x_ext = _gather_spatial(xb, plan)
        d = C.spec_dims(x_ext.shape, wb.shape, ls)
        y = C._execute(
            "forward", policy.forward, d, False,
            lambda eng: C._forward(x_ext, C._weight_for(eng, wb, ls),
                                   d, eng, ls.groups))
        if plan.cin:
            y = jax.lax.psum(y, plan.cin)
        return y

    return jax.shard_map(
        body, mesh=plan.mesh,
        in_specs=(P(plan.batch_spec, plan.cin, plan.h, plan.w),
                  P(plan.cout, plan.cin, None, None)),
        out_specs=P(plan.batch_spec, plan.cout, plan.h, plan.w),
        check_vma=False)(x, w)


def _dgrad_regular(dy, w, x_shape, spec: ConvSpec, policy,
                   plan: ConvShardPlan):
    ls = _local_spec(spec, plan)
    b_loc = x_shape[0] // plan.size(plan.batch)
    c_loc = x_shape[1] // plan.size(plan.cin)
    blk_h, blk_w = (x_shape[2] // plan.size(plan.h),
                    x_shape[3] // plan.size(plan.w))
    h_ext = _ext(x_shape[2], plan.size(plan.h), plan.halo_h, bool(plan.h))
    w_ext = _ext(x_shape[3], plan.size(plan.w), plan.halo_w, bool(plan.w))

    def body(dyb, wb):
        d = C.spec_dims((b_loc, c_loc, h_ext, w_ext), wb.shape, ls)
        dx_ext = C._execute(
            "input_grad", policy.input_grad, d, False,
            lambda eng: C._input_grad(dyb, C._weight_for(eng, wb, ls),
                                      d, eng, ls.groups))
        if plan.cout:
            dx_ext = jax.lax.psum(dx_ext, plan.cout)
        return _scatter_spatial(dx_ext, plan, blk_h, blk_w)

    return jax.shard_map(
        body, mesh=plan.mesh,
        in_specs=(P(plan.batch_spec, plan.cout, plan.h, plan.w),
                  P(plan.cout, plan.cin, None, None)),
        out_specs=P(plan.batch_spec, plan.cin, plan.h, plan.w),
        check_vma=False)(dy, w)


def _wgrad_regular(x, dy, w_shape, spec: ConvSpec, policy,
                   plan: ConvShardPlan):
    ls = _local_spec(spec, plan)
    w_loc = (w_shape[0] // plan.size(plan.cout),
             w_shape[1] // plan.size(plan.cin), w_shape[2], w_shape[3])
    reduce_axes = _wgrad_axes(plan)

    def body(xb, dyb):
        x_ext = _gather_spatial(xb, plan)
        d = C.spec_dims(x_ext.shape, w_loc, ls)
        dw = C._execute(
            "weight_grad", policy.weight_grad, d, False,
            lambda eng: C._run_wgrad(x_ext, dyb, d, eng, ls))
        if reduce_axes:
            dw = jax.lax.psum(dw, reduce_axes)
        return dw

    return jax.shard_map(
        body, mesh=plan.mesh,
        in_specs=(P(plan.batch_spec, plan.cin, plan.h, plan.w),
                  P(plan.batch_spec, plan.cout, plan.h, plan.w)),
        out_specs=P(plan.cout, plan.cin, None, None),
        check_vma=False)(x, dy)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _sharded_conv2d(x, w, spec, policy, plan):
    return _fwd_regular(x, w, spec, policy, plan)


def _sharded_conv2d_fwd(x, w, spec, policy, plan):
    return _fwd_regular(x, w, spec, policy, plan), (x, w)


def _sharded_conv2d_bwd(spec, policy, plan, res, dy):
    x, w = res
    dx = _dgrad_regular(dy, w, x.shape, spec, policy, plan)
    dw = _wgrad_regular(x, dy, w.shape, spec, policy, plan)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_sharded_conv2d.defvjp(_sharded_conv2d_fwd, _sharded_conv2d_bwd)


# ---------------------------------------------------------------------------
# Transposed conv: every pass is a role-swap over the mirror dims; the
# mirror INPUT plane (= the transposed output) is the halo-exchanged one.
# ---------------------------------------------------------------------------

def _t_fwd(x, w, spec: ConvTransposeSpec, policy, plan: ConvShardPlan,
           y_hw: tuple[int, int]):
    tl = _local_tspec(spec, plan)
    blk_h, blk_w = (y_hw[0] // plan.size(plan.h),
                    y_hw[1] // plan.size(plan.w))

    def body(xb, wb):
        # Local zero-pad/zero-op geometry: the mirror input plane IS the
        # extended block (blk + lo + hi rows); scatter folds the seams.
        d = C.transpose_dims(xb.shape, wb.shape, tl)
        y_ext = C._execute(
            "forward", policy.forward, d, True,
            lambda eng: C._t_forward(xb, wb, d, eng, tl))
        if plan.cin:
            y_ext = jax.lax.psum(y_ext, plan.cin)
        return _scatter_spatial(y_ext, plan, blk_h, blk_w)

    return jax.shard_map(
        body, mesh=plan.mesh,
        in_specs=(P(plan.batch_spec, plan.cin, plan.h, plan.w),
                  P(plan.cin, plan.cout, None, None)),
        out_specs=P(plan.batch_spec, plan.cout, plan.h, plan.w),
        check_vma=False)(x, w)


def _t_dgrad(dy, w, x_shape, spec: ConvTransposeSpec, policy,
             plan: ConvShardPlan):
    tl = _local_tspec(spec, plan)
    x_loc = (x_shape[0] // plan.size(plan.batch),
             x_shape[1] // plan.size(plan.cin),
             x_shape[2] // plan.size(plan.h),
             x_shape[3] // plan.size(plan.w))

    def body(dyb, wb):
        dy_ext = _gather_spatial(dyb, plan)
        d = C.transpose_dims(x_loc, wb.shape, tl)
        dx = C._execute(
            "input_grad", policy.input_grad, d, True,
            lambda eng: C._forward(dy_ext, C._weight_for(eng, wb, tl),
                                   d, eng, tl.groups))
        if plan.cout:
            dx = jax.lax.psum(dx, plan.cout)
        return dx

    return jax.shard_map(
        body, mesh=plan.mesh,
        in_specs=(P(plan.batch_spec, plan.cout, plan.h, plan.w),
                  P(plan.cin, plan.cout, None, None)),
        out_specs=P(plan.batch_spec, plan.cin, plan.h, plan.w),
        check_vma=False)(dy, w)


def _t_wgrad(dy, x, x_shape, w_shape, spec: ConvTransposeSpec, policy,
             plan: ConvShardPlan):
    tl = _local_tspec(spec, plan)
    x_loc = (x_shape[0] // plan.size(plan.batch),
             x_shape[1] // plan.size(plan.cin),
             x_shape[2] // plan.size(plan.h),
             x_shape[3] // plan.size(plan.w))
    w_loc = (w_shape[0] // plan.size(plan.cin),
             w_shape[1] // plan.size(plan.cout), w_shape[2], w_shape[3])
    reduce_axes = _wgrad_axes(plan)

    def body(dyb, xb):
        dy_ext = _gather_spatial(dyb, plan)
        d = C.transpose_dims(x_loc, w_loc, tl)
        dw = C._execute(
            "weight_grad", policy.weight_grad, d, True,
            lambda eng: C._run_wgrad(dy_ext, xb, d, eng, tl))
        if reduce_axes:
            dw = jax.lax.psum(dw, reduce_axes)
        return dw

    return jax.shard_map(
        body, mesh=plan.mesh,
        in_specs=(P(plan.batch_spec, plan.cout, plan.h, plan.w),
                  P(plan.batch_spec, plan.cin, plan.h, plan.w)),
        out_specs=P(plan.cin, plan.cout, None, None),
        check_vma=False)(dy, x)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _sharded_conv2d_transpose(x, w, spec, policy, plan):
    y_hw = C.conv_transpose_output_shape(x.shape, w.shape, spec)[2:]
    return _t_fwd(x, w, spec, policy, plan, y_hw)


def _sharded_conv2d_transpose_fwd(x, w, spec, policy, plan):
    y_hw = C.conv_transpose_output_shape(x.shape, w.shape, spec)[2:]
    return _t_fwd(x, w, spec, policy, plan, y_hw), (x, w)


def _sharded_conv2d_transpose_bwd(spec, policy, plan, res, dy):
    x, w = res
    dx = _t_dgrad(dy, w, x.shape, spec, policy, plan)
    dw = _t_wgrad(dy, x, x.shape, w.shape, spec, policy, plan)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_sharded_conv2d_transpose.defvjp(_sharded_conv2d_transpose_fwd,
                                 _sharded_conv2d_transpose_bwd)


# ---------------------------------------------------------------------------
# The lowering hook: policy context + per-call plan + event recording
# ---------------------------------------------------------------------------

_STACK: list[tuple[object, object]] = []


def _record_plan(plan: ConvShardPlan, requested) -> None:
    suffix = "_T" if plan.transposed else ""
    for role, reason in plan.dropped:
        C._record_event(f"mesh:drop:{role}")
        if len(C.POLICY_DECISIONS) < C._MAX_DECISIONS:
            C.POLICY_DECISIONS.append({
                "pass": "mesh", "requested": str(requested),
                "engine": f"replicated:{role}", "reason": reason,
                "transpose": plan.transposed, "dims": ()})
    if plan.roles:
        C._record_event(f"mesh:conv2d{suffix}:{plan.tag}")
    else:
        C._record_event(f"mesh:fallback{suffix}")
        if len(C.POLICY_DECISIONS) < C._MAX_DECISIONS:
            C.POLICY_DECISIONS.append({
                "pass": "mesh", "requested": str(requested),
                "engine": "replicated",
                "reason": ("; ".join(r for _, r in plan.dropped)
                           or "no shardable role for this mesh"),
                "transpose": plan.transposed, "dims": ()})


def _maybe_lower(x, w, spec, policy):
    """``repro.core.conv.MESH_LOWERING`` hook: return a sharded lowering
    or ``NotImplemented`` (single-device custom_vjp proceeds)."""
    requested, mesh = _STACK[-1]
    if mesh is None:
        mesh = _active_mesh()
    if mesh is None:
        C._record_event("mesh:no_mesh")
        return NotImplemented
    par = ConvParallel.coerce(requested, mesh)
    plan = plan_conv_sharding(x.shape, w.shape, spec, par, mesh)
    _record_plan(plan, requested)
    if not plan.roles:
        return NotImplemented
    if plan.transposed:
        return _sharded_conv2d_transpose(x, w, spec, policy, plan)
    return _sharded_conv2d(x, w, spec, policy, plan)


@contextlib.contextmanager
def conv_mesh(policy, mesh=None):
    """Scoped mesh-parallel conv lowering for every conv2d /
    conv2d_transpose traced in the dynamic extent::

        with conv_parallel.conv_mesh("tp"):        # or a ConvParallel
            grads = jax.grad(loss)(params, batch)  # convs lower sharded

    ``policy`` is a :class:`ConvParallel`, a ``dist.sharding`` policy name
    (``"tp"`` / ``"dp_only"`` / ``"tp_rep"`` / ``"spatial"``), or None (a
    no-op, so call sites can thread an optional config through).  ``mesh``
    defaults to the enclosing ``with mesh:`` context at trace time.
    Applies at TRACE time, like :func:`repro.core.conv.conv_policy`.
    """
    if policy is None:
        yield None
        return
    if isinstance(policy, str) and policy not in (
            "tp", "tensor_parallel", "dp_only", "tp_rep", "spatial"):
        raise ValueError(f"unknown conv mesh policy {policy!r}")
    _STACK.append((policy, mesh))
    C.MESH_LOWERING = _maybe_lower
    try:
        yield policy
    finally:
        _STACK.pop()
        if not _STACK:
            C.MESH_LOWERING = None
