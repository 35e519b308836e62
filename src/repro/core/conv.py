"""Convolution with structured geometry and per-pass backprop engines.

The public surface is built from two objects (``repro.core.convspec``):

  * ``ConvSpec`` -- the layer geometry: per-axis stride, per-axis dilation,
    asymmetric padding, feature groups, activation layout;
  * ``EnginePolicy`` -- WHICH engine realizes each of the three lowered
    GEMMs (``forward`` / ``input_grad`` / ``weight_grad``), independently.

    y = conv2d(x, w, ConvSpec.make(stride=(2, 2), padding=1),
               EnginePolicy.parse("fwd=pallas,dgrad=auto,wgrad=bp_phase"))

Registered engines (``ENGINES``; extend with :func:`register_engine`):

  * ``"lax"``         -- XLA's native conv + autodiff (control / ground truth)
  * ``"traditional"`` -- explicit im2col with zero-space materialization (the
                         paper's baseline accelerator behaviour)
  * ``"bp_im2col"``   -- the paper's implicit algorithm: Algorithms 1 & 2
                         address mapping + gather (literal reproduction)
  * ``"bp_phase"``    -- stride-phase decomposition (same zero elimination,
                         dense MXU form; supports asymmetric strides)
  * ``"pallas"``      -- Pallas tap-GEMM kernels (explicit VMEM BlockSpecs;
                         per-axis tap tables, so asymmetric strides and
                         tap-native dilation are first-class)
  * ``"auto"``        -- not an engine: the resolver picks per pass.  It
                         consults the spec's geometry and the Pallas tile
                         planner (``repro.kernels.ops``): stride-1
                         undilated layers stay on the dense native path (no
                         zero-space to eliminate), strided OR dilated
                         layers take the Pallas tap-GEMM path whenever the
                         tile plan fits the VMEM budget, and every fallback
                         records WHY (:func:`policy_decisions`).

Engines that cannot serve a spec (asymmetric stride on an engine without
per-axis support -- declared via the ``asym_stride`` capability flag;
geometry outside the paper's ``P <= K - 1`` constraints on any implicit
engine; a tile plan over budget on ``pallas``) gracefully resolve to the
strongest capable engine -- the substitution is recorded, never silent:
:func:`dispatch_events` counts the engine *actually used* per pass and
:func:`policy_decisions` keeps the per-decision reasons.

The same ladder also runs at EXECUTION time (:func:`_execute`): an engine
that *raises* mid-pass is re-dispatched down the capability chain instead
of killing the step.  The failure edge is recorded
(``"pass:failed->survivor"`` in :func:`dispatch_events`, exception class in
:func:`runtime_failures`), the failing engine is quarantined for that
(pass, geometry) and probed for recovery after
:data:`QUARANTINE_PROBE_AFTER` dispatches, and a crashing pallas launch
poison-marks its plan-cache entry (``autotune.poison_plan``) so
``autotune="cached"`` cannot re-crash on restart.  ``lax`` is the terminal
anchor: never quarantined, and if every engine fails the first exception
propagates.

Dilation is lowered per engine, declared by the ``native_dilation``
capability flag.  Engines WITHOUT it get a dispatch-level kernel
materialization: the kernel is zero-dilated to its effective extent
(``K_eff = (K-1)*D + 1``) before entering the engine, and the weight
gradient's real taps are sliced back out -- exact, because the inserted
kernel zeros contribute nothing to ``y``/``dI`` and their ``dW`` entries
are discarded.  Engines WITH it (``pallas``) receive the compact kernel
untouched: their tap tables simply skip the zero positions, so a dilated
conv runs ``k_h*k_w`` tap-GEMMs instead of ``K_eff_h*K_eff_w`` --
~``1/(d_h*d_w)`` of the materialized FLOPs -- and the weight gradient is
computed only for real taps.  The materialization path stays registered as
the cross-check oracle the tests compare against.

``conv2d`` carries a ``jax.custom_vjp`` whose nondiff arguments are the
``(ConvSpec, EnginePolicy)`` pair, so ``jax.grad``, ``jit`` and ``vmap``
over any model transparently exercise a *mixed* datapath -- e.g. native
forward, Pallas input gradient, phase-decomposed weight gradient in one
training step.  :func:`conv_policy` is a context-manager override that
swaps the policy for every conv in scope (it beats per-call policies)
without rebuilding the model; it applies at trace time, so wrap the
``jit``/``grad`` call, not the cached executable.

Backward compatibility: the pre-ConvSpec surface
``conv2d(x, w, stride:int, padding, mode="bp_phase", groups)`` still works.
``mode=`` (kwarg or legacy 5th positional) maps to
``EnginePolicy.uniform(mode)`` and emits a ``DeprecationWarning``; loose
``stride=/padding=/dilation=/groups=`` kwargs are non-deprecated sugar that
builds the ``ConvSpec`` internally.  Passing a bare engine name as
``policy=`` is the blessed spelling of a uniform policy.
"""

from __future__ import annotations

import contextlib
import dataclasses
import warnings
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import bpim2col, im2col_ref, phase_decomp
from repro.core.config import config
from repro.obs import events as obs_events
from repro.obs import trace as obs_trace
from repro.core.convspec import (AUTO, ConvSpec, ConvTransposeSpec,
                                 EnginePolicy)
from repro.core.im2col_ref import ConvDims, rot180, zero_insert

Mode = str   # legacy alias: engine names are plain strings now


# ---------------------------------------------------------------------------
# Engine registry: forward / input-grad / weight-grad + capabilities
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Engine:
    """The three lowered GEMMs of one conv layer under one engine, plus the
    static capabilities the policy resolver gates on."""
    name: str
    forward: Callable      # (x, w, d) -> y
    input_grad: Callable   # (dy, w, d) -> dx   (transposed mode, Algorithm 1)
    weight_grad: Callable  # (x, dy, d) -> dw   (dilated mode, Algorithm 2)
    asym_stride: bool = False     # supports d.s_h != d.s_w
    paper_geometry: bool = True   # requires ConvDims.validate() (P <= K-1 ..)
    native_dilation: bool = False  # consumes the compact kernel and skips
    #                                dilation zero taps itself; False means
    #                                the dispatcher materializes the dilated
    #                                kernel before/after the engine runs
    native_transpose: bool = False  # serves a TRANSPOSED-conv forward
    #                                 implicitly (role-swapped onto its
    #                                 input_grad machinery, zero insertion
    #                                 never built); False means the
    #                                 dispatcher physically zero-inserts the
    #                                 input and runs the engine's ordinary
    #                                 stride-1 forward -- the materialization
    #                                 lowering that doubles as the oracle


def _pallas_forward(x, w, d):
    from repro.kernels import ops
    return ops.conv2d_forward(x, w, d)


def _pallas_input_grad(dy, w, d):
    from repro.kernels import ops
    return ops.conv2d_input_grad(dy, w, d)


def _pallas_weight_grad(x, dy, d):
    from repro.kernels import ops
    return ops.conv2d_weight_grad(x, dy, d)


def _lax_input_grad(dy, w, d):
    # Anchor: autodiff of the native conv (never dispatched through the
    # implicit path; used by engine "lax" and as the registry's control).
    x_shape = (d.B, d.C, d.H_i, d.W_i)
    _, vjp = jax.vjp(
        lambda x_: im2col_ref.conv2d_lax(x_, w, d),
        jnp.zeros(x_shape, dy.dtype))
    return vjp(dy)[0]


def _lax_weight_grad(x, dy, d):
    w_shape = (d.N, d.C, d.K_h, d.K_w)
    _, vjp = jax.vjp(
        lambda w_: im2col_ref.conv2d_lax(x, w_, d),
        jnp.zeros(w_shape, dy.dtype))
    return vjp(dy)[0]


ENGINES: dict[str, Engine] = {}


def register_engine(name: str, forward: Callable, input_grad: Callable,
                    weight_grad: Callable, *, asym_stride: bool = False,
                    paper_geometry: bool = True,
                    native_dilation: bool = False,
                    native_transpose: bool = False,
                    overwrite: bool = False) -> Engine:
    """Register a conv engine under ``name`` for use in any ``EnginePolicy``.

    The three callables take ``(x, w, d)`` / ``(dy, w, d)`` / ``(x, dy, d)``
    with ``d`` the per-group :class:`ConvDims`.  ``asym_stride`` declares
    support for ``d.s_h != d.s_w``; ``paper_geometry`` declares that the
    engine needs ``ConvDims.validate()`` to hold (the resolver falls back
    otherwise); ``native_dilation`` declares that the engine consumes the
    COMPACT kernel and handles ``d.D_h``/``d.D_w`` itself (skipping zero
    taps) -- without it, the dispatcher hands the engine a materialized
    zero-dilated kernel of extent ``K_eff`` and slices the real taps back
    out of its weight gradient.  ``native_transpose`` declares that the
    engine's ``input_grad`` implements the paper's transposed mode WITHOUT
    building the zero-spaced tensor, so a transposed-conv *forward* may be
    role-swapped onto it (and its ``forward``/``weight_grad`` serve the
    transposed layer's dX/dW, which are ordinary regular-conv passes) --
    without it, the dispatcher physically zero-inserts the input and runs
    the engine's ordinary stride-1 forward (the materialization lowering,
    kept as the cross-check oracle).  Re-registering an existing name
    requires ``overwrite=True``.
    """
    if name == AUTO or not name:
        raise ValueError(f"invalid engine name {name!r}")
    if name in ENGINES and not overwrite:
        raise ValueError(f"engine {name!r} is already registered "
                         "(pass overwrite=True to replace it)")
    eng = Engine(name, forward, input_grad, weight_grad,
                 asym_stride=asym_stride, paper_geometry=paper_geometry,
                 native_dilation=native_dilation,
                 native_transpose=native_transpose)
    ENGINES[name] = eng
    return eng


register_engine("lax", im2col_ref.conv2d_lax, _lax_input_grad,
                _lax_weight_grad, asym_stride=True, paper_geometry=False,
                native_transpose=True)
register_engine("traditional", im2col_ref.conv2d_forward_explicit,
                im2col_ref.input_grad_explicit,
                im2col_ref.weight_grad_explicit, asym_stride=True)
register_engine("bp_im2col", im2col_ref.conv2d_forward_explicit,
                bpim2col.input_grad_implicit,
                bpim2col.weight_grad_implicit, asym_stride=True,
                native_transpose=True)
register_engine("bp_phase", im2col_ref.conv2d_lax,
                phase_decomp.input_grad_phase,
                phase_decomp.weight_grad_phase, asym_stride=True,
                native_transpose=True)
register_engine("pallas", _pallas_forward, _pallas_input_grad,
                _pallas_weight_grad, asym_stride=True,
                native_dilation=True, native_transpose=True)

#: the built-in engine names (legacy export; registry may grow beyond it).
MODES: tuple[str, ...] = tuple(ENGINES)


def _engine(name: str) -> Engine:
    try:
        return ENGINES[name]
    except KeyError:
        raise ValueError(
            f"unknown conv engine {name!r}; choose from "
            f"{tuple(ENGINES)} or 'auto'") from None


# ---------------------------------------------------------------------------
# Geometry: ConvSpec + shapes -> per-group ConvDims (dilation folded in)
# ---------------------------------------------------------------------------

def make_dims(x_shape, w_shape, stride=1, padding=0,
              groups: int = 1, dilation=1) -> ConvDims:
    """Per-group ConvDims: C and N are the per-group channel counts.

    ``stride``/``dilation`` accept an int or a per-axis pair.  Dilation is
    folded into the kernel extent (``K_h``/``K_w`` are the EFFECTIVE
    ``K_eff``) and also recorded per axis (``D_h``/``D_w``), so
    materializing engines and the tap-native Pallas engine both read the
    geometry they need from the same dims.
    """
    return spec_dims(x_shape, w_shape,
                     ConvSpec.make(stride=stride, padding=padding,
                                   dilation=dilation, groups=groups))


def spec_dims(x_shape, w_shape, spec: ConvSpec) -> ConvDims:
    """The per-group ``ConvDims`` a ``(x, w, spec)`` triple dispatches with."""
    b, c, h, w = x_shape
    n, cg, kh, kw = w_shape
    g = spec.groups
    assert c == cg * g, (
        f"channel mismatch: input C={c}, weight C/g={cg}, groups={g}")
    assert n % g == 0, f"N={n} not divisible by groups={g}"
    keff_h, keff_w = spec.effective_kernel(kh, kw)
    (ph_lo, ph_hi), (pw_lo, pw_hi) = spec.padding
    d = ConvDims(B=b, C=cg, H_i=h, W_i=w, N=n // g,
                 K_h=keff_h, K_w=keff_w,
                 S=spec.s_h, S_w=(-1 if spec.s_w == spec.s_h else spec.s_w),
                 P_h=ph_lo, P_w=pw_lo, P_h_hi=ph_hi, P_w_hi=pw_hi,
                 D_h=spec.d_h, D_w=spec.d_w)
    if d.H_o < 1 or d.W_o < 1:
        # A mis-sized layer, not a capability question: fail at trace time
        # for EVERY engine rather than training on empty activations.
        raise ValueError(
            f"conv output plane is empty ({d.H_o}x{d.W_o}): input "
            f"{h}x{w}, effective kernel {keff_h}x{keff_w} "
            f"(dilation {spec.dilation}), stride {spec.stride}, "
            f"padding {spec.padding}")
    return d


def transpose_dims(x_shape, w_shape, spec: ConvTransposeSpec) -> ConvDims:
    """Per-group ``ConvDims`` of the MIRROR regular conv of a transposed
    layer.

    A transposed conv with forward stride ``s`` *is* the input gradient
    (the paper's transposed mode) of a regular conv whose output plane is
    the transposed layer's input: its ``ConvDims`` carry the transposed
    spec's stride/dilation/padding verbatim, its input plane is the
    transposed layer's OUTPUT, and ``output_padding`` lands exactly on the
    tiling remainder ``R`` (the extra high-side rows/cols the mirror
    conv's last stride window does not reach -- already first-class since
    the engines support general ``R``).  Every engine pass of the
    transposed layer is then a role-swap of the mirror conv's passes:

        forward      -> mirror input_grad   (Algorithm 1 / tap-GEMM phases)
        input grad   -> mirror forward      (an ordinary strided conv)
        weight grad  -> mirror weight_grad  (Algorithm 2, roles swapped)

    Weights ``(C_in, C_out/g, K_h, K_w)`` are the mirror conv's OIHW
    weights unchanged (``N = C_in/g`` per group, ``C = C_out/g``).
    """
    b, cin, h, w = x_shape
    cin2, cog, kh, kw = w_shape
    g = spec.groups
    assert cin == cin2, (
        f"channel mismatch: input C={cin}, weight C_in={cin2}")
    assert cin % g == 0, f"C_in={cin} not divisible by groups={g}"
    keff_h, keff_w = spec.effective_kernel(kh, kw)
    (ph_lo, ph_hi), (pw_lo, pw_hi) = spec.padding
    h_out, w_out = spec.output_shape(h, w, kh, kw)
    if h_out < 1 or w_out < 1:
        raise ValueError(
            f"transposed-conv output plane is empty ({h_out}x{w_out}): "
            f"input {h}x{w}, effective kernel {keff_h}x{keff_w} "
            f"(dilation {spec.dilation}), stride {spec.stride}, "
            f"padding {spec.padding}, output_padding {spec.output_padding}")
    d = ConvDims(B=b, C=cog, H_i=h_out, W_i=w_out, N=cin // g,
                 K_h=keff_h, K_w=keff_w,
                 S=spec.s_h, S_w=(-1 if spec.s_w == spec.s_h else spec.s_w),
                 P_h=ph_lo, P_w=pw_lo, P_h_hi=ph_hi, P_w_hi=pw_hi,
                 D_h=spec.d_h, D_w=spec.d_w)
    # The mirror conv must reproduce the transposed layer's input plane
    # exactly, with output_padding as the remainder (guaranteed by the
    # spec's 0 <= output_padding < stride validation).
    assert d.H_o == h and d.W_o == w, (d, x_shape, spec)
    assert d.R_h == spec.op_h and d.R_w == spec.op_w, (d, spec)
    return d


def conv_transpose_output_shape(x_shape, w_shape,
                                spec: ConvTransposeSpec) \
        -> tuple[int, int, int, int]:
    """The exact output shape of ``conv2d_transpose`` in the spec's layout:
    (B, C_out, H_out, W_out) for NCHW, (B, H_out, W_out, C_out) for NHWC."""
    b = x_shape[0]
    cout = w_shape[1] * spec.groups
    h, w = (x_shape[2], x_shape[3]) if spec.layout == "NCHW" \
        else (x_shape[1], x_shape[2])
    h_out, w_out = spec.output_shape(h, w, w_shape[2], w_shape[3])
    if spec.layout == "NHWC":
        return b, h_out, w_out, cout
    return b, cout, h_out, w_out


def transpose_tap_counts(d: ConvDims) -> dict[str, object]:
    """The zero-insertion accounting of one transposed-conv forward.

    ``real`` is the number of tap-GEMMs the fused phase plan actually runs
    across all ``s_h*s_w`` output phases (every real kernel tap belongs to
    exactly one phase, so full coverage totals ``k_taps_h * k_taps_w``);
    ``zero_inserted`` is what a stride-1 dense conv over the physically
    zero-inserted input would run over the same phase grid
    (``s_h*s_w*K_eff_h*K_eff_w``).  ``skip_ratio`` is therefore
    ``1 - 1/(s_h*s_w)`` for a dense kernel, and folds in the additional
    ``1/(d_h*d_w)`` kernel-dilation skipping."""
    from repro.kernels import ops
    pp = ops.input_grad_plan(d)
    if pp is not None:
        real = sum(len(t) for t in pp.phase_taps)
    else:   # jnp phase-decomposition fallback: per-phase subsamples of the
            # zero-dilated kernel (every effective position in one phase)
        real = d.K_h * d.K_w
    zero_inserted = d.s_h * d.s_w * d.K_h * d.K_w
    return {"real": real, "zero_inserted": zero_inserted,
            "skip_ratio": round(1.0 - real / zero_inserted, 3)}


def _dilate_weight(w: jax.Array, spec: ConvSpec) -> jax.Array:
    """Materialize the dilated kernel (zeros between taps) so an engine
    WITHOUT native dilation sees an ordinary dense conv of extent K_eff."""
    if not spec.has_dilation:
        return w
    return zero_insert(w, (spec.d_h, spec.d_w))


def _undilate_dweight(dw_eff: jax.Array, spec: ConvSpec) -> jax.Array:
    """Slice the real taps back out of the effective-kernel weight grad."""
    if not spec.has_dilation:
        return dw_eff
    return dw_eff[..., ::spec.d_h, ::spec.d_w]


def _weight_for(eng: Engine, w: jax.Array, spec: ConvSpec) -> jax.Array:
    """The kernel an engine consumes: compact for native-dilation engines
    (their tap tables skip the zero positions), materialized otherwise."""
    return w if eng.native_dilation else _dilate_weight(w, spec)


# ---------------------------------------------------------------------------
# Policy resolution: requested engine -> engine actually dispatched
# ---------------------------------------------------------------------------

#: (pass, engine-actually-used) trace-time counters, key "pass:engine".
DISPATCH_EVENTS: dict[str, int] = {}

#: mesh-parallel lowering hook, installed by
#: ``repro.dist.conv_parallel.conv_mesh``.  Called as ``hook(x, w, spec,
#: policy)`` with the NCHW-normalized spec (ConvSpec or ConvTransposeSpec);
#: returns the sharded result or ``NotImplemented`` to decline, in which
#: case the single-device custom_vjp proceeds unchanged.  A mesh-aware
#: RESOLUTION step, not an engine: inside the sharded lowering every local
#: pass still dispatches through ``resolve_engine``/``_execute``.
MESH_LOWERING = None


def _mesh_dispatch(fn, x, w, spec, policy):
    """Offer one conv call to the mesh hook before the single-device vjp."""
    hook = MESH_LOWERING
    if hook is not None:
        out = hook(x, w, spec, policy)
        if out is not NotImplemented:
            return out
    return fn(x, w, spec, policy)

#: per-decision log: requested engine, engine used, and why (bounded).
POLICY_DECISIONS: list[dict] = []
_MAX_DECISIONS = 512


def dispatch_events() -> dict[str, int]:
    """Counts of the engine ACTUALLY used per pass (``"input_grad:pallas"``
    -> n), recorded at trace time inside the custom_vjp.  A jit cache hit
    does not re-trace and therefore does not re-count."""
    return dict(DISPATCH_EVENTS)


def policy_decisions() -> list[dict]:
    return list(POLICY_DECISIONS)


def reset_dispatch_events() -> None:
    DISPATCH_EVENTS.clear()
    POLICY_DECISIONS.clear()
    RUNTIME_FAILURES.clear()
    _QUARANTINE.clear()
    # Keep the bus-backed view (obs.events.counters("dispatch")) in lockstep
    # with the legacy dict under every reset pattern (no-op when off).
    obs_events.drop("dispatch")


def _paper_geometry_gap(d: ConvDims) -> str | None:
    """The ``ConvDims.validate()`` conditions, evaluated explicitly: the
    resolver ROUTES on this (not just error messaging), so it must not
    evaporate under ``python -O`` the way a bare assert would."""
    if d.H_o < 1 or d.W_o < 1:
        return f"empty output plane ({d.H_o}x{d.W_o})"
    if d.K_h - 1 - d.P_h < 0 or d.K_w - 1 - d.P_w < 0:
        return "transposed-conv padding K-1-P is negative"
    if d.K_h - 1 - d.p_h_hi + d.R_h < 0 or d.K_w - 1 - d.p_w_hi + d.R_w < 0:
        return "high-side transposed-conv padding K-1-P_hi+R is negative"
    return None


def _capability_gap(e: Engine, d: ConvDims) -> str | None:
    """None when ``e`` can serve geometry ``d``, else the human reason."""
    if d.s_h != d.s_w and not e.asym_stride:
        return (f"asymmetric stride ({d.s_h}, {d.s_w}) needs per-axis phase "
                "support")
    if e.paper_geometry:
        gap = _paper_geometry_gap(d)
        if gap is not None:
            return f"geometry outside the paper's constraints ({gap})"
    return None


#: transposed-conv pass -> the MIRROR regular-conv pass it role-swaps onto.
_TRANSPOSE_ROLE = {"forward": "input_grad", "input_grad": "forward",
                   "weight_grad": "weight_grad"}


def _pallas_fits(pass_name: str, d: ConvDims,
                 transposed: bool = False) -> bool:
    from repro.kernels import ops
    if transposed:
        pass_name = _TRANSPOSE_ROLE[pass_name]
    if pass_name == "forward":
        return ops.forward_plan(d).fits
    if pass_name == "input_grad":
        return ops.input_grad_plan(d) is not None
    return ops.weight_grad_plan(d).fits


_FALLBACK_CHAIN = ("bp_phase", "lax")


def _first_capable(d: ConvDims, reason: str) -> tuple[str, str]:
    for name in _FALLBACK_CHAIN:
        if name in ENGINES and _capability_gap(ENGINES[name], d) is None:
            return name, reason
    return "lax", reason


def resolve_engine(requested: str, pass_name: str, d: ConvDims,
                   transposed: bool = False) -> tuple[str, str]:
    """One pass's selection: ``(engine actually used, reason)``.

    ``"auto"`` is the shape-dependent strategy: stride-1 undilated layers
    have no zero-space (the phase decomposition degenerates to the native
    dense conv, which is optimal), strided or dilated layers go to the
    Pallas tap-GEMM -- per-axis tap tables serve asymmetric strides, and a
    dilated kernel's zero taps are skipped rather than materialized --
    whenever the tile plan fits, and everything else falls back down
    ``bp_phase -> lax`` with the reason recorded.  Explicit requests that
    the engine cannot serve resolve the same way -- recorded, not silent.

    ``transposed=True`` resolves the pass of a TRANSPOSED conv over the
    mirror dims ``d`` (see :func:`transpose_dims`): the tile planner
    consulted is the role-swapped one (the transposed forward runs the
    mirror input-grad phase plan), and ``"auto"`` keeps plannable
    transposed specs on ``pallas`` -- the stride IS the zero-insertion.
    """
    if requested == AUTO:
        if d.s_h == 1 and d.s_w == 1 and not d.has_dilation:
            if _capability_gap(ENGINES["bp_phase"], d) is None:
                return "bp_phase", ("auto: stride 1 has no zero-space; "
                                    "phase decomposition degenerates to the "
                                    "native dense conv")
            return _first_capable(
                d, "auto: stride 1, geometry outside implicit constraints")
        gap = _capability_gap(ENGINES["pallas"], d)
        if gap is None and _pallas_fits(pass_name, d, transposed):
            if transposed:
                return "pallas", ("auto: transposed conv is the tap-GEMM "
                                  "phase plan; zero insertion skipped at "
                                  "plan time and the tile plan fits the "
                                  "VMEM budget")
            if d.has_dilation:
                return "pallas", ("auto: tap table skips the dilation zero "
                                  "taps and the tile plan fits the VMEM "
                                  "budget")
            return "pallas", "auto: tap-GEMM tile plan fits the VMEM budget"
        return _first_capable(
            d, f"auto: pallas unavailable "
               f"({gap or 'tile plan exceeds the VMEM budget'})")
    e = _engine(requested)
    gap = _capability_gap(e, d)
    if gap is not None:
        return _first_capable(d, f"{requested} requested but {gap}")
    if requested == "pallas" and not _pallas_fits(pass_name, d, transposed):
        return _first_capable(
            d, "pallas requested but the tile plan exceeds the VMEM budget")
    return requested, "requested"


# ---------------------------------------------------------------------------
# Runtime graceful degradation: execute-with-fallback, quarantine, probes
# ---------------------------------------------------------------------------

#: structured log of runtime engine failures (bounded like the decisions).
RUNTIME_FAILURES: list[dict] = []

#: a quarantined (pass, engine, geometry) is skipped for this many
#: dispatches, then probed for recovery (each dispatch is one trace -- one
#: step when the caller is eager, one retrace boundary under jit).
QUARANTINE_PROBE_AFTER = 3

#: (pass_key, engine, d) -> dispatches skipped since quarantine began.
_QUARANTINE: dict[tuple, int] = {}


def runtime_failures() -> list[dict]:
    """Every runtime engine failure absorbed by the degradation layer:
    pass, engine, exception class, the survivor that served the pass, and
    the geometry.  Reset by :func:`reset_dispatch_events`."""
    return list(RUNTIME_FAILURES)


def quarantined_engines() -> list[dict]:
    """The currently quarantined (pass, engine, geometry) entries and how
    many dispatches each has been skipped for."""
    return [{"pass": k[0], "engine": k[1], "dims": k[2], "skips": v}
            for k, v in sorted(_QUARANTINE.items(),
                               key=lambda kv: (kv[0][0], kv[0][1]))]


def clear_quarantine() -> None:
    _QUARANTINE.clear()


def _record_event(key: str) -> None:
    DISPATCH_EVENTS[key] = DISPATCH_EVENTS.get(key, 0) + 1
    obs_events.emit("dispatch", key)


def _dims_key(d: ConvDims) -> tuple:
    return (d.B, d.C, d.H_i, d.W_i, d.N, d.K_h, d.K_w, d.s_h, d.s_w)


def _runtime_chain(name: str, d: ConvDims) -> list[str]:
    """``name`` followed by the capability-ordered engines below it --
    the same ``bp_phase -> lax`` ladder plan-time fallback walks, with
    ``lax`` always terminal."""
    chain = [name]
    for cand in _FALLBACK_CHAIN:
        if cand != name and cand in ENGINES and \
                _capability_gap(ENGINES[cand], d) is None:
            chain.append(cand)
    if "lax" not in chain:
        chain.append("lax")
    return chain


def _poison_plan_entry(pass_name: str, transposed: bool, d: ConvDims) -> None:
    """Poison-mark the plan-cache entry that fed a crashing pallas launch
    (best effort -- poisoning must never mask the degradation itself)."""
    if config.autotune == "off":
        return
    role = _TRANSPOSE_ROLE[pass_name] if transposed else pass_name
    try:
        from repro.kernels import autotune
        autotune.poison_plan(role, d)
    except Exception:
        pass


def _execute(pass_name: str, requested: str, d: ConvDims, transposed: bool,
             run: Callable):
    """Resolve one conv pass and execute it, degrading at run time only
    while a fault is armed.

    ``run(engine)`` performs the pass.  With no fault armed
    (``config.fault_spec`` unset) an exception from the engine propagates:
    a kernel that fails to trace, lower or run is a bug to surface, and
    serving the pass from another engine would hide which engine ran.
    With a fault armed, an exception re-dispatches down the
    capability-ordered fallback chain: the failure
    is recorded (``dispatch_events`` gains ``"pass:failed->survivor"``,
    :func:`runtime_failures` keeps the exception class), the failing
    engine is QUARANTINED for this (pass, geometry) -- subsequent
    dispatches skip it for :data:`QUARANTINE_PROBE_AFTER` rounds, then
    probe it once; a successful probe lifts the quarantine
    (``"pass:engine:recovered"``), a failed one re-arms it -- and a
    crashing pallas launch poison-marks its plan-cache entry so
    ``autotune="cached"`` cannot re-crash on restart.  ``lax`` is the
    terminal anchor: it is never quarantined, and when every engine in
    the chain fails the FIRST exception propagates (nothing to degrade
    to).  The no-failure path records exactly what it always did: one
    dispatch event, one policy decision.
    """
    name, reason = resolve_engine(requested, pass_name, d, transposed)
    # Transposed-conv passes count under their own keys ("forward_T:pallas")
    # so a decoder's dispatch is distinguishable from its encoder's.
    pkey = f"{pass_name}{'_T' if transposed else ''}"
    first_exc = None
    failures: list[dict] = []
    for cand in _runtime_chain(name, d):
        qkey = (pkey, cand, _dims_key(d))
        probing = False
        if qkey in _QUARANTINE and cand != "lax":
            _QUARANTINE[qkey] += 1
            if _QUARANTINE[qkey] <= QUARANTINE_PROBE_AFTER:
                _record_event(f"{pkey}:{cand}:quarantined")
                continue
            probing = True
            _record_event(f"{pkey}:{cand}:probe")
        try:
            with obs_trace.conv_pass(pkey, cand, d):
                out = run(ENGINES[cand])
        except Exception as e:
            if not config.fault_spec:
                raise
            if first_exc is None:
                first_exc = e
            if cand != "lax":
                _QUARANTINE[qkey] = 0
            fail = {"pass": pkey, "engine": cand,
                    "exception": type(e).__name__, "error": str(e)[:200],
                    "survivor": None, "probe": probing,
                    "dims": _dims_key(d)}
            failures.append(fail)
            if len(RUNTIME_FAILURES) < _MAX_DECISIONS:
                RUNTIME_FAILURES.append(fail)
            if cand == "pallas":
                _poison_plan_entry(pass_name, transposed, d)
            continue
        if probing:
            del _QUARANTINE[qkey]
            _record_event(f"{pkey}:{cand}:recovered")
        for fail in failures:
            fail["survivor"] = cand
            _record_event(f"{pkey}:{fail['engine']}->{cand}")
            reason = (f"runtime degradation: {fail['engine']} raised "
                      f"{fail['exception']}; quarantined, {cand} survives")
        _record_event(f"{pkey}:{cand}")
        if len(POLICY_DECISIONS) < _MAX_DECISIONS:
            POLICY_DECISIONS.append({
                "pass": pass_name, "requested": requested, "engine": cand,
                "reason": reason, "transpose": transposed,
                "dims": _dims_key(d)})
        return out
    if first_exc is not None:
        raise first_exc
    raise RuntimeError(
        f"every engine for {pkey} is quarantined for dims {_dims_key(d)}; "
        f"chain {_runtime_chain(name, d)}")


def _validate_policy(policy: EnginePolicy) -> EnginePolicy:
    for _, engine in policy.slots():
        if engine != AUTO:
            _engine(engine)           # raises on unknown names
    return policy


# ---------------------------------------------------------------------------
# Grouped dispatch: vmap the per-group engine over the group dim
# ---------------------------------------------------------------------------

def _split_groups(x, w, groups: int):
    """x (B,C,H,W), w (N,C/g,Kh,Kw) -> xg (g,B,C/g,H,W), wg (g,N/g,...)."""
    b, c, h, wd = x.shape
    n = w.shape[0]
    xg = x.reshape(b, groups, c // groups, h, wd).transpose(1, 0, 2, 3, 4)
    wg = w.reshape(groups, n // groups, *w.shape[1:])
    return xg, wg


def _merge_groups(yg):
    """(g, B, N/g, H, W) -> (B, g*N/g, H, W)."""
    g, b, ng, h, w = yg.shape
    return yg.transpose(1, 0, 2, 3, 4).reshape(b, g * ng, h, w)


def _forward(x, w, d: ConvDims, eng: Engine, groups: int):
    if groups == 1:
        return eng.forward(x, w, d)
    if eng.name == "lax":
        return jax.lax.conv_general_dilated(
            x, w, (d.s_h, d.s_w),
            [(d.P_h, d.p_h_hi), (d.P_w, d.p_w_hi)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=groups)
    xg, wg = _split_groups(x, w, groups)
    yg = jax.vmap(lambda xx, ww: eng.forward(xx, ww, d))(xg, wg)
    return _merge_groups(yg)


def _input_grad(dy, w, d: ConvDims, eng: Engine, groups: int):
    if groups == 1:
        return eng.input_grad(dy, w, d)
    b = dy.shape[0]
    dyg = dy.reshape(b, groups, d.N, d.H_o, d.W_o).transpose(1, 0, 2, 3, 4)
    wg = w.reshape(groups, d.N, *w.shape[1:])
    dxg = jax.vmap(lambda dd, ww: eng.input_grad(dd, ww, d))(dyg, wg)
    return _merge_groups(dxg)


def _weight_grad(x, dy, d: ConvDims, eng: Engine, groups: int):
    if groups == 1:
        return eng.weight_grad(x, dy, d)
    b, c = x.shape[0], x.shape[1]
    xg = x.reshape(b, groups, c // groups, d.H_i, d.W_i).transpose(
        1, 0, 2, 3, 4)
    dyg = dy.reshape(b, groups, d.N, d.H_o, d.W_o).transpose(1, 0, 2, 3, 4)
    dwg = jax.vmap(lambda xx, dd: eng.weight_grad(xx, dd, d))(
        xg, dyg)                                   # (g, N/g, C/g, kh, kw)
    # Kernel extent from the engine's output: compact (k_taps) for
    # native-dilation engines, effective (K_eff) otherwise.
    return dwg.reshape(groups * d.N, *dwg.shape[2:])


# ---------------------------------------------------------------------------
# Policy override context and the default policy
# ---------------------------------------------------------------------------

#: the repo-wide default: shape-dependent per-pass selection.
DEFAULT_POLICY = EnginePolicy()

_POLICY_OVERRIDE: list[EnginePolicy] = []


@contextlib.contextmanager
def conv_policy(policy):
    """Scoped policy override for EVERY conv2d/conv1d in the dynamic extent.

    Beats per-call and per-config policies, so an experiment can swap
    engines without rebuilding the model::

        with conv_policy("fwd=lax,dgrad=pallas,wgrad=bp_phase"):
            grads = jax.grad(loss)(params)      # traced under the override

    Applies at TRACE time (the policy is a static jit argument): wrap the
    call that traces, not an already-compiled executable.
    """
    p = EnginePolicy.coerce(policy)
    _validate_policy(p)
    _POLICY_OVERRIDE.append(p)
    try:
        yield p
    finally:
        _POLICY_OVERRIDE.pop()


def effective_policy(explicit=None) -> EnginePolicy:
    """Override stack > per-call/explicit policy > DEFAULT_POLICY (auto)."""
    if _POLICY_OVERRIDE:
        return _POLICY_OVERRIDE[-1]
    if explicit is not None:
        return EnginePolicy.coerce(explicit)
    return DEFAULT_POLICY


# ---------------------------------------------------------------------------
# custom_vjp conv on the structured surface
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv2d(x: jax.Array, w: jax.Array, spec: ConvSpec,
            policy: EnginePolicy) -> jax.Array:
    d = spec_dims(x.shape, w.shape, spec)
    return _execute(
        "forward", policy.forward, d, False,
        lambda eng: _forward(x, _weight_for(eng, w, spec), d, eng,
                             spec.groups))


def _conv2d_fwd(x, w, spec, policy):
    d = spec_dims(x.shape, w.shape, spec)
    y = _execute(
        "forward", policy.forward, d, False,
        lambda eng: _forward(x, _weight_for(eng, w, spec), d, eng,
                             spec.groups))
    return y, (x, w)


def _run_wgrad(x, dy, d, eng, spec):
    """One engine's complete weight-grad pass, un-dilation included --
    the degradation unit must cover the whole engine-dependent pipeline,
    since the survivor may differ in ``native_dilation``."""
    dw = _weight_grad(x, dy, d, eng, spec.groups)
    if not eng.native_dilation:
        dw = _undilate_dweight(dw, spec)
    return dw


def _conv2d_bwd(spec, policy, res, dy):
    x, w = res
    d = spec_dims(x.shape, w.shape, spec)
    dx = _execute(
        "input_grad", policy.input_grad, d, False,
        lambda eng: _input_grad(dy, _weight_for(eng, w, spec), d, eng,
                                spec.groups))
    dw = _execute(
        "weight_grad", policy.weight_grad, d, False,
        lambda eng: _run_wgrad(x, dy, d, eng, spec))
    return dx.astype(x.dtype), dw.astype(w.dtype)


_conv2d.defvjp(_conv2d_fwd, _conv2d_bwd)


# ---------------------------------------------------------------------------
# Transposed convolution: tap-native lhs dilation through the same engines
# ---------------------------------------------------------------------------

def conv2d_transpose_materialized(x: jax.Array, w: jax.Array,
                                  spec: ConvTransposeSpec,
                                  engine: str = "lax") -> jax.Array:
    """The zero-insertion MATERIALIZATION of a transposed conv: physically
    build the lhs-dilated input (``s - 1`` zeros between pixels, virtual
    pad ``K_eff - 1 - p`` per side, ``output_padding`` extra rows/cols on
    the high side), rotate/swap the (zero-dilated) kernel, and run an
    ordinary stride-1 dense conv over the zero-spaced tensor.

    This is what engines WITHOUT the ``native_transpose`` capability get
    at dispatch, and it is the executable oracle the tap-native path is
    tested against -- it pays exactly the reorganization + zero-FLOPs the
    paper eliminates.  Differentiable (pure jax ops), so ``jax.grad`` of
    it anchors the transposed VJP too.
    """
    eng = _engine(engine)
    b, cin, h, wd = x.shape
    g = spec.groups
    cog = w.shape[1]
    w_eff = _dilate_weight(w, spec)          # (C_in, C_out/g, Keff, Keff)
    keff_h, keff_w = w_eff.shape[-2:]
    (ph_lo, ph_hi), (pw_lo, pw_hi) = spec.padding
    # lax.pad applies the interior (zero-insertion) dilation first, then
    # the edge pads -- negative edge pads crop, so p > K_eff - 1 works too.
    x_zi = jax.lax.pad(
        x, jnp.zeros((), x.dtype),
        [(0, 0, 0), (0, 0, 0),
         (keff_h - 1 - ph_lo, keff_h - 1 - ph_hi + spec.op_h, spec.s_h - 1),
         (keff_w - 1 - pw_lo, keff_w - 1 - pw_hi + spec.op_w, spec.s_w - 1)])
    # Mirror OIHW weight of the stride-1 dense conv: rot180 + in/out swap.
    wt = rot180(w_eff).reshape(g, cin // g, cog, keff_h, keff_w)
    wt = wt.transpose(0, 2, 1, 3, 4).reshape(g * cog, cin // g,
                                             keff_h, keff_w)
    d1 = ConvDims(B=b, C=cin // g, H_i=x_zi.shape[2], W_i=x_zi.shape[3],
                  N=cog, K_h=keff_h, K_w=keff_w, S=1)
    return _forward(x_zi, wt, d1, eng, g)


def _t_forward(x, w, d: ConvDims, eng: Engine, spec: ConvTransposeSpec):
    """Transposed forward under one engine: role-swap onto the mirror
    input-grad machinery when the engine is transpose-native (zero space
    never built), else the physical zero-insertion lowering."""
    if not eng.native_transpose:
        return conv2d_transpose_materialized(x, w, spec, eng.name)
    return _input_grad(x, _weight_for(eng, w, spec), d, eng, spec.groups)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv2d_transpose(x: jax.Array, w: jax.Array, spec: ConvTransposeSpec,
                      policy: EnginePolicy) -> jax.Array:
    d = transpose_dims(x.shape, w.shape, spec)
    return _execute("forward", policy.forward, d, True,
                    lambda eng: _t_forward(x, w, d, eng, spec))


def _conv2d_transpose_fwd(x, w, spec, policy):
    d = transpose_dims(x.shape, w.shape, spec)
    y = _execute("forward", policy.forward, d, True,
                 lambda eng: _t_forward(x, w, d, eng, spec))
    return y, (x, w)


def _conv2d_transpose_bwd(spec, policy, res, dy):
    x, w = res
    d = transpose_dims(x.shape, w.shape, spec)
    # dX of a transposed conv is the mirror STRIDED regular conv of dy;
    # dW is the mirror weight grad with the input/output roles swapped.
    dx = _execute(
        "input_grad", policy.input_grad, d, True,
        lambda eng: _forward(dy, _weight_for(eng, w, spec), d, eng,
                             spec.groups))
    dw = _execute(
        "weight_grad", policy.weight_grad, d, True,
        lambda eng: _run_wgrad(dy, x, d, eng, spec))
    return dx.astype(x.dtype), dw.astype(w.dtype)


_conv2d_transpose.defvjp(_conv2d_transpose_fwd, _conv2d_transpose_bwd)


def _canon_transpose_call(args: tuple, kw: dict) \
        -> tuple[ConvTransposeSpec, EnginePolicy | None]:
    """conv2d_transpose(x, w, spec | policy, policy=..., <geometry kwargs>)
    -- the structured surface only (this API postdates ``mode=``)."""
    spec = kw.pop("spec", None)
    policy = kw.pop("policy", None)
    geom = {k: kw.pop(k) for k in ("stride", "padding", "output_padding",
                                   "dilation", "groups", "layout")
            if k in kw}
    if kw:
        raise TypeError(
            f"conv2d_transpose got unexpected kwargs {sorted(kw)}")
    args = list(args)
    if args and isinstance(args[0], ConvTransposeSpec):
        if spec is not None:
            raise TypeError(
                "ConvTransposeSpec given both positionally and as spec=")
        spec = args.pop(0)
    if args:
        if policy is not None:
            raise TypeError("policy given twice")
        if not isinstance(args[0], (str, EnginePolicy)):
            raise TypeError(
                "expected a policy (str | EnginePolicy) after the spec, "
                f"got {args[0]!r}")
        policy = args.pop(0)
    if args:
        raise TypeError("too many positional arguments")
    if spec is None:
        spec = ConvTransposeSpec.make(**geom)
    elif geom:
        raise TypeError(
            f"geometry given both in the ConvTransposeSpec and as kwargs "
            f"{sorted(geom)}; put it all in the spec")
    return spec, policy


def conv2d_transpose(x: jax.Array, w: jax.Array, *args, **kwargs) \
        -> jax.Array:
    """NCHW x (C_in, C_out/g, K_h, K_w) -> NCHW TRANSPOSED convolution.

    ``conv2d_transpose(x, w, spec: ConvTransposeSpec, policy=...)`` (or the
    geometry kwargs ``stride= padding= output_padding= dilation= groups=
    layout=``, which build the spec).  The stride is the input (lhs)
    dilation; engines with the ``native_transpose`` capability never build
    the zero-inserted input -- the forward IS the paper's transposed-mode
    tap-GEMM over the mirror regular conv (:func:`transpose_dims`), one
    fused launch across all ``s_h*s_w`` output phases on ``pallas``.  The
    VJP lowers to the already-tested regular-conv engines: dX is the
    mirror strided conv, dW the mirror weight grad with roles swapped.

    ``policy`` selects the engine per pass exactly as for :func:`conv2d`
    (``EnginePolicy`` / policy string / engine name / None for auto), and
    a surrounding :func:`conv_policy` context overrides it.
    ``spec.layout == "NHWC"`` transposes activations at the boundary.
    """
    spec, policy = _canon_transpose_call(args, kwargs)
    policy = _validate_policy(effective_policy(policy))
    if spec.layout == "NHWC":
        y = _mesh_dispatch(_conv2d_transpose, jnp.transpose(x, (0, 3, 1, 2)),
                           w, spec.with_layout("NCHW"), policy)
        return jnp.transpose(y, (0, 2, 3, 1))
    return _mesh_dispatch(_conv2d_transpose, x, w, spec, policy)


# ---------------------------------------------------------------------------
# Public entry point: structured surface + backward-compat shim
# ---------------------------------------------------------------------------

_LEGACY_POSITIONAL = ("stride", "padding", "mode", "groups")


def _deprecated_mode(mode) -> EnginePolicy:
    warnings.warn(
        "conv2d(..., mode=...) is deprecated; pass policy='<engine>' "
        "(uniform) or an EnginePolicy (per-pass) instead",
        DeprecationWarning, stacklevel=4)
    return EnginePolicy.uniform(mode)


def _canon_call(args: tuple, kw: dict) -> tuple[ConvSpec, EnginePolicy | None]:
    """Interpret both call surfaces:

    new:    conv2d(x, w, spec: ConvSpec, policy=...)  (or geometry kwargs)
    legacy: conv2d(x, w, stride, padding, mode, groups)  (mode deprecated)
    """
    spec = kw.pop("spec", None)
    policy = kw.pop("policy", None)
    mode = kw.pop("mode", None)
    geom = {k: kw.pop(k) for k in ("stride", "padding", "dilation", "groups",
                                   "layout") if k in kw}
    if kw:
        raise TypeError(f"conv2d got unexpected kwargs {sorted(kw)}")
    args = list(args)
    if args and isinstance(args[0], ConvSpec):
        if spec is not None:
            raise TypeError("ConvSpec given both positionally and as spec=")
        spec = args.pop(0)
        if args:
            if policy is not None:
                raise TypeError("policy given twice")
            policy = args.pop(0)
        if args:
            raise TypeError("too many positional arguments after ConvSpec")
    elif args and isinstance(args[0], (str, EnginePolicy)):
        # conv2d(x, w, "pallas") / conv2d(x, w, EnginePolicy(...)): a
        # leading policy with default/kwarg geometry (legacy stride is
        # numeric, so this is unambiguous).
        if policy is not None:
            raise TypeError("policy given twice")
        policy = args.pop(0)
        if args:
            raise TypeError("too many positional arguments after policy")
    elif args:
        # Legacy positional (stride, padding, mode, groups).
        if len(args) > len(_LEGACY_POSITIONAL):
            raise TypeError("too many positional arguments")
        for name, val in zip(_LEGACY_POSITIONAL, args):
            if name == "mode":
                if mode is not None:
                    raise TypeError("mode given twice")
                mode = val
            else:
                if name in geom:
                    raise TypeError(f"{name} given twice")
                geom[name] = val
    if mode is not None:
        if policy is not None:
            raise TypeError("pass either policy= or the deprecated mode=, "
                            "not both")
        policy = _deprecated_mode(mode)
    if spec is None:
        spec = ConvSpec.make(**geom)
    elif geom:
        raise TypeError(
            f"geometry given both in the ConvSpec and as kwargs "
            f"{sorted(geom)}; put it all in the spec")
    return spec, policy


def conv2d(x: jax.Array, w: jax.Array, *args, **kwargs) -> jax.Array:
    """NCHW x OIHW -> NCHW convolution with per-pass backprop engines.

    New surface: ``conv2d(x, w, spec: ConvSpec, policy=EnginePolicy | str)``
    (or the non-deprecated geometry kwargs ``stride= padding= dilation=
    groups= layout=``, which build the spec).  ``policy`` is an
    :class:`EnginePolicy`, a policy string (``"fwd=pallas,dgrad=auto,
    wgrad=bp_phase"``), a bare engine name (uniform), or None for the
    ``auto`` default; a surrounding :func:`conv_policy` context overrides
    it.  Legacy surface ``conv2d(x, w, stride, padding, mode, groups)``
    still works; ``mode=`` emits a ``DeprecationWarning``.

    ``spec.layout == "NHWC"`` transposes activations at the boundary
    (weights stay OIHW); everything inside runs NCHW.
    """
    spec, policy = _canon_call(args, kwargs)
    policy = _validate_policy(effective_policy(policy))
    if spec.layout == "NHWC":
        y = _mesh_dispatch(_conv2d, jnp.transpose(x, (0, 3, 1, 2)), w,
                           spec.with_layout("NCHW"), policy)
        return jnp.transpose(y, (0, 2, 3, 1))
    return _mesh_dispatch(_conv2d, x, w, spec, policy)


# ---------------------------------------------------------------------------
# 1-D and depthwise wrappers (Mamba2 / RecurrentGemma temporal convs)
# ---------------------------------------------------------------------------

def _merge_policy(policy, mode):
    if mode is not None:
        if policy is not None:
            raise TypeError("pass either policy= or the deprecated mode=, "
                            "not both")
        return _deprecated_mode(mode)
    return policy


def conv1d(x: jax.Array, w: jax.Array, stride: int = 1, padding=0,
           policy=None, groups: int = 1, dilation: int = 1, *,
           mode=None) -> jax.Array:
    """(B, C, L) x (N, C/g, K) -> (B, N, L_o) through the 2-D engines.

    padding: int (symmetric) or (lo, hi) along the temporal dim.  The
    stride/dilation are applied symmetrically on the degenerate (H=1) axis
    too (a no-op there: one row has no stride phases or dilation gaps).
    """
    policy = _merge_policy(policy, mode)
    if isinstance(padding, int):
        padding = (padding, padding)
    spec = ConvSpec.make(stride=stride, padding=((0, 0), tuple(padding)),
                         dilation=dilation, groups=groups)
    x4 = x[:, :, None, :]
    w4 = w[:, :, None, :]
    y = conv2d(x4, w4, spec, policy)
    return y[:, :, 0, :]


def conv1d_causal(x: jax.Array, w: jax.Array, policy=None,
                  groups: int = 1, *, mode=None) -> jax.Array:
    """Causal (left-pad K-1) stride-1 conv1d: (B, C, L) -> (B, N, L)."""
    k = w.shape[-1]
    return conv1d(x, w, 1, (k - 1, 0), _merge_policy(policy, mode), groups)


def depthwise_causal_conv1d(x: jax.Array, w: jax.Array,
                            policy=None, *, mode=None) -> jax.Array:
    """Causal depthwise conv used by Mamba2: x (B, L, C), w (K, C).

    Lowered as a grouped (groups == C) causal conv1d: the causal shift is an
    asymmetric left-only pad and each channel convolves with its own K-tap
    filter, so the BP-im2col datapath is exercised for the depthwise case
    too.  When every pass of the effective policy resolves inside
    {lax, bp_phase, auto} the layer short-circuits to ONE fused
    ``conv_general_dilated`` with ``feature_group_count``: a stride-1
    backward has no zero-insertion, so the phase decomposition (and the
    auto policy, whose stride-1 rule picks it) degenerates to exactly the
    native conv -- same math, one XLA op on the production hot path.
    """
    b, l, c = x.shape
    k = w.shape[0]
    p = effective_policy(_merge_policy(policy, mode))
    if {p.forward, p.input_grad, p.weight_grad} <= {"lax", "bp_phase", AUTO}:
        xt = x.transpose(0, 2, 1)[:, :, None, :]            # (B, C, 1, L)
        wt = w.T[:, None, None, :]                          # (C, 1, 1, K)
        y = jax.lax.conv_general_dilated(
            xt, wt, (1, 1), [(0, 0), (k - 1, 0)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=c)
        return y[:, :, 0, :].transpose(0, 2, 1)
    xt = x.transpose(0, 2, 1)                           # (B, C, L)
    wt = w.T[:, None, :]                                # (C, 1, K)
    y = conv1d_causal(xt, wt, p, groups=c)              # (B, C, L)
    return y.transpose(0, 2, 1)


def output_shape(d: ConvDims) -> tuple[int, int, int, int]:
    return (d.B, d.N, d.H_o, d.W_o)


# ---------------------------------------------------------------------------
# Static introspection: what WOULD dispatch, and why
# ---------------------------------------------------------------------------

def resolve_policy(d: ConvDims, policy=None,
                   transposed: bool = False) -> dict[str, dict[str, str]]:
    """Pure per-pass resolution for one per-group geometry: no arrays, no
    event recording.  ``{pass: {requested, engine, reason}}``.
    ``transposed=True`` resolves over the mirror dims of a transposed conv
    (the planners consulted are role-swapped per pass)."""
    p = _validate_policy(EnginePolicy.coerce(policy) if policy is not None
                         else DEFAULT_POLICY)
    out = {}
    for pass_name, requested in p.slots():
        engine, reason = resolve_engine(requested, pass_name, d, transposed)
        out[pass_name] = {"requested": requested, "engine": engine,
                          "reason": reason}
    return out


def policy_report(x_shape, w_shape, spec=None, policy=None) -> dict:
    """Static dispatch summary for one conv layer under one policy: the
    per-pass engines the resolver would pick (with reasons) plus the Pallas
    tile plans (the planners build per-axis tap tables, so asymmetric
    strides and dilations plan like any other geometry).

    ``spec`` may be a :class:`ConvTransposeSpec` (then ``w_shape`` is the
    transposed ``(C_in, C_out/g, K_h, K_w)`` convention): the report plans
    the MIRROR regular conv the transposed layer role-swaps onto, flags
    ``"transpose": True``, and adds the zero-insertion tap accounting
    (``taps.real`` vs ``taps.zero_inserted``)."""
    from repro.kernels import ops
    if isinstance(spec, ConvTransposeSpec):
        d = transpose_dims(x_shape, w_shape, spec)
        report = {"passes": resolve_policy(d, policy, transposed=True),
                  "spec": str(spec), "transpose": True,
                  "plan": ops.plan_report(d),
                  "taps": transpose_tap_counts(d)}
    else:
        spec = ConvSpec.coerce(spec)
        d = spec_dims(x_shape, w_shape, spec)
        report = {"passes": resolve_policy(d, policy), "spec": str(spec),
                  "transpose": False, "plan": ops.plan_report(d)}
    report["pallas_path"] = all(
        v["engine"] == "pallas" for v in report["passes"].values())
    return report


def conv_plan_report(x_shape, w_shape, stride=1, padding=0,
                     groups: int = 1,
                     budget: int | None = None,
                     dilation=1) -> dict[str, object]:
    """Static Pallas dispatch summary for one conv layer: per-op tile plans
    (spatial/channel tiles, split counts, VMEM footprint) and whether the
    whole layer stays on the Pallas path.  Convenience wrapper over
    ``repro.kernels.ops.plan_report`` taking array shapes instead of a
    ``ConvDims``; pure planner introspection, no arrays are touched."""
    from repro.kernels import ops
    d = make_dims(x_shape, w_shape, stride, padding, groups, dilation)
    return ops.plan_report(d, budget)
