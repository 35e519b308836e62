"""Span tracer emitting Chrome/Perfetto ``trace_event`` JSON.

Spans wrap the hot structural moments of a run -- conv dispatch per
pass/engine (annotated with the ConvDims geometry, ``taps{real,
materialized}``, ``skip_ratio`` and modeled ``bytes_moved``), mesh halo
``ppermute`` exchanges, checkpoint writes and serve prefill/insert/decode
steps -- and export as a single
``{"traceEvents": [...]}`` file that chrome://tracing and ui.perfetto.dev
load directly.  Each span also passes through
``jax.profiler.TraceAnnotation`` so the same names line up inside XLA
device profiles.

Disarmed idiom (``ft/inject.py`` contract): the buffer global is ``None``
when tracing is off and :func:`span` returns one shared pre-allocated
null context manager -- no per-call allocation, no timestamping.

Events use the Duration form: paired ``"ph": "B"`` / ``"ph": "E"`` records
per (pid, tid) with microsecond ``ts`` from ``perf_counter``, so nesting
is positional and ``scripts/validate_trace.py`` can check balance.
Because conv dispatch happens at jax TRACE time, conv spans measure
trace/compile-side dispatch, not steady-state device time -- which is
exactly where engine resolution and plan lookups live.

The device side of a conv pass is its named scope instead
(:func:`pass_scope`, entered with the span by :func:`conv_pass`): an
always-on ``jax.named_scope`` that only writes the HLO ``op_name``
metadata at trace time, so every device op of the pass -- and the
layout glue inside it, under :data:`GLUE_SCOPE` -- carries the pass in a
profiler trace.  It costs nothing on the device and is not gated on
telemetry.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time

MAX_TRACE_EVENTS = 200_000

_BUF: list[dict] | None = None    # None == tracing off (disarmed idiom)
_DROPPED = 0
_PID = os.getpid()

_JAX_ANNOTATION = None            # resolved lazily on first span


class _NullSpan:
    """The shared do-nothing context manager returned when tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


def _annotation_cls():
    """``jax.profiler.TraceAnnotation`` resolved lazily, so importing
    repro.obs never forces jax in (dryrun sets XLA_FLAGS pre-import)."""
    global _JAX_ANNOTATION
    if _JAX_ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
            _JAX_ANNOTATION = TraceAnnotation
        except Exception:               # pragma: no cover - jax always here
            _JAX_ANNOTATION = False
    return _JAX_ANNOTATION


class _Span:
    __slots__ = ("name", "args", "_ann")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args

    def __enter__(self):
        global _DROPPED
        buf = _BUF
        if buf is not None:
            if len(buf) < MAX_TRACE_EVENTS:
                buf.append({"ph": "B", "name": self.name, "pid": _PID,
                            "tid": threading.get_ident(),
                            "ts": time.perf_counter() * 1e6,
                            "args": self.args})
            else:
                _DROPPED += 1
        ann = _annotation_cls()
        self._ann = ann(self.name) if ann else None
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        global _DROPPED
        if self._ann is not None:
            self._ann.__exit__(*exc)
        buf = _BUF
        if buf is not None:
            if len(buf) < MAX_TRACE_EVENTS:
                buf.append({"ph": "E", "name": self.name, "pid": _PID,
                            "tid": threading.get_ident(),
                            "ts": time.perf_counter() * 1e6})
            else:
                _DROPPED += 1
        return False


def active() -> bool:
    """True when spans are being recorded."""
    return _BUF is not None


def span(name: str, **args):
    """A context manager recording one B/E span pair.  When tracing is off
    this returns the shared null singleton (a single ``is None`` check)."""
    if _BUF is None:
        return _NULL
    return _Span(name, args)


def conv_annotations(d, transposed: bool = False) -> dict:
    """The paper-facing annotation dict for one conv dispatch: geometry,
    real-vs-materialized taps, zero-space ``skip_ratio`` and the modeled
    compact-layout traffic ``bytes_moved`` (f32 activations + compact
    weights + outputs -- what roofline.py calls the implicit-im2col
    traffic, NOT a measured number)."""
    real = d.k_taps_h * d.k_taps_w
    if transposed:
        # Mirror-conv identity: the materialized alternative zero-inserts
        # stride phases too, so the denominator is s*K per axis.
        materialized = (d.s_h * d.K_h) * (d.s_w * d.K_w)
    else:
        materialized = d.K_h * d.K_w
    itemsize = 4
    elems = (d.B * d.C * d.H_i * d.W_i          # source activations
             + d.N * d.C * real                 # compact weight taps
             + d.B * d.N * d.H_o * d.W_o)       # outputs
    return {
        "dims": {"B": d.B, "C": d.C, "H_i": d.H_i, "W_i": d.W_i,
                 "N": d.N, "K_h": d.K_h, "K_w": d.K_w,
                 "s_h": d.s_h, "s_w": d.s_w, "D_h": d.D_h, "D_w": d.D_w},
        "taps": {"real": real, "materialized": materialized},
        "skip_ratio": round(1.0 - real / materialized, 6),
        "bytes_moved": elems * itemsize,
    }


def dispatch_span(pkey: str, engine: str, d):
    """Span around one conv engine execution (``core/conv.py _execute``).
    ``pkey`` is the dispatch pass key (``forward``/``input_grad``/
    ``weight_grad`` with an ``_T`` suffix for transposed convs).  Conv
    dispatch runs while jax traces the program, so the span times the
    set-up's dispatch and tile planning, never the device."""
    if _BUF is None:
        return _NULL
    args = {"pass": pkey, "engine": engine}
    args.update(conv_annotations(d, transposed=pkey.endswith("_T")))
    return _Span(f"conv:{pkey}:{engine}", args)


#: the named scope of the layout rearrangements around a tap-GEMM kernel
#: call (padding, phase split/unsplit, NCHW<->NHWC, weight tap gathers).
GLUE_SCOPE = "glue"
#: the named scopes of a whole network's work outside its convs
#: (``models/resnet.py``): each batch norm with the residual add and ReLU
#: that follow it, the max pool, and the head (global pool, fc, loss).
NORM_SCOPE = "batch_norm"
POOL_SCOPE = "max_pool"
HEAD_SCOPE = "head"


def pass_scope(pkey: str) -> str:
    """The device-side named scope of one conv pass: ``conv_forward``,
    ``conv_input_grad``, ``conv_weight_grad``, ``_T`` suffixed for a
    transposed conv."""
    return f"conv_{pkey}"


@contextlib.contextmanager
def conv_pass(pkey: str, engine: str, d):
    """Both marks of one conv pass execution: the always-on named scope
    :func:`pass_scope` (HLO metadata) and :func:`dispatch_span` (the host
    span, recorded only while tracing is on)."""
    import jax
    with jax.named_scope(pass_scope(pkey)), dispatch_span(pkey, engine, d):
        yield


def dropped() -> int:
    return _DROPPED


def summary() -> dict:
    """Shape of the recorded trace (for ``obs.report()``)."""
    buf = _BUF if _BUF is not None else []
    names: dict[str, int] = {}
    for e in buf:
        if e["ph"] == "B":
            key = e["name"].split(":", 1)[0]
            names[key] = names.get(key, 0) + 1
    return {"active": _BUF is not None, "events": len(buf),
            "spans_by_prefix": names, "dropped": _DROPPED}


def export(path: str | None = None) -> str | None:
    """Write the Chrome/Perfetto ``trace_event`` JSON.  ``path`` defaults
    to ``config.trace_path``; returns the path written, or None when
    tracing is off / no path is configured."""
    if _BUF is None:
        return None
    if path is None:
        from repro.core.config import config
        path = config.trace_path
    if path is None:
        return None
    doc = {"traceEvents": list(_BUF),
           "displayTimeUnit": "ms",
           "otherData": {"producer": "repro.obs.trace",
                         "dropped_events": _DROPPED}}
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return path


def reset() -> None:
    """Clear the buffer; keeps the active/inactive state."""
    global _BUF, _DROPPED
    if _BUF is not None:
        _BUF = []
    _DROPPED = 0


def sync_from_config() -> None:
    """Tracing is active iff ``telemetry`` is on AND a ``trace_path`` is
    set (spans exist to be exported; the bus alone needs no buffer)."""
    global _BUF
    from repro.core.config import config
    if config.telemetry and config.trace_path:
        if _BUF is None:
            _BUF = []
    else:
        _BUF = None


sync_from_config()
