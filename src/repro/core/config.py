"""All global runtime configuration for this project: ``repro.config``.

One frozen-by-default singleton (the alpa ``global_env`` idiom) replaces the
env-var knobs that used to be read ad hoc across five modules
(``BPIM2COL_INTERPRET`` in ``kernels/ops.py``, ``REPRO_SSD_CHUNK`` in
``models/mamba2.py``, ``REPRO_BLOCKWISE_THRESHOLD`` in
``models/attention.py``, ``REPRO_SCAN_UNROLL`` / ``REPRO_REMAT`` in
``models/transformer.py`` and ``launch/dryrun.py``):

    from repro.core.config import config        # or: import repro; repro.config

    config.vmem_budget_bytes                    # read anywhere, any time
    config.update(ssd_chunk=64)                 # permanent, validated
    with config.override(vmem_budget_bytes=1 << 20):
        ...                                     # scoped, restored on exit

Fields initialize ONCE from the environment (so launcher scripts that export
``REPRO_*`` before python starts keep working unchanged), and direct
attribute assignment raises -- mutation goes through :meth:`GlobalConfig.
update` / :meth:`GlobalConfig.override`, which validate values and
invalidate the tile-plan caches when a plan-affecting field
(``vmem_budget_bytes``, ``interpret``) changes.  That kills the pre-config
footgun where mutating a module global (``ops.VMEM_BUDGET_BYTES``) relied
on the lru key catching the change.

Backward compatibility: mutating the environment AFTER import still works --
each attribute read re-checks the raw env string against the snapshot taken
at init, adopts the new value, and emits a ``DeprecationWarning`` -- but new
code should call ``config.update(...)``.  ``scripts/check_no_raw_mode.py``
lints raw ``os.environ.get("REPRO_*" / "BPIM2COL_*")`` reads out of every
module except this one.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
import warnings
from typing import Any, Callable


def _parse_bool(raw: str) -> bool:
    """unset/1/true -> True; 0/false/no/off -> False (BPIM2COL_INTERPRET's
    historical parsing, kept verbatim)."""
    return raw.strip().lower() not in ("0", "false", "no", "off")


def _parse_optional_str(raw: str) -> str | None:
    return raw or None


def _check_positive_int(name: str) -> Callable[[Any], int]:
    def check(v: Any) -> int:
        if not isinstance(v, int) or isinstance(v, bool) or v < 1:
            raise ValueError(f"{name} must be a positive int, got {v!r}")
        return v
    return check


def _check_bool(v: Any) -> bool:
    if not isinstance(v, bool):
        raise ValueError(f"expected a bool, got {v!r}")
    return v


def _check_optional_bool(v: Any) -> bool | None:
    if v is not None and not isinstance(v, bool):
        raise ValueError(f"expected a bool or None, got {v!r}")
    return v


def _backend_interpret() -> bool:
    """Interpret Pallas kernels unless jax's default backend is a TPU.
    Asked on the first read of ``interpret``, never while a module is
    imported: asking initializes jax's backends."""
    import jax
    return jax.default_backend() != "tpu"


def _check_optional_str(v: Any) -> str | None:
    if v is not None and not isinstance(v, str):
        raise ValueError(f"expected a str or None, got {v!r}")
    return v


def _check_int_any(v: Any) -> int:
    if not isinstance(v, int) or isinstance(v, bool):
        raise ValueError(f"expected an int, got {v!r}")
    return v


def _check_fault_spec(v: Any) -> str | None:
    """Validate the grammar BEFORE the value is stored, so a bad spec fails
    the update() cleanly (imported here, not at module top: config must
    stay importable before -- and without -- the ft stack)."""
    v = _check_optional_str(v)
    if v:
        from repro.ft.inject import parse_fault_spec
        parse_fault_spec(v)
    return v


@dataclasses.dataclass(frozen=True)
class _Field:
    env: str                       # the legacy env var this field absorbs
    default: Any
    parse: Callable[[str], Any]    # raw env string -> value
    check: Callable[[Any], Any]    # validate/normalize an update() value
    plan_affecting: bool = False   # True: changing it invalidates plan caches


#: field name -> spec.  The env vars are the DEPRECATED aliases; the field
#: is the source of truth after import.
FIELDS: dict[str, _Field] = {
    # Pallas kernels: interpret mode (CPU) vs Mosaic compile (TPU).  None
    # resolves from the backend on first read: False on a TPU, True
    # elsewhere (kernels/tap_gemm.py refuses to interpret on a TPU).
    "interpret": _Field("BPIM2COL_INTERPRET", None, _parse_bool,
                        _check_optional_bool, plan_affecting=True),
    # Tile-plan search budget: per-grid-step VMEM footprint ceiling.
    "vmem_budget_bytes": _Field("REPRO_VMEM_BUDGET_BYTES", 14 * 1024 * 1024,
                                int, _check_positive_int("vmem_budget_bytes"),
                                plan_affecting=True),
    # Mamba2 SSD chunk length (intra-chunk quadratic vs inter-chunk linear).
    "ssd_chunk": _Field("REPRO_SSD_CHUNK", 128, int,
                        _check_positive_int("ssd_chunk")),
    # KV length above which prefill attention switches to the blockwise
    # online-softmax scan.
    "blockwise_kv_threshold": _Field("REPRO_BLOCKWISE_THRESHOLD", 1024, int,
                                     _check_positive_int(
                                         "blockwise_kv_threshold")),
    # Layer-scan unroll factor (roofline dry-runs set 9999 so
    # cost_analysis() sees all layers).
    "scan_unroll": _Field("REPRO_SCAN_UNROLL", 1, int,
                          _check_positive_int("scan_unroll")),
    # Remat override: None defers to each ArchConfig.remat; "none"/"block"
    # force the policy globally.
    "remat": _Field("REPRO_REMAT", None, _parse_optional_str,
                    _check_optional_str),
    # Deterministic fault injection (repro.ft.inject): ';'-separated rules
    # '<site-glob>:<action>[@stepN][~pP]', e.g.
    # "ckpt.write:raise@step3;grad.values:nan@step5".  None/"" disarms.  The
    # spec grammar is validated by the injector at arm time, so a bad spec
    # fails the update() that sets it (when the injector is importable).
    "fault_spec": _Field("REPRO_FAULT_SPEC", None, _parse_optional_str,
                         _check_fault_spec),
    # Seed of the injector's probability stream (the '~pP' rules).
    "fault_seed": _Field("REPRO_FAULT_SEED", 0, int, _check_int_any),
    # Unified telemetry (repro.obs): the master switch for the structured
    # event bus, the span tracer and the metrics stream.  Off (the
    # default) is today's zero-overhead behavior -- every obs hook is a
    # single ``is None`` check, the ft.inject disarmed idiom.
    "telemetry": _Field("REPRO_TELEMETRY", False, _parse_bool, _check_bool),
    # Perfetto/Chrome trace_event JSON output path (repro.obs.trace).
    # Spans are only recorded when ``telemetry`` is on AND a path is set;
    # the file is written by ``repro.obs.finalize()`` / ``trace.export()``.
    "trace_path": _Field("REPRO_TRACE_PATH", None, _parse_optional_str,
                         _check_optional_str),
    # Per-step metrics JSONL output path (repro.obs.metrics); one line per
    # training step / serve tick, flushed as written.  Active only when
    # ``telemetry`` is on AND a path is set.
    "metrics_path": _Field("REPRO_METRICS_PATH", None, _parse_optional_str,
                           _check_optional_str),
}

#: fields whose change must re-arm the fault injector.
_FAULT_FIELDS = ("fault_spec", "fault_seed")

#: fields whose change must re-sync the telemetry subsystem.
_OBS_FIELDS = ("telemetry", "trace_path", "metrics_path")


def _invalidate_plan_caches() -> None:
    """Drop every memoized tile plan.  Lazy through sys.modules: config
    must stay importable before (and without) the kernel stack, and must
    not create an import cycle with it."""
    ops = sys.modules.get("repro.kernels.ops")
    if ops is not None:
        ops.clear_tile_plan_cache()


def _sync_fault_injector(import_now: bool = False) -> None:
    """Re-arm ``repro.ft.inject`` from the current fault fields.  Lazy by
    default (same no-cycle rule as the plan caches); ``import_now`` forces
    the import so an explicit ``update(fault_spec=...)`` validates the
    spec immediately instead of on first fault_point."""
    inject = sys.modules.get("repro.ft.inject")
    if inject is None and import_now:
        import importlib
        inject = importlib.import_module("repro.ft.inject")
    if inject is not None:
        inject.sync_from_config()


def _sync_obs(import_now: bool = False) -> None:
    """Re-sync ``repro.obs`` (event bus / tracer / metrics stream) from the
    current telemetry fields.  Lazy by default (same no-cycle rule as the
    plan caches); ``import_now`` forces the import so an explicit
    ``update(telemetry=True)`` activates the bus immediately."""
    obs = sys.modules.get("repro.obs")
    if obs is None and import_now:
        import importlib
        obs = importlib.import_module("repro.obs")
    if obs is not None:
        obs.sync_from_config()


class GlobalConfig:
    """The global configuration singleton (``repro.config``).

    Frozen by default: ``config.field = x`` raises; go through
    :meth:`update` (permanent) or :meth:`override` (scoped).  Reading a
    field whose legacy env var changed since init adopts the env value with
    a ``DeprecationWarning`` (the post-import env-mutation shim).
    """

    def __init__(self, env: dict | None = None):
        env = os.environ if env is None else env
        object.__setattr__(self, "_env", env)
        values, raws = {}, {}
        for name, f in FIELDS.items():
            raw = env.get(f.env)
            raws[name] = raw
            values[name] = f.default if raw is None else f.parse(raw)
        object.__setattr__(self, "_values", values)
        object.__setattr__(self, "_env_raw", raws)

    # -- reads ------------------------------------------------------------

    def __getattr__(self, name: str):
        f = FIELDS.get(name)
        if f is None:
            raise AttributeError(
                f"repro.config has no field {name!r}; fields: "
                f"{tuple(FIELDS)}")
        raw = self._env[f.env] if f.env in self._env else None
        if raw != self._env_raw[name]:
            warnings.warn(
                f"mutating {f.env} after import is deprecated; use "
                f"repro.config.update({name}=...) instead",
                DeprecationWarning, stacklevel=2)
            self._env_raw[name] = raw
            self._values[name] = f.default if raw is None else f.parse(raw)
            if f.plan_affecting:
                _invalidate_plan_caches()
            if name in _FAULT_FIELDS:
                _sync_fault_injector()
            if name in _OBS_FIELDS:
                _sync_obs()
        if name == "interpret" and self._values[name] is None:
            self._values[name] = _backend_interpret()
        return self._values[name]

    def snapshot(self) -> dict[str, Any]:
        """Current value of every field (a plain dict copy)."""
        return {name: getattr(self, name) for name in FIELDS}

    # -- writes -----------------------------------------------------------

    def __setattr__(self, name: str, value) -> None:
        raise AttributeError(
            f"repro.config is frozen; use config.update({name}={value!r}) "
            f"or the config.override(...) context manager")

    def update(self, **kw) -> None:
        """Validated permanent update; invalidates the tile-plan caches
        when a plan-affecting field actually changes."""
        unknown = set(kw) - set(FIELDS)
        if unknown:
            raise ValueError(
                f"unknown config field(s) {sorted(unknown)}; fields: "
                f"{tuple(FIELDS)}")
        invalidate = resync_faults = resync_obs = False
        for name, value in kw.items():
            f = FIELDS[name]
            value = f.check(value)
            if f.plan_affecting and self._values[name] != value:
                invalidate = True
            if name in _FAULT_FIELDS and self._values[name] != value:
                resync_faults = True
            if name in _OBS_FIELDS and self._values[name] != value:
                resync_obs = True
            self._values[name] = value
            # An explicit update() supersedes the env var: re-snapshot so a
            # subsequent read does not "restore" the stale env value.
            self._env_raw[name] = self._env.get(f.env)
        if invalidate:
            _invalidate_plan_caches()
        if resync_faults:
            _sync_fault_injector(import_now=True)
        if resync_obs:
            _sync_obs(import_now=True)

    @contextlib.contextmanager
    def override(self, **kw):
        """Scoped :meth:`update`: previous values restored on exit (also on
        exception), with the same cache invalidation on both edges."""
        saved = {name: self._values[name] for name in kw}
        self.update(**kw)
        try:
            yield self
        finally:
            self.update(**saved)


#: the singleton.  ``import repro; repro.config`` and
#: ``from repro.core.config import config`` are the same object.
config = GlobalConfig()
