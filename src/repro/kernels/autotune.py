"""Measured autotuning of tap-GEMM tile plans, with a persistent cache.

The analytic planner in :mod:`repro.kernels.ops` minimizes a bytes-moved
model under the VMEM budget -- but analytic cost models routinely
mispredict on real matmul accelerators (the plan that moves the fewest
bytes is often not the fastest).  When ``repro.config.autotune`` is
enabled, the planners route through :func:`tuned_plan`:

    analytic plan (feasibility + event accounting stay analytic)
      -> in-process memo
      -> persistent JSON plan cache (key: schema | role | platform |
         interpret | budget | ConvDims) -> revalidate via
         ``ops.plan_from_tile`` (geometry/budget drift => "stale")
      -> mode "measure": time the top-k analytic candidates on device
         (warmup + best-of-reps around ``block_until_ready``), persist the
         winner atomically;
         mode "cached": never time -- persisted winners when present,
         the analytic plan otherwise.

The cache file lives inside jax's compilation cache directory by default
(``config.plan_cache_dir`` overrides), is written atomically
(tmp + ``os.replace``), and tolerates corrupt files and stale entries:
a bad entry re-tunes, it never crashes.  Timing is interpret-mode aware:
under ``config.interpret`` the numbers measure the CPU interpreter (only
useful to exercise the full path in CI), on a real TPU they measure the
Mosaic-compiled kernels.

Every resolution is observable: plans carry ``autotuned`` /
``measured_us`` / ``candidates_timed`` / ``cache``
(``hit|miss|stale|poisoned``), surfaced by ``ops.plan_report`` and counted
in ``ops.plan_events()`` as ``{role}_autotune_{hit,miss,stale,poisoned,
measure_failed}``.  A runtime engine failure poison-marks its entry
(:func:`poison_plan`) so ``autotune="cached"`` cannot re-crash on restart;
a candidate that crashes while being timed is skipped, never fatal.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
import warnings

import jax
import jax.numpy as jnp

from repro.core.compile_cache import compile_cache_dir
from repro.core.config import config
from repro.core.im2col_ref import ConvDims
from repro.ft.inject import InjectedFault, fault_point
from repro.kernels import ops
from repro.obs import trace as obs_trace

#: bump when the key layout, the entry payload or the meaning of a tile
#: changes; older files are ignored wholesale (equivalent to a cold cache).
CACHE_SCHEMA = 3

_CACHE_FILE = "plan_cache.json"

#: key -> fully annotated plan; dropped by config changes (clear_memo).
_MEMO: dict[str, object] = {}


def clear_memo() -> None:
    """Drop the in-process tuned-plan memo (NOT the on-disk cache)."""
    _MEMO.clear()


# ---------------------------------------------------------------------------
# Persistent store
# ---------------------------------------------------------------------------

def default_cache_dir() -> str:
    """``config.plan_cache_dir`` when set, else a ``repro_plan_cache``
    directory inside jax's compilation cache directory."""
    if config.plan_cache_dir is not None:
        return config.plan_cache_dir
    return os.path.join(compile_cache_dir(), "repro_plan_cache")


def cache_path() -> str:
    return os.path.join(default_cache_dir(), _CACHE_FILE)


def _load_store() -> dict:
    """The on-disk store, or a fresh one on any read/parse/schema problem
    (a corrupt cache is a cold cache, never an error)."""
    try:
        fault_point("plan_cache.read")
        with open(cache_path(), encoding="utf-8") as f:
            store = json.load(f)
        if (isinstance(store, dict) and store.get("schema") == CACHE_SCHEMA
                and isinstance(store.get("entries"), dict)):
            return store
    except (OSError, ValueError, InjectedFault):
        pass
    return {"schema": CACHE_SCHEMA, "entries": {}}


def _save_store(store: dict) -> None:
    """Atomic best-effort write (tmp + ``os.replace``); an unwritable
    cache dir degrades to tuning every process, not to a crash."""
    path = cache_path()
    try:
        fault_point("plan_cache.write")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(store, f, indent=0, sort_keys=True)
        os.replace(tmp, path)
    except (OSError, InjectedFault) as e:
        warnings.warn(f"plan cache not persisted ({e}); will re-tune next "
                      f"process", RuntimeWarning, stacklevel=2)


def plan_key(role: str, d: ConvDims, budget: int) -> str:
    """Stable identity of one planning problem.  Platform and interpret
    mode are part of the key: a plan timed on the CPU interpreter must
    never be served to a TPU run (and vice versa)."""
    dims = ",".join(f"{f.name}={getattr(d, f.name)}"
                    for f in dataclasses.fields(d))
    return (f"v{CACHE_SCHEMA}|{role}|{jax.default_backend()}"
            f"|interpret={int(bool(config.interpret))}|budget={budget}"
            f"|{dims}")


# ---------------------------------------------------------------------------
# Timing harness
# ---------------------------------------------------------------------------

def _run_fn(role: str, d: ConvDims, plan):
    """A jitted zero-arg closure running one conv pass under ``plan``.
    Dummy operands: timing is data-independent."""
    x = jnp.ones((d.B, d.C, d.H_i, d.W_i), jnp.float32)
    w = jnp.ones((d.N, d.C, d.k_taps_h, d.k_taps_w), jnp.float32)
    dy = jnp.ones((d.B, d.N, d.H_o, d.W_o), jnp.float32)
    if role == "forward":
        f = jax.jit(lambda a, b: ops.conv2d_forward(a, b, d, plan=plan))
        return lambda: f(x, w)
    if role == "weight_grad":
        f = jax.jit(lambda a, b: ops.conv2d_weight_grad(a, b, d, plan=plan))
        return lambda: f(x, dy)
    if role == "input_grad":
        f = jax.jit(lambda a, b: ops.conv2d_input_grad(a, b, d, plan=plan))
        return lambda: f(dy, w)
    raise ValueError(
        f"unknown plan role {role!r}; roles: {ops.PLAN_ROLES}")


def measure_plan(role: str, d: ConvDims, plan,
                 reps: int | None = None, warmup: int = 1) -> float:
    """Best-of-``reps`` wall time of one conv pass in MICROSECONDS, after
    ``warmup`` untimed calls (absorbing compilation).  Each call is fenced
    with ``block_until_ready`` so async dispatch cannot flatter a plan."""
    fault_point("autotune.measure")
    reps = config.autotune_reps if reps is None else reps
    with obs_trace.span(
            "autotune:measure", role=role, reps=reps,
            dims=[d.B, d.C, d.H_i, d.W_i, d.N, d.K_h, d.K_w]):
        fn = _run_fn(role, d, plan)
        for _ in range(max(1, warmup)):
            jax.block_until_ready(fn())
        best = float("inf")
        for _ in range(max(1, reps)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
    return best * 1e6


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------

def _tile_of(plan) -> ops.TilePlan:
    return plan.tile if isinstance(plan, ops.PhasePlan) else plan


def _annotate(plan, **kw):
    """A copy of ``plan`` with autotune provenance fields set (on the
    inner tile for a PhasePlan -- that is what plan_report renders)."""
    if isinstance(plan, ops.PhasePlan):
        return dataclasses.replace(
            plan, tile=dataclasses.replace(plan.tile, **kw))
    return dataclasses.replace(plan, **kw)


def memoized(role: str, d: ConvDims, budget: int):
    """The plan this process already resolved for one planning problem,
    or None."""
    return _MEMO.get(plan_key(role, d, budget))


def tuned_plan(role: str, d: ConvDims, budget: int, analytic):
    """The tuned (or cache-served, or annotated-analytic) plan for one
    planning problem.  ``analytic`` is the already-resolved analytic plan
    and is always feasible here (``ops._autotuned`` never routes
    fits=False / None plans -- there is nothing to race)."""
    key = plan_key(role, d, budget)
    hit = _MEMO.get(key)
    if hit is not None:
        return hit

    store = _load_store()
    entry = store["entries"].get(key)
    state = "miss"
    if entry is not None and entry.get("poisoned"):
        # A runtime engine failure poison-marked this entry (conv.py's
        # degradation layer): never serve the persisted tile again.  In
        # "cached" mode degrade to the analytic plan; "measure" mode
        # re-tunes, and the fresh winner overwrites the poison mark.
        ops._count_event(f"{role}_autotune_poisoned")
        if config.autotune != "measure":
            plan = _annotate(analytic, cache="poisoned")
            _MEMO[key] = plan
            return plan
        entry = None
        state = "poisoned"
    if entry is not None:
        plan = ops.plan_from_tile(role, d, budget, entry.get("tile", ()))
        if plan is not None:
            plan = _annotate(
                plan, autotuned=True,
                measured_us=float(entry.get("measured_us", -1.0)),
                candidates_timed=int(entry.get("candidates_timed", 0)),
                cache="hit")
            ops._count_event(f"{role}_autotune_hit")
            _MEMO[key] = plan
            return plan
        state = "stale"                   # geometry/budget drift or garbage
    if state != "poisoned":               # poisoned already counted above
        ops._count_event(f"{role}_autotune_{state}")

    if config.autotune != "measure":      # "cached": never time
        plan = _annotate(analytic, cache=state)
        _MEMO[key] = plan
        return plan

    cands = ops.plan_candidates(role, d, budget, k=config.autotune_top_k)
    if not cands:                         # defensive; analytic was feasible
        cands = [analytic]
    best, best_us, timed = None, float("inf"), 0
    for cand in cands:
        try:
            us = measure_plan(role, d, cand)
        except Exception:
            # A candidate that crashes (lowering error, injected fault)
            # must not kill tuning for the whole problem: skip it.
            ops._count_event(f"{role}_autotune_measure_failed")
            continue
        timed += 1
        if us < best_us:
            best, best_us = cand, us
    if best is None:                      # every candidate crashed
        plan = _annotate(analytic, cache=state)
        _MEMO[key] = plan
        return plan
    best = _annotate(best, autotuned=True, measured_us=best_us,
                     candidates_timed=timed, cache=state)
    store["entries"][key] = {
        "tile": list(_tile_of(best).tile_key),
        "measured_us": best_us,
        "candidates_timed": timed,
    }
    _save_store(store)
    _MEMO[key] = best
    return best


def poison_plan(role: str, d: ConvDims, budget: int | None = None) -> str:
    """Poison-mark the persisted plan-cache entry of one planning problem.

    Called by the runtime-degradation layer (``core/conv.py``) when a
    pallas engine execution raises: whatever plan served that launch must
    not be served again on restart -- ``autotune="cached"`` degrades to
    the analytic plan for the key, ``autotune="measure"`` re-tunes (a
    successful fresh measurement overwrites the mark, which is the
    recovery path).  Returns the poisoned key.
    """
    if budget is None:
        budget = config.vmem_budget_bytes
    key = plan_key(role, d, budget)
    _MEMO.pop(key, None)
    store = _load_store()
    entry = store["entries"].get(key) or {}
    store["entries"][key] = {**entry, "poisoned": True}
    _save_store(store)
    return key
