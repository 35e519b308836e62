"""Kernel microbenchmarks: wall-clock of the conv backprop engines and the
Pallas kernels (interpret mode) on CPU, plus derived bytes-moved ratios, the
static tile plans the Pallas lanes dispatch with, and the per-pass engines
the ``auto`` policy resolves to.

Two levels are measured per case:
  * raw engine primitives (input_grad_*, weight_grad_*), as before;
  * the end-to-end ``jax.grad`` path through the ``conv2d`` custom_vjp
    under several ``EnginePolicy`` configurations -- the uniform engines,
    ``auto``, and a mixed per-pass policy -- what a training step actually
    runs.

interpret-mode wall-clock is NOT TPU performance; the derived columns
(bytes/elements moved, tile plans, fallback counts, resolved policies) are
the hardware-independent quantities -- they are what TPU runs (where the
kernels compile with Mosaic) compare against.

    PYTHONPATH=src python benchmarks/bench_kernels.py [--tiny] \
        [--json BENCH_kernels.json] [--compare BENCH_kernels.json]

``--tiny`` runs one small shape with 1 rep (the CI smoke lane) and FAILS if
any case falls off the Pallas path: a tile-plan fallback counter > 0 OR the
``auto`` policy resolving any pass of any tiny case to a non-pallas engine.
``--json`` writes the machine-readable record (schema 5): per-case
wall-clock, bytes-moved ratios, tile plans (fits / spatial splits / VMEM
footprint), per-pass auto-policy resolution, the per-case tap counts
(``taps.real`` vs ``taps.materialized`` -- the dilated case's skip_ratio
shows the ~1/(d_h*d_w) zero-skipping), and the planner's hit/fallback
event counts.  The case list includes an asymmetric-stride (2, 3) layer
and a dilated (d=2) layer, both of which the per-axis tap tables keep on
the Pallas path, plus TRANSPOSED-conv forward cases (a stride-2 decoder
stage and a stride-2 + dilated-kernel stage): their records carry
``taps{real, zero_inserted, skip_ratio}`` -- the taps the fused phase
plan runs vs what a stride-1 conv over the physically zero-inserted
input would run, ``skip_ratio ~ 1 - 1/(s_h*s_w)`` -- and the bench FAILS
outright if a transposed case's ``real`` is not strictly below
``zero_inserted``.  The committed ``BENCH_kernels.json`` is the perf
baseline.  ``--compare PATH`` re-runs the bench and exits non-zero if any
shared timing column slowed down by more than ``--tolerance`` (default
35%, re-measured once so only REPRODUCED slowdowns fail -- interpret-mode
CPU wall-clock is long-tailed), any case that previously stayed on the
Pallas path now falls back, or a case's Pallas tap count grew
(zero-skipping regressed -- the gate covers the transposed cases'
``taps.real`` identically).

Schema 5 adds ``plan_time_us = {cold, warm}``: the total planning time
for every case with the tile-plan caches dropped vs memoized.

Schema 6 adds the telemetry-overhead columns (``repro.obs``): every case
is re-measured through the SAME jitted ``jax.grad`` path with telemetry
off and then on (bus + trace active), ``telemetry_off_us`` /
``telemetry_on_us`` / ``telemetry_overhead`` (the on/off ratio).  All
obs emission happens at dispatch (trace) time, so the compiled
steady-state cost of enabling telemetry is designed to be zero -- the
disarmed-check idiom -- and ``--compare`` gates the ratio at < 3%
per case (re-measured once, like every wall-clock gate).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

from repro.core.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import bpim2col, im2col_ref, phase_decomp   # noqa: E402
from repro.core.conv import (conv2d, conv2d_transpose,      # noqa: E402
                             resolve_policy, transpose_dims,
                             transpose_tap_counts)
from repro.core.convspec import ConvSpec, ConvTransposeSpec  # noqa: E402
from repro.core.config import config                        # noqa: E402
from repro.core.im2col_ref import ConvDims                  # noqa: E402
from repro.kernels import ops                               # noqa: E402

CASES = [
    ConvDims(B=2, C=16, H_i=32, W_i=32, N=32, K_h=3, K_w=3, S=2, P_h=1, P_w=1),
    ConvDims(B=2, C=32, H_i=28, W_i=28, N=32, K_h=3, K_w=3, S=2, P_h=1, P_w=1),
    # Realistic mid-network layer: a >=56x56 spatial plane that previously
    # had to prove the WHOLE plane fits VMEM to stay on the Pallas path.
    ConvDims(B=1, C=128, H_i=56, W_i=56, N=128, K_h=3, K_w=3, S=2,
             P_h=1, P_w=1),
    # Asymmetric stride (2, 3): per-axis tap tables keep it on the Pallas
    # path (pre-PR-4 this was capability-gated onto bp_phase).
    ConvDims(B=2, C=16, H_i=32, W_i=24, N=32, K_h=3, K_w=3, S=2, S_w=3,
             P_h=1, P_w=1),
    # Dilated 3x3 (d=2, effective extent 5): the tap table skips the zero
    # taps, so the Pallas GEMMs run 9 taps, not the materialized 25.
    ConvDims(B=2, C=16, H_i=32, W_i=32, N=32, K_h=5, K_w=5, S=2,
             P_h=2, P_w=2, D_h=2, D_w=2),
]

TINY_CASES = [
    ConvDims(B=1, C=4, H_i=12, W_i=12, N=8, K_h=3, K_w=3, S=2, P_h=1, P_w=1),
]

# Transposed convolution AS A FORWARD LAYER (decoders / GAN generators):
# (x_shape NCHW, w_shape (C_in, C_out/g, K, K), ConvTransposeSpec).  The
# stride is the lhs (input) dilation; the tap-native path runs the fused
# phase plan over the compact input while the zero-insertion lowering
# ("traditional") physically builds the zero-spaced tensor.
TRANSPOSE_CASES = [
    # Stride-2 decoder stage: 16x16 -> 32x32 (pad 1, output_padding 1).
    ((2, 32, 16, 16), (32, 16, 3, 3),
     ConvTransposeSpec.make(stride=2, padding=1, output_padding=1)),
    # Stride-2 + dilated 3x3 kernel (d=2, effective extent 5): lhs AND rhs
    # dilation zero-skipping compose.
    ((2, 16, 16, 16), (16, 16, 3, 3),
     ConvTransposeSpec.make(stride=2, padding=2, output_padding=1,
                            dilation=2)),
]

TINY_TRANSPOSE_CASES = [
    ((1, 8, 8, 8), (8, 4, 3, 3),
     ConvTransposeSpec.make(stride=2, padding=1, output_padding=1)),
]

# End-to-end jax.grad policies: uniform engines (the old mode matrix), the
# shape-dependent auto default, and a mixed per-pass policy exercising three
# different engines in one backward.
GRAD_POLICIES = (
    ("traditional", "traditional"),
    ("bp_im2col", "bp_im2col"),
    ("bp_phase", "bp_phase"),
    ("pallas", "pallas"),
    ("auto", "auto"),
    ("mixed", "fwd=lax,dgrad=pallas,wgrad=bp_phase"),
)

# Transposed-case policies: the zero-insertion materialization baseline
# ("traditional"), the implicit engines, and a mixed per-pass policy.
GRAD_POLICIES_T = (
    ("traditional", "traditional"),
    ("bp_phase", "bp_phase"),
    ("pallas", "pallas"),
    ("auto", "auto"),
    ("mixed", "fwd=pallas,dgrad=bp_phase,wgrad=bp_im2col"),
)


def _t(fn, *args, reps=5):
    """Best-of-``reps`` wall-clock in us (min is the standard
    noise-robust microbenchmark statistic: load spikes on a shared CPU
    only ever INFLATE a sample, so the minimum tracks the true cost and
    keeps the --compare gate from tripping on scheduler noise)."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _spec(d: ConvDims) -> ConvSpec:
    return ConvSpec.make(stride=(d.s_h, d.s_w),
                         padding=((d.P_h, d.p_h_hi), (d.P_w, d.p_w_hi)),
                         dilation=(d.D_h, d.D_w))


def _grad_fn(d: ConvDims, policy: str):
    """jit'd jax.grad through the conv2d custom_vjp for one policy."""
    spec = _spec(d)

    @jax.jit
    def g(x, w):
        return jax.grad(
            lambda a, b: jnp.sum(conv2d(a, b, spec, policy) ** 2),
            argnums=(0, 1))(x, w)
    return g


def _bytes_moved(d: ConvDims) -> dict[str, float]:
    """Hardware-independent reorganization traffic: how many elements the
    traditional zero-space datapath moves per compact element (BP-im2col
    moves none of the zero-space)."""
    loss = im2col_ref.reorg_traffic_elems_loss(d)
    grad = im2col_ref.reorg_traffic_elems_grad(d)
    compact = d.B * d.N * d.H_o * d.W_o
    return {
        "loss_offchip_ratio": round(loss["offchip_stream"] / compact, 3),
        "grad_offchip_ratio": round(grad["offchip_stream"] / compact, 3),
        "loss_extra_storage_elems": loss["extra_storage"],
        "grad_extra_storage_elems": grad["extra_storage"],
        "lowered_sparsity": round(bpim2col.lowered_sparsity_loss(d), 3),
    }


def _t_grad_fn(spec: ConvTransposeSpec, policy: str):
    """jit'd jax.grad through the conv2d_transpose custom_vjp."""
    @jax.jit
    def g(x, w):
        return jax.grad(
            lambda a, b: jnp.sum(conv2d_transpose(a, b, spec, policy) ** 2),
            argnums=(0, 1))(x, w)
    return g


#: the telemetry-overhead gate: a case's on/off wall-clock ratio above
#: this fails --compare (re-measured once, like every wall-clock gate).
#: All obs emission is dispatch-time, so a compiled step should not move
#: at all; 3% is pure scheduler-noise headroom.
TELEMETRY_OVERHEAD_MAX = 1.03


def _telemetry_overhead(make_fn, x, w, reps) -> dict[str, float]:
    """Steady-state telemetry cost: the same jax.grad case through a
    FRESH jitted fn with telemetry off vs on (bus + trace active).
    Dispatch-time emission lands in ``_t``'s warmup call (which compiles
    the fresh fn), so the measured reps see exactly what enabling
    telemetry adds to a compiled training step.  The two arms are timed
    back-to-back in INTERLEAVED rounds and the ratio is taken PER ROUND,
    keeping the round with the smallest ratio: a real steady-state cost
    would survive every round, while scheduler noise / CPU-frequency
    drift inflates only some rounds (and both arms of a round equally)."""
    fn_off = make_fn()
    fn_on = None
    best = None                              # (ratio, off_us, on_us)
    reps = max(reps, 20)                     # the 3% gate needs a low floor
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "trace.json")
        for _ in range(5):
            off = _t(fn_off, x, w, reps=reps)
            with config.override(telemetry=True, trace_path=path):
                if fn_on is None:
                    fn_on = make_fn()        # traced with telemetry on
                on = _t(fn_on, x, w, reps=reps)
            if best is None or on / off < best[0]:
                best = (on / off, off, on)
    ratio, off, on = best
    return {"telemetry_off_us": round(off, 1),
            "telemetry_on_us": round(on, 1),
            "telemetry_overhead": round(ratio, 3)}


def run_transpose(csv=True, tcases=None, reps=5,
                  grad_policies=GRAD_POLICIES_T):
    """Timing rows for the transposed (lhs-dilation) forward-layer cases:
    end-to-end forward and jax.grad per policy -- "traditional" is the
    physical zero-insertion materialization the implicit engines avoid."""
    rng = np.random.RandomState(1)
    rows = []
    for x_shape, w_shape, spec in tcases if tcases is not None \
            else TRANSPOSE_CASES:
        x = jnp.asarray(rng.randn(*x_shape), jnp.float32)
        w = jnp.asarray(rng.randn(*w_shape), jnp.float32)
        d = transpose_dims(x_shape, w_shape, spec)
        dil = f"/d{spec.d_h}x{spec.d_w}" if spec.has_dilation else ""
        row = {"case": f"T:{x_shape[2]}/{x_shape[1]}/{w_shape[1]}/"
                       f"{w_shape[2]}/{spec.s_h}x{spec.s_w}/"
                       f"{spec.padding[0][0]}+op{spec.op_h}{dil}"}
        for label, policy in grad_policies:
            fwd = jax.jit(lambda a, b, p=policy:
                          conv2d_transpose(a, b, spec, p))
            row[f"fwdT_{label}_us"] = round(_t(fwd, x, w, reps=reps), 1)
            row[f"gradT_{label}_us"] = round(
                _t(_t_grad_fn(spec, policy), x, w, reps=reps), 1)
        row.update(_telemetry_overhead(
            lambda s=spec: _t_grad_fn(s, "bp_phase"), x, w, reps))
        tap = transpose_tap_counts(d)
        row["taps_skip_ratio"] = tap["skip_ratio"]
        rows.append(row)
    if csv and rows:
        print(",".join(rows[0].keys()))
        for r in rows:
            print(",".join(str(v) for v in r.values()))
    return rows


def run(csv=True, cases=None, reps=5, grad_policies=GRAD_POLICIES):
    rng = np.random.RandomState(0)
    rows = []
    for d in cases or CASES:
        x = jnp.asarray(rng.randn(d.B, d.C, d.H_i, d.W_i), jnp.float32)
        # The Pallas engine (and the end-to-end conv2d surface) take the
        # COMPACT kernel; the materializing engines take the zero-dilated
        # effective kernel -- identical arrays when the case is undilated.
        w = jnp.asarray(rng.randn(d.N, d.C, d.k_taps_h, d.k_taps_w),
                        jnp.float32)
        w_eff = im2col_ref.zero_insert(w, (d.D_h, d.D_w)) \
            if d.has_dilation else w
        dy = jnp.asarray(rng.randn(d.B, d.N, d.H_o, d.W_o), jnp.float32)
        t_trad = _t(jax.jit(lambda a, b: im2col_ref.input_grad_explicit(a, b, d)), dy, w_eff, reps=reps)
        t_bp = _t(jax.jit(lambda a, b: bpim2col.input_grad_implicit(a, b, d)), dy, w_eff, reps=reps)
        t_ph = _t(jax.jit(lambda a, b: phase_decomp.input_grad_phase(a, b, d)), dy, w_eff, reps=reps)
        t_pl = _t(jax.jit(lambda a, b: ops.conv2d_input_grad(a, b, d)), dy, w, reps=reps)
        tg_trad = _t(jax.jit(lambda a, b: im2col_ref.weight_grad_explicit(a, b, d)), x, dy, reps=reps)
        tg_ph = _t(jax.jit(lambda a, b: phase_decomp.weight_grad_phase(a, b, d)), x, dy, reps=reps)
        tg_pl = _t(jax.jit(lambda a, b: ops.conv2d_weight_grad(a, b, d)), x, dy, reps=reps)
        dil = f"/d{d.D_h}x{d.D_w}" if d.has_dilation else ""
        row = {
            "case": f"{d.H_i}/{d.C}/{d.N}/{d.K_h}/{d.s_h}x{d.s_w}/"
                    f"{d.P_h}{dil}",
            "dI_trad_us": round(t_trad, 1),
            "dI_bp_gather_us": round(t_bp, 1),
            "dI_phase_us": round(t_ph, 1),
            "dI_pallas_us": round(t_pl, 1),
            "dI_speedup_phase": round(t_trad / t_ph, 2),
            "dW_trad_us": round(tg_trad, 1),
            "dW_phase_us": round(tg_ph, 1),
            "dW_pallas_us": round(tg_pl, 1),
            "dW_speedup_phase": round(tg_trad / tg_ph, 2),
            "lowered_sparsity": round(bpim2col.lowered_sparsity_loss(d), 3),
        }
        # End-to-end jax.grad through the custom_vjp (the training path).
        for label, policy in grad_policies:
            row[f"grad_{label}_us"] = round(_t(_grad_fn(d, policy), x, w,
                                               reps=reps), 1)
        row.update(_telemetry_overhead(
            lambda dd=d: _grad_fn(dd, "bp_phase"), x, w, reps))
        rows.append(row)
    if csv:
        print(",".join(rows[0].keys()))
        for r in rows:
            print(",".join(str(v) for v in r.values()))
    return rows


def _auto_resolution(d: ConvDims) -> dict[str, str]:
    """pass -> engine the auto policy resolves to for this geometry."""
    return {p: v["engine"] for p, v in resolve_policy(d, "auto").items()}


def _transpose_record_cases(trows, tcases) -> list[dict]:
    """Per-transposed-case records: the mirror-conv tile plans, the
    zero-insertion tap accounting (``taps.real`` vs ``taps.zero_inserted``
    -- ``skip_ratio ~ 1 - 1/(s_h*s_w)`` is the lhs-dilation skipping), and
    the per-pass auto-policy resolution over the mirror dims."""
    out = []
    for (x_shape, w_shape, spec), row in zip(tcases, trows):
        d = transpose_dims(x_shape, w_shape, spec)
        plan = ops.plan_report(d)
        auto = {p: v["engine"] for p, v in
                resolve_policy(d, "auto", transposed=True).items()}
        taps = transpose_tap_counts(d)
        if taps["real"] >= taps["zero_inserted"]:
            # Structural gate (explicit raise: must not evaporate under
            # python -O the way a bare assert would).
            raise SystemExit(
                "transposed case runs no fewer taps than the zero-inserted "
                f"materialization: {taps}")
        out.append({
            "dims": {"transpose": True, "B": x_shape[0], "C": x_shape[1],
                     "H_i": x_shape[2], "W_i": x_shape[3],
                     "N": w_shape[1] * spec.groups,
                     "K_h": w_shape[2], "K_w": w_shape[3],
                     "S": spec.s_h, "S_w": spec.s_w,
                     "D_h": spec.d_h, "D_w": spec.d_w,
                     "P_h": spec.padding[0][0], "P_w": spec.padding[1][0],
                     "op_h": spec.op_h, "op_w": spec.op_w},
            "timings_us": row,
            "plan": plan,
            "taps": taps,
            "auto_policy": auto,
            "auto_all_pallas": all(e == "pallas" for e in auto.values()),
            "fits": plan["pallas_path"],
            "input_grad_plan_none": not plan["input_grad"].get("fused",
                                                               False),
        })
    return out


def _all_plan_dims(cases, tcases) -> list[ConvDims]:
    """Every ConvDims the record plans: the direct cases plus the
    transposed cases' mirror-conv dims."""
    return list(cases) + [transpose_dims(x_shape, w_shape, spec)
                          for x_shape, w_shape, spec in tcases]


def _measure_plan_time(cases, tcases) -> dict[str, float]:
    """Total wall time (us) to plan EVERY case, cold (the tile-plan lru
    caches dropped) then warm (everything memoized).  Warm is the
    steady-state cost a training step sees."""
    dims = _all_plan_dims(cases, tcases)

    def once():
        t0 = time.perf_counter()
        for d in dims:
            ops.plan_report(d)
        return (time.perf_counter() - t0) * 1e6

    ops.clear_tile_plan_cache()
    cold = once()
    warm = once()
    return {"cold": round(cold, 1), "warm": round(warm, 1)}


def _json_record(rows, cases, trows=(), tcases=(),
                 plan_time_us=None) -> dict:
    """Attach the static tile plans + traffic ratios + per-pass auto-policy
    resolution to the timing rows."""
    cases = list(cases)
    record_cases = []
    for d, row in zip(cases, rows):
        plan = ops.plan_report(d)
        auto = _auto_resolution(d)
        real = plan["kernel_taps"]["real"]
        materialized = plan["kernel_taps"]["materialized"]
        record_cases.append({
            "dims": {"B": d.B, "C": d.C, "H_i": d.H_i, "W_i": d.W_i,
                     "N": d.N, "K_h": d.K_h, "K_w": d.K_w, "S": d.S,
                     "S_w": d.S_w, "D_h": d.D_h, "D_w": d.D_w,
                     "P_h": d.P_h, "P_w": d.P_w},
            "timings_us": row,
            "bytes_moved": _bytes_moved(d),
            "plan": plan,
            # Zero-skipping dilation: the tap count the Pallas GEMMs run
            # vs what the kernel-materialization lowering would run.
            "taps": {"real": real, "materialized": materialized,
                     "skip_ratio": round(real / materialized, 3)},
            "auto_policy": auto,
            "auto_all_pallas": all(e == "pallas" for e in auto.values()),
            "fits": plan["pallas_path"],
            "input_grad_plan_none": not plan["input_grad"].get("fused",
                                                               False),
        })
    record_cases.extend(_transpose_record_cases(trows, tcases))
    events = ops.plan_events()
    fallbacks = sum(v for k, v in events.items() if k.endswith("_fallback"))
    return {
        "bench": "bench_kernels",
        "schema": 6,
        "vmem_budget_bytes": config.vmem_budget_bytes,
        "interpret": config.interpret,
        "plan_time_us": plan_time_us,
        "cases": record_cases,
        "plan_events": events,
        "tile_plan_fallbacks": fallbacks,
        "pallas_path_all_cases": all(c["fits"] for c in record_cases),
        "auto_policy_all_pallas": all(c["auto_all_pallas"]
                                      for c in record_cases),
    }


def _case_key(case: dict) -> tuple:
    return tuple(sorted(case["dims"].items()))


def compare_records(record: dict, baseline: dict,
                    tolerance: float = 0.35) -> list[str]:
    """Regressions of ``record`` vs ``baseline``: any shared timing column
    slower by > tolerance, any case leaving the Pallas path, and any pass
    the auto policy used to place on pallas but no longer does."""
    problems = []
    base_cases = {_case_key(c): c for c in baseline.get("cases", [])}
    new_keys = {_case_key(c) for c in record["cases"]}
    for key, b in base_cases.items():
        if key not in new_keys:
            # Dropping a benchmarked shape must not pass vacuously.
            problems.append(
                f"baseline case {dict(b['dims'])} missing from the new "
                "record (case dropped or dims changed?)")
    for c in record["cases"]:
        b = base_cases.get(_case_key(c))
        if b is None:
            continue                        # new case: nothing to compare
        name = c["timings_us"].get("case", str(dict(c["dims"])))
        for col, base_us in b["timings_us"].items():
            if not col.endswith("_us") or not isinstance(base_us,
                                                         (int, float)):
                continue
            if col.startswith("telemetry_"):
                # The off/on arms only exist to form the ratio; their
                # contract is the ABSOLUTE overhead gate below, not a
                # baseline-relative wall-clock diff (the grad_*_us
                # columns already gate this fn's wall-clock).
                continue
            now_us = c["timings_us"].get(col)
            if now_us is None:
                # A renamed/dropped column must not pass vacuously.
                problems.append(
                    f"{name} {col}: present in baseline but missing from "
                    "the new record (renamed or dropped?)")
                continue
            if now_us > base_us * (1.0 + tolerance):
                problems.append(
                    f"{name} {col}: {now_us:.1f}us vs baseline "
                    f"{base_us:.1f}us (+{now_us / base_us - 1.0:.0%} "
                    f"> {tolerance:.0%})")
        if b.get("fits") and not c.get("fits"):
            problems.append(f"{name}: tile plan regressed off the Pallas "
                            "path (fits: true -> false)")
        base_taps, new_taps = b.get("taps"), c.get("taps")
        if base_taps and new_taps and new_taps["real"] > base_taps["real"]:
            # More taps than the baseline means the dilation zero-skipping
            # (or the per-axis table) regressed to a denser enumeration.
            problems.append(
                f"{name}: Pallas tap count regressed "
                f"{base_taps['real']} -> {new_taps['real']}")
        base_auto = b.get("auto_policy", {})
        for pass_name, engine in c.get("auto_policy", {}).items():
            if base_auto.get(pass_name) == "pallas" and engine != "pallas":
                problems.append(
                    f"{name} {pass_name}: auto policy regressed "
                    f"pallas -> {engine}")
        # Telemetry must stay free in compiled steady state (emission is
        # dispatch-time only): an absolute gate, not baseline-relative.
        overhead = c["timings_us"].get("telemetry_overhead")
        if overhead is not None and overhead > TELEMETRY_OVERHEAD_MAX:
            problems.append(
                f"{name} telemetry_overhead: on/off ratio {overhead} > "
                f"{TELEMETRY_OVERHEAD_MAX} (enabling telemetry slowed "
                "the compiled step)")
    return problems


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="one small shape, 1 rep (CI smoke)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the machine-readable benchmark record")
    ap.add_argument("--compare", metavar="PATH", default=None,
                    help="exit non-zero on regression vs this baseline "
                         "record (slowdown > --tolerance, or a case "
                         "falling off the Pallas path)")
    ap.add_argument("--tolerance", type=float, default=0.35,
                    help="allowed per-column slowdown for --compare.  The "
                         "default absorbs interpret-mode CPU wall-clock "
                         "bimodality (the structural gates -- Pallas path, "
                         "auto policy, tap counts -- are tolerance-free); "
                         "tighten it for real-TPU comparisons")
    args = ap.parse_args()
    enable_compile_cache()
    cases = TINY_CASES if args.tiny else CASES
    tcases = TINY_TRANSPOSE_CASES if args.tiny else TRANSPOSE_CASES
    reps = 1 if args.tiny else 10
    ops.clear_tile_plan_cache()
    ops.reset_plan_events()
    rows = run(cases=cases, reps=reps)
    trows = run_transpose(tcases=tcases, reps=reps)
    assert rows and trows and all(
        v > 0 for r in (*rows, *trows) for k, v in r.items()
        if k.endswith("_us")), "bench produced no timings"
    plan_time = _measure_plan_time(cases, tcases)
    record = _json_record(rows, cases, trows, tcases,
                          plan_time_us=plan_time)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.json}", file=sys.stderr)
    if args.tiny:
        # CI gate (with or without --json): a tiny shape falling off the
        # Pallas path -- by tile-plan fallback OR by the auto policy
        # resolving any pass elsewhere -- is a planner/resolver regression,
        # not a capacity problem.
        if record["tile_plan_fallbacks"] > 0 or \
                not record["pallas_path_all_cases"] or \
                not record["auto_policy_all_pallas"]:
            print(f"FAIL: tile-plan fallbacks="
                  f"{record['tile_plan_fallbacks']}, "
                  f"pallas_path_all_cases="
                  f"{record['pallas_path_all_cases']}, "
                  f"auto_policy_all_pallas="
                  f"{record['auto_policy_all_pallas']}", file=sys.stderr)
            raise SystemExit(1)
    if args.compare:
        with open(args.compare) as f:
            baseline = json.load(f)
        problems = compare_records(record, baseline, args.tolerance)
        if problems:
            # CPU wall-clock is long-tailed on shared machines: re-measure
            # once and keep only the findings that REPRODUCE (a structural
            # regression -- Pallas path, auto policy, tap count -- always
            # does; a scheduler hiccup does not).
            ops.clear_tile_plan_cache()
            ops.reset_plan_events()
            record2 = _json_record(run(csv=False, cases=cases, reps=reps),
                                   cases,
                                   run_transpose(csv=False, tcases=tcases,
                                                 reps=reps),
                                   tcases)
            keys2 = {p.split(":", 1)[0]
                     for p in compare_records(record2, baseline,
                                              args.tolerance)}
            problems = [p for p in problems
                        if p.split(":", 1)[0] in keys2]
        if problems:
            print("PERF REGRESSION vs " + args.compare, file=sys.stderr)
            for p in problems:
                print("  " + p, file=sys.stderr)
            raise SystemExit(1)
        print(f"no regression vs {args.compare} "
              f"(tolerance {args.tolerance:.0%})", file=sys.stderr)


if __name__ == "__main__":
    main()
