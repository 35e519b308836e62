"""The observability subsystem: disarmed-path zero-cost contract, bus
consistency with the legacy counters, span tracing + Perfetto export,
the metrics stream, the covering reset, and the docs gate."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.config import config
from repro.core import conv
from repro.core.convspec import ConvSpec
from repro.serve.engine import SUMMARY_COUNTERS, merged_summary


def _x(b=1):
    return jnp.asarray(np.random.RandomState(0).randn(b, 3, 16, 16),
                       jnp.float32)


def _w():
    return jnp.asarray(np.random.RandomState(1).randn(8, 3, 3, 3) * 0.1,
                       jnp.float32)


SPEC = ConvSpec.make(stride=2, padding=1)


# ---------------------------------------------------------------------------
# Disarmed path: telemetry off must be literally free
# ---------------------------------------------------------------------------

def test_disarmed_records_nothing():
    assert not obs.enabled()
    conv.conv2d(_x(), _w(), SPEC, "bp_phase")
    assert conv.dispatch_events()                 # legacy surface records
    assert obs.events.events() == []              # the bus does not
    assert obs.events.counters("dispatch") == {}
    obs.events.emit("dispatch", "anything")       # no-op, no raise
    assert obs.events.events() == []


def test_disarmed_span_is_shared_null_singleton():
    # The inject.py idiom: no per-call allocation on the disabled path.
    assert not obs.trace.active()
    assert obs.trace.span("a", k=1) is obs.trace.span("b")
    d = conv.spec_dims((1, 3, 16, 16), (8, 3, 3, 3), SPEC)
    assert obs.trace.dispatch_span("fwd", "bp_phase", d) \
        is obs.trace.span("c")


def test_pass_scopes_are_on_with_telemetry_off():
    """The device-side scope of each conv pass needs no telemetry: it is
    HLO metadata written at trace time, and the span buffer stays
    disarmed."""
    assert not obs.enabled() and obs.trace._BUF is None

    def loss(x, w):
        return jnp.sum(conv.conv2d(x, w, SPEC, "bp_phase") ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        _x(), _w()).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    for pkey in ("forward", "input_grad", "weight_grad"):
        scope = obs.trace.pass_scope(pkey)
        assert any(re.search(rf"\b{scope}\b", p) for p in paths), scope
    assert obs.trace._BUF is None
    d = conv.spec_dims((1, 3, 16, 16), (8, 3, 3, 3), SPEC)
    assert obs.trace.dispatch_span("forward", "bp_phase", d) \
        is obs.trace.span("c")


def test_disarmed_metrics_write_nothing(tmp_path):
    obs.metrics.train_step(0, {"loss": 1.0})
    obs.metrics.record_latency(0.1)
    assert obs.metrics.lines_written() == 0
    assert not obs.metrics.active()


# ---------------------------------------------------------------------------
# The bus: legacy counters == bus views, exactly
# ---------------------------------------------------------------------------

def test_bus_matches_dispatch_events():
    with config.override(telemetry=True):
        assert obs.enabled()
        conv.conv2d(_x(), _w(), SPEC, "bp_phase")
        conv.conv2d(_x(), _w(), SPEC, "lax")
        legacy = conv.dispatch_events()
        assert legacy and obs.events.counters("dispatch") == legacy
        rep = obs.report()
        assert rep["consistent"], rep["divergences"]
        assert rep["events_by_kind"]["dispatch"] == sum(legacy.values())
    assert not obs.enabled()                      # override restored


def test_legacy_reset_drops_bus_kind():
    # The consistency contract under resets: reset_dispatch_events drops
    # the bus's dispatch events too, so the views can never desync.
    with config.override(telemetry=True):
        conv.conv2d(_x(), _w(), SPEC, "bp_phase")
        obs.events.emit("train", "marker")
        conv.reset_dispatch_events()
        assert obs.events.counters("dispatch") == {} == \
            conv.dispatch_events()
        assert [e["name"] for e in obs.events.events()] == ["marker"]
        assert obs.report()["consistent"]


def test_report_flags_divergence():
    with config.override(telemetry=True):
        obs.events.emit("dispatch", "forward:ghost")   # bus-only event
        rep = obs.report()
        assert not rep["consistent"]
        assert any("ghost" in d for d in rep["divergences"])


def test_unknown_kind_raises_when_enabled():
    with config.override(telemetry=True):
        with pytest.raises(ValueError, match="unregistered event kind"):
            obs.events.emit("nope", "x")


def test_bus_overflow_is_counted_not_silent(monkeypatch):
    monkeypatch.setattr(obs.events, "MAX_EVENTS", 3)
    with config.override(telemetry=True):
        for i in range(5):
            obs.events.emit("train", f"e{i}")
        assert len(obs.events.events()) == 3
        assert obs.events.dropped() == 2
        rep = obs.report()
        assert rep["events_dropped"] == 2
        # Saturated bus: the divergence check is skipped, not failed.
        assert rep["consistent"]


# ---------------------------------------------------------------------------
# Spans: nesting, annotations, Perfetto export
# ---------------------------------------------------------------------------

def test_trace_export_validates(tmp_path):
    out = tmp_path / "trace.json"
    with config.override(telemetry=True, trace_path=str(out)):
        with obs.trace.span("outer", step=0):
            with obs.trace.span("inner"):
                conv.conv2d(_x(), _w(), SPEC, "bp_phase")
        assert obs.trace.export() == str(out)
    doc = json.loads(out.read_text())
    from scripts.validate_trace import validate_trace
    problems, stats = validate_trace(doc)
    assert problems == []
    assert "outer" in stats["b_names"] and "inner" in stats["b_names"]
    conv_spans = [n for n in stats["b_names"] if n.startswith("conv:")]
    assert conv_spans, stats["b_names"]
    assert doc["otherData"]["producer"] == "repro.obs.trace"


def test_conv_span_annotations():
    d = conv.spec_dims((2, 3, 16, 16), (8, 3, 3, 3), SPEC)
    ann = obs.trace.conv_annotations(d)
    assert ann["taps"] == {"real": 9, "materialized": 9}
    assert ann["skip_ratio"] == 0.0
    assert ann["bytes_moved"] > 0
    # Dilated case: the tap table runs 9 real taps of a materialized 25.
    dd = conv.spec_dims((1, 3, 16, 16), (8, 3, 3, 3),
                        ConvSpec.make(stride=2, padding=2, dilation=2))
    ann = obs.trace.conv_annotations(dd)
    assert ann["taps"] == {"real": 9, "materialized": 25}
    assert ann["skip_ratio"] == round(1 - 9 / 25, 6)


def test_transposed_span_skip_ratio_matches_tap_counts():
    from repro.core.convspec import ConvTransposeSpec
    tspec = ConvTransposeSpec.make(stride=2, padding=1, output_padding=1)
    d = conv.transpose_dims((1, 8, 8, 8), (8, 4, 3, 3), tspec)
    taps = conv.transpose_tap_counts(d)
    ann = obs.trace.conv_annotations(d, transposed=True)
    assert ann["taps"]["real"] == taps["real"]
    assert ann["taps"]["materialized"] == taps["zero_inserted"]


def test_validate_trace_rejects_broken_nesting():
    from scripts.validate_trace import validate_trace
    lane = {"pid": 1, "tid": 1}
    bad = {"traceEvents": [
        {"name": "a", "ph": "B", "ts": 1.0, **lane},
        {"name": "b", "ph": "B", "ts": 2.0, **lane},
        {"name": "a", "ph": "E", "ts": 3.0, **lane},   # crosses "b"
    ]}
    problems, _ = validate_trace(bad)
    assert any("must nest" in p for p in problems)
    assert any("left open" in p for p in problems)


# ---------------------------------------------------------------------------
# Metrics stream
# ---------------------------------------------------------------------------

def test_metrics_train_step_lines(tmp_path):
    out = tmp_path / "m.jsonl"
    with config.override(telemetry=True, metrics_path=str(out)):
        conv.conv2d(_x(), _w(), SPEC, "bp_phase")
        obs.metrics.train_step(0, {"loss": 1.5, "grad_norm": 0.2},
                               step_s=0.01)
        obs.metrics.train_step(1, {"loss": 1.2})
        assert obs.metrics.lines_written() == 2
    lines = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert [ln["step"] for ln in lines] == [0, 1]
    assert all(ln["kind"] == "train_step" and "ts" in ln for ln in lines)
    assert lines[0]["loss"] == 1.5 and lines[0]["step_s"] == 0.01
    assert lines[0]["dispatch_mix"].get("bp_phase", 0) > 0
    assert "plan_cache_hit_rate" in lines[0]


def test_metrics_serve_tick(tmp_path):
    class _Stub:
        engine_kind = "static"
        max_batch = 4
        counters = {"decode_steps": 5, "completed": 2, "timed_out": 1,
                    "failed": 0}
        stats = {"lane_steps": 12, "tokens": 20, "decode_s": 0.5}

    out = tmp_path / "m.jsonl"
    with config.override(telemetry=True, metrics_path=str(out)):
        for lat in (0.1, 0.2, 0.3):
            obs.metrics.record_latency(lat)
        obs.metrics.serve_tick(_Stub())
    line = json.loads(out.read_text().splitlines()[0])
    assert line["kind"] == "serve_tick"
    assert line["engine"] == "static"
    assert line["occupancy"] == round(12 / (5 * 4), 4)
    assert line["decode_tok_s"] == round(20 / 0.5, 2)
    assert line["p50_s"] == 0.2 and line["p99_s"] == 0.3
    assert line["timed_out"] == 1


# ---------------------------------------------------------------------------
# Shared serve summary vocabulary
# ---------------------------------------------------------------------------

def test_merged_summary_keys_align_across_engines():
    static = merged_summary("static", {"completed": 1, "waves": 2,
                                       "decode_steps": 3},
                            {"prefill_s": 0.12345678, "tokens": 7})
    cont = merged_summary("continuous", {"completed": 1, "admitted": 2,
                                         "inserts": 2, "decode_steps": 3},
                          {"prefill_s": 0.2, "tokens": 7})
    assert set(static) == set(cont)               # directly diffable
    for key in SUMMARY_COUNTERS:
        assert key in static and key in cont
    assert static["inserts"] == 0 and cont["waves"] == 0  # 0, not absent
    assert static["engine_kind"] == "static"
    assert static["prefill_s"] == 0.123457        # floats rounded


# ---------------------------------------------------------------------------
# The covering reset + the docs gate
# ---------------------------------------------------------------------------

def test_reset_all_covers_every_surface():
    from repro.ft import inject
    with config.override(telemetry=True, fault_spec="grad.values:nan",
                         fault_seed=0):
        conv.conv2d(_x(), _w(), SPEC, "pallas")
        inject.fault_point("grad.values", value=_w())   # fires a nan rule
        assert conv.dispatch_events() and inject.fired_events()
        assert obs.events.events("fault")
        obs.reset_all()
        assert conv.dispatch_events() == {}
        assert inject.fired_events() == []
        assert obs.events.events() == [] and obs.events.dropped() == 0


def test_docs_taxonomy_matches_registry():
    import scripts.check_obs_events as chk
    assert chk.main(["check_obs_events"]) == 0
