"""The trace reduction and the per-layer metric readers, on a synthetic
trace with known answers."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402
from bench import trace as T  # noqa: E402
from bench.flops import Conv, least_seconds, pass_flops  # noqa: E402

MS = 1_000_000  # ns

# A 10 ms window holding two 4 ms steps.  Each step: a kernel (2 ms),
# then a fusion (1 ms) that overlaps a copy; the host dispatches, then
# waits.  One op straddles the window's start and one lies outside it.
EVENTS = {
    "device": [
        [0, "before_window", -2 * MS, 3 * MS],          # clipped to 1 ms
        [0, "jvp_tap_gemm_.2", 2 * MS, 2 * MS],
        [0, "fusion.3", 4 * MS, 1 * MS],
        [0, "copy.1", 4.5 * MS, 1 * MS],               # overlaps fusion.3
        [0, "transpose_jvp_tap_wgrad__.4", 6 * MS, 2 * MS],
        [0, "fusion.3", 8 * MS, 1 * MS],
        [0, "after_window", 11 * MS, 1 * MS],
        [1, "jvp_tap_gemm_.2", 2 * MS, 2 * MS],         # a chip not used
    ],
    "host": [
        ["bench:window", 0, 10 * MS],
        ["bench:dispatch", 0.2 * MS, 1.5 * MS],
        ["bench:wait", 9 * MS, 1 * MS],
    ],
}


@pytest.fixture
def view():
    return T.TraceView(EVENTS, chips=1)


def test_window_and_busy_union(view):
    assert view.window_s == pytest.approx(0.010)
    # [0,1] + [2,5.5] + [6,9] ms = 7.5 ms; overlap counted once
    assert view.busy_s == pytest.approx(0.0075)


def test_op_seconds_match_kernel_names(view):
    assert view.op_seconds(("tap_gemm", "tap_wgrad")) == pytest.approx(
        0.004)
    top = dict(view.top_ops(3))
    assert set(top) == {"jvp_tap_gemm_.2", "fusion.3",
                        "transpose_jvp_tap_wgrad__.4"}
    assert all(s == pytest.approx(0.002) for s in top.values())


def test_idle_gaps_are_labelled_by_host_span(view):
    # gaps: [9, 10] ms under the wait, [1, 2] under the dispatch,
    # [5.5, 6] under the window alone; longest first
    assert view.idle_gaps() == [["bench:wait", pytest.approx(0.001)],
                                ["bench:dispatch", pytest.approx(0.001)],
                                ["bench:window", pytest.approx(0.0005)]]


def test_op_name_keeps_the_instruction():
    line = "%jvp_tap_gemm_.2 = f32[8,128]{1,0} custom-call(%pad.1)"
    assert T.op_name(line) == "jvp_tap_gemm_.2"
    assert T.op_name("fusion.3") == "fusion.3"


def test_window_annotation_is_required():
    with pytest.raises(ValueError, match="bench:window"):
        T.TraceView({"device": [], "host": []}, chips=1)


def _ctx(view, engine="pallas"):
    conv = Conv(2, 3, 16, 8, 3, 2, 1)
    passes = [(None, conv, "forward", True, engine),
              (None, conv, "input_grad", False, engine),
              (None, conv, "weight_grad", True, engine)]
    return {"view": view, "steps": 2, "passes": passes,
            "peak": spec.peaks("TPU v5 lite")}, conv


def test_metric_readers(view):
    ctx, conv = _ctx(view)
    read = spec.metric_reader
    assert read("idle_share")(ctx) == pytest.approx(25.0)
    assert read("tap_gemm_busy")(ctx) == pytest.approx(100 * 4 / 7.5)
    step_s = 0.010 / 2
    assert read("mfu")(ctx) == pytest.approx(
        100 * 2 * pass_flops(conv) / (step_s * 197e12))
    least = 2 * least_seconds(conv, ctx["peak"])
    assert read("tap_gemm_roofline")(ctx) == pytest.approx(
        100 * least * 2 / 0.004)


def test_kernel_readers_return_nothing_without_kernels():
    events = {"device": [[0, "convolution.1", 0, MS]],
              "host": [["bench:window", 0, 2 * MS]]}
    ctx, _ = _ctx(T.TraceView(events, chips=1), engine="bp_phase")
    assert spec.metric_reader("tap_gemm_roofline")(ctx) is None
    assert spec.metric_reader("tap_gemm_busy")(ctx) is None
    assert spec.metric_reader("idle_share")(ctx) == pytest.approx(50.0)


def test_recorded_chip_trace():
    """Five autoencoder steps traced on a TPU v5 lite: every one of the 11
    kernel launches of a step is found, and the shares are in range."""
    import json
    events = json.loads((ROOT / "tests/bench/data/autoencoder_trace.json")
                        .read_text())
    view = T.TraceView(events, chips=1)
    kernels = [name for _, name, _, _ in view.ops
               if "tap_gemm" in name or "tap_wgrad" in name]
    assert len(set(kernels)) == 11 and len(kernels) == 5 * 11
    assert view.window_s == pytest.approx(0.0905863, rel=1e-5)
    assert view.busy_s == pytest.approx(0.0874943, rel=1e-5)
    assert 0 < view.op_seconds(("tap_gemm", "tap_wgrad")) < view.busy_s
    assert view.idle_gaps(1)[0][0] == "bench:wait"
