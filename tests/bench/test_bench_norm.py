"""The batch-norm scope as ``bench/norm.py`` reads it, and the
``norm_busy`` reader on a synthetic op-name map and trace."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import norm, scopes, spec  # noqa: E402
from bench import trace as T  # noqa: E402



def test_scope_name_is_the_programs():
    from repro.obs.trace import NORM_SCOPE
    assert norm.NORM == NORM_SCOPE


@pytest.mark.parametrize("path, want", [
    ("jit(step)/jvp(batch_norm)/sub", True),
    ("jit(step)/transpose(jvp(batch_norm))/reduce_sum", True),
    ("jit(step)/transpose(jvp(batch_norm))/max", True),
    ("jit(step)/x/copy;jit(step)/jvp(batch_norm)/mul", True),
    ("jit(step)/transpose(jvp(conv_weight_grad))/glue/pad", False),
    ("jit(step)/jvp(conv_forward);jit(step)/jvp(batch_norm)/add", False),
    ("jit(step)/jvp(batch_norms)/add", False),
    ("jit(step)/transpose(jvp(max_pool))/select_and_scatter_add", False),
    ("jit(step)/head/dot_general", False),
    ("", False),
])
def test_is_norm(path, want):
    assert norm.is_norm(path) is want


def _ctx(scope_map):
    """Two steps in a 10 ms window: 3 ms of BN, 5 ms of conv, 1 ms idle,
    1 ms of an op with no op_name."""
    ms = 10 ** 6
    events = {"device": [[0, "fusion.1", 0, 2 * ms],
                         [0, "fusion.2", 2 * ms, 1 * ms],
                         [0, "tap_gemm.3", 3 * ms, 5 * ms],
                         [0, "copy.4", 9 * ms, 1 * ms]],
              "host": [["bench:window", 0, 10 * ms]]}
    return {"view": T.TraceView(events, chips=1), "steps": 2,
            "scopes": scope_map}


SCOPES = {"fusion.1": "jit(step)/jvp(batch_norm)/mul",
          "fusion.2": "jit(step)/transpose(jvp(batch_norm))/reduce_sum",
          "tap_gemm.3": "jit(step)/jvp(conv_forward)/tap_gemm/pallas_call"}


def test_reader_on_a_synthetic_trace():
    ctx = _ctx(SCOPES)
    assert norm.seconds(ctx["view"], SCOPES) == pytest.approx(3e-3)
    busy = spec.metric_reader("norm_busy")(ctx)
    assert busy == pytest.approx(100 * 3 / 9)
    # The norm and the conv passes never share an op.
    assert scopes.seconds(ctx["view"], SCOPES)["forward"] == \
        pytest.approx(5e-3)


def test_reader_reports_nothing_without_the_scope():
    """The parent program has no ``batch_norm`` scope: no number, and no
    zero."""
    ctx = _ctx({"tap_gemm.3": SCOPES["tap_gemm.3"]})
    assert spec.metric_reader("norm_busy")(ctx) is None


def test_benchmark_lists_the_reader_for_the_resnet_cell():
    cell = spec.load_cell("resnet50_train.b32")
    names = {m["name"] for m in cell.per_layer}
    assert {"norm_busy", "mfu", "idle_share", "forward_roofline",
            "input_grad_roofline", "weight_grad_roofline",
            "glue_busy"} <= names
    for other in ("resnet50_s2.b32", "autoencoder_256.b8"):
        assert "norm_busy" not in {m["name"]
                                   for m in spec.load_cell(other).per_layer}
