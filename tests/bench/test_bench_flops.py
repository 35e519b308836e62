"""The benchmark's operation counts, peaks table and BENCHMARK.json, checked
against hand counts and the contract's shape, without a device."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops, spec  # noqa: E402
from bench.flops import Conv  # noqa: E402

CONFIG = json.loads((ROOT / "bench/configs/resnet50_convs.json").read_text())


def _layers(name, batch=1):
    return [Conv(batch, c, h, n, k, s, p)
            for h, c, n, k, s, p in CONFIG["layer_sets"][name]]


def test_forward_macs_per_sample_match_hand_counts():
    # stem 64*112^2*3*49; three 1x1 s2 convs 102.76M; three 3x3 s2 115.61M
    assert sum(l.macs() for l in _layers("strided")) == 773_111_808
    # the 3x3 conv of each stage: 4 x 115,605,504
    assert sum(l.macs() for l in _layers("stride1")) == 462_422_016


@pytest.mark.parametrize("config,traffic,gflop", [
    ("resnet50_convs", "strided_layers", 140.89),
    ("resnet50_convs", "stride1_layers", 88.78),
    ("conv_autoencoder", "train_steps", 31.26)])
def test_step_flops_of_each_cell(config, traffic, gflop):
    """Forward and weight grad of every layer, input grad where needed
    (not for a layer whose input is the image)."""
    config = json.loads((ROOT / f"bench/configs/{config}.json").read_text())
    traffic = json.loads((ROOT / f"bench/traffic/{traffic}.json")
                         .read_text())
    kind = spec.step_kind(traffic["step"]).build(config, traffic, "auto")
    total = sum(flops.pass_flops(conv) for _, conv, _, needed in
                kind.passes() if needed)
    assert total / 1e9 == pytest.approx(gflop, abs=0.01)


def test_transposed_conv_counts_its_mirror():
    t = Conv(8, 128, 64, 64, 3, 2, 1, transposed=True, out_pad=1)
    assert t.H_o == 128
    mirror = Conv(8, 64, 128, 128, 3, 2, 1)
    assert t.macs() == mirror.macs()
    assert t.elems() == mirror.elems()


def test_least_time_is_the_larger_bound():
    peak = spec.peaks("TPU v5 lite")
    stem = _layers("strided", batch=32)[0]
    compute = flops.pass_flops(stem) / 197e12
    memory = flops.pass_bytes(stem) / 819e9
    assert memory > compute      # the stem pass is bound by HBM
    assert flops.least_seconds(stem, peak) == memory
    assert flops.pass_bytes(stem) == 4 * (32 * 3 * 224 ** 2 + 64 * 3 * 49
                                          + 32 * 64 * 112 ** 2)


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no peaks"):
        spec.peaks("TPU v99")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_names_files_that_exist():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"] for c in bench["configs"]}
    used = {w["config"] for w in bench["workloads"]}
    assert used == configs
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        cell = spec.load_cell(w["name"])
        assert cell.limits and cell.end_to_end and cell.per_layer
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                        "step_ms"}
    for m in bench["per_layer"]:
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for e in bench["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25
