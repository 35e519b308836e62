"""The cells' step builders, the reference and the comparison, at tiny
shapes on the CPU.

Each test drives the harness's ``run_cell`` -- everything of a run after
the look for a chip -- with the cell's own limits.  A sound run has to come
out correct; a run with the timed path broken underneath, and the control
(the reference one precision below, in the program's place), have to come
out not correct.
"""

import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import compare, faults, harness, spec  # noqa: E402

SEED = 2 ** 33 + 5          # wider than 32 bits

LAYERS = {"precision": "highest", "image_size": 16, "image_channels": 3,
          "batch": 4, "layer_sets": {"tiny": [[16, 3, 8, 3, 2, 1],
                                              [8, 8, 16, 3, 2, 1]]}}
AUTOENCODER = {"precision": "highest", "image_size": 16,
               "image_channels": 3, "channels_per_level": [4, 8, 16],
               "levels": 2, "kernel": 3, "stride": 2, "padding": 1,
               "output_padding": 1, "batch": 2}


#: the benchmark's own traffic and limits that each tiny cell borrows.
FILES = {"layers": ("strided_layers", "resnet50_s2.b32"),
         "autoencoder": ("train_steps", "autoencoder_256.b8")}


def _cell(which: str, policy: str) -> spec.Cell:
    """A tiny cell with the traffic and limits of the benchmark's own."""
    traffic_name, limits_name = FILES[which]
    read = lambda path: json.loads((spec.BENCH / path).read_text())
    traffic = dict(read(f"traffic/{traffic_name}.json"), policy=policy)
    if which == "layers":
        traffic["layer_set"] = "tiny"
    config = LAYERS if which == "layers" else AUTOENCODER
    end_to_end = json.loads((ROOT / "BENCHMARK.json").read_text())[
        "end_to_end"]
    return spec.Cell(f"tiny_{which}", config, traffic, 1,
                     read(f"checks/{limits_name}.json")["limits"],
                     tuple(end_to_end), ())


def _run(which: str, policy: str = "lax") -> dict:
    return harness.run_cell(_cell(which, policy), SEED, 0.05, False,
                            time.perf_counter(), log=lambda *_: None)


def test_sound_run_on_the_kernels_is_correct():
    result = _run("layers", policy="auto")
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"setup_s", "step_ms", "peak_hbm_mib"}
    assert result["attempted"] > harness.CHECKED_STEPS
    json.dumps(result)


@pytest.mark.parametrize("which", ["layers", "autoencoder"])
def test_sound_run_is_correct(which):
    result = _run(which)
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("which", ["layers", "autoencoder"])
@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_answer"])
def test_broken_step_is_not_correct(which, fault, monkeypatch):
    if fault == "altered_answer":
        with faults.altered_answers():
            result = _run(which)
    else:
        build, plant = harness.build, getattr(faults, fault)

        def planted(*args):
            kind = build(*args)
            kind.program = plant(kind.program)
            return kind

        monkeypatch.setattr(harness, "build", planted)
        result = _run(which)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("which", ["layers", "autoencoder"])
def test_control_is_not_correct(which):
    """The reference at three bfloat16 passes, in the program's place."""
    cell = _cell(which, "lax")
    kind = harness.build(cell)
    ref = harness.reference(kind, cell, SEED)
    control = harness.reference(kind, cell, SEED, mode="high")
    correct, checks = compare.verdict(compare.numbers(control, ref),
                                      cell.limits)
    assert not correct, checks
