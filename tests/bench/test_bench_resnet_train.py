"""The ``resnet50_train`` step builder, its reference and the comparison,
at a tiny size on the CPU, with the cell's own traffic and limits.

Each test drives the harness's ``run_cell``.  A sound run, under the cell's
own policy ``auto`` as under ``lax``, has to come out correct; a run with
the timed path broken underneath, and the control (the reference one
precision below, in the program's place), have to come out not correct.
``faults.altered_answers`` cannot break this step: every conv feeds a BN,
which cancels a scaled conv output up to rounding, so it has no test here.
A fault in the weight-grad pass alone is planted here instead, to show
where the cell's limits stop seeing it.

The tiny network has the published depth (stages 3, 4, 6, 3) at an
eighth of the widths, 10 classes, 64x64 images and batch 8.  At this size
a sound run reads about 1e-2 on ``grad`` and the control is caught by its
logits.  Smaller planes make the stem's BN degenerate: where every
max-pool output of a channel is positive, a shift of that channel passes
through the pool and the next BNs remove it, so its ``beta`` gradient is
zero to rounding.
"""

import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import compare, faults, harness, spec  # noqa: E402

SEED = 2 ** 33 + 7          # wider than 32 bits
CELL = "resnet50_train.b32"

TINY = {"image_size": 64, "batch": 8, "num_classes": 10, "stem_width": 8,
        "widths": [8, 16, 32, 64], "stages": [3, 4, 6, 3]}


def _cell(policy: str) -> spec.Cell:
    """The tiny network with the traffic and limits of the benchmark's
    cell."""
    full = spec.load_cell(CELL)
    return spec.Cell(f"tiny_{CELL}", dict(full.config, **TINY),
                     dict(full.traffic, policy=policy), 1, full.limits,
                     full.end_to_end, ())


def _run(policy: str = "lax") -> dict:
    return harness.run_cell(_cell(policy), SEED, 0.05, False,
                            time.perf_counter(), log=lambda *_: None)


@pytest.mark.parametrize("policy", ["auto", "lax"])
def test_sound_run_is_correct(policy):
    result = _run(policy)
    assert result["correct"], result["checks"]
    assert set(result["checks"]) == {"fwd", "grad", "grad_proj", "update"}
    assert set(result["metrics"]) == {"setup_s", "step_ms", "peak_hbm_mib"}
    assert result["attempted"] > harness.CHECKED_STEPS
    json.dumps(result)


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_broken_step_is_not_correct(fault, monkeypatch):
    build, plant = harness.build, getattr(faults, fault)

    def planted(*args):
        kind = build(*args)
        kind.program = plant(kind.program)
        return kind

    monkeypatch.setattr(harness, "build", planted)
    result = _run()
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("factor, caught", [(1.1, True), (1.01, False)])
def test_weight_grad_fault(factor, caught, monkeypatch):
    """Every conv's weight-grad pass, and no other pass, returns its output
    times ``factor``.  The global-norm clip then passes the scale on to
    the other leaves, which read about ``factor - 1`` on ``grad``; AdamW
    divides a leaf's scale out of its update, and the forward pass is
    untouched.  So ``grad`` alone can see the fault: at 10% it does, and
    at 1% it lies under the limit, which has to leave room for the BN
    parameters' own float32 spread (PERF.md, section 7)."""
    from repro.core import conv as C
    run_wgrad = C._run_wgrad
    monkeypatch.setattr(C, "_run_wgrad",
                        lambda *a: run_wgrad(*a) * factor)
    result = _run()
    checks = result["checks"]
    assert checks["grad"]["value"] >= 0.9 * (factor - 1), checks
    assert result["correct"] is not caught, checks
    assert all(c["value"] <= c["limit"] for k, c in checks.items()
               if k != "grad"), checks


def test_control_is_not_correct():
    """The reference at three bfloat16 passes, in the program's place."""
    cell = _cell("lax")
    kind = harness.build(cell)
    ref = harness.reference(kind, cell, SEED)
    control = harness.reference(kind, cell, SEED, mode="high")
    correct, checks = compare.verdict(compare.numbers(control, ref),
                                      cell.limits)
    assert not correct, checks


def test_passes_list_every_conv():
    """53 convs at the published size, three passes each; only the stem's
    input gradient is not needed."""
    kind = harness.build(spec.load_cell(CELL))
    passes = kind.passes()
    assert len(passes) == 3 * 53
    unneeded = [conv for _, conv, p, needed in passes if not needed]
    assert unneeded == [kind.stem] and kind.stem.C == 3
    assert sum(conv.K == 1 and conv.S == 1 for _, conv, p, _ in passes
               if p == "forward") == 33
