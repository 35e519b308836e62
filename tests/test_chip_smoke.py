"""``chip_smoke.py``: refuses to run without a TPU, and its phases' checks
pass at tiny sizes on the CPU (interpret mode).

The chip run itself is the script's job; these tests keep its control flow
and its checks honest between chip runs.
"""

import os
import subprocess
import sys

import pytest

import chip_smoke

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_table2_phase_checks_pass_at_tiny_size():
    res = chip_smoke.phase_table2([(16, 3, 8, 3, 2, 0), (12, 8, 8, 3, 2, 1)],
                                  batch=2, seed=0, reps=1)
    for row in res["layers"]:
        assert row["dispatch"] == {p: 1 for p in chip_smoke.PASSES}, row
        assert max(row["rel_err"].values()) <= chip_smoke.LAYER_TOL, row


def test_autoencoder_phase_checks_pass_at_tiny_size():
    res = chip_smoke.phase_autoencoder((8, 16), hw=16, batch=4, steps=2,
                                       seed=0)
    assert res["loss_rel_diff"] <= chip_smoke.LOSS_RTOL
    assert set(res["dispatch"]) == {
        *chip_smoke.PASSES, *(f"{p}_T" for p in chip_smoke.PASSES)}


def test_trainer_phase_checks_pass_at_tiny_size(monkeypatch):
    from repro.launch import train
    # Leave this worker's compilation cache as the suite configured it.
    monkeypatch.setattr(train, "enable_compile_cache", lambda: None)
    res = chip_smoke.phase_trainer("mamba2-370m", batch=2, seq=128, steps=2,
                                   smoke=True)
    assert len(res["losses"]) == len(res["grad_norms"]) == 2


def test_a_failed_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure, match="only pallas"):
        chip_smoke._check_dispatch({"forward:pallas": 1,
                                    "forward:bp_phase": 1},
                                   ("forward",), "case")
