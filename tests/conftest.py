import os
import sys

# Tests run on the host's single CPU device (the dry-run sets its own 512-
# device flag in a separate process; never set it here).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(__file__))

try:  # real hypothesis when available (CI installs it via the dev extra)
    import hypothesis  # noqa: F401
except ImportError:  # minimal env: deterministic replay stub
    import types

    import _hypothesis_stub as _stub

    _mod = types.ModuleType("hypothesis")
    _mod.given = _stub.given
    _mod.settings = _stub.settings
    _mod.strategies = _stub.strategies
    _mod.IS_STUB = True
    sys.modules["hypothesis"] = _mod
    sys.modules["hypothesis.strategies"] = _stub.strategies

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.RandomState(0)


@pytest.fixture(autouse=True)
def _clean_introspection():
    """Every test starts with clean introspection state everywhere -- the
    legacy counters (dispatch/plan/fault/checkpoint) and the
    obs bus/trace/metrics window -- via the one covering reset."""
    from repro import obs
    obs.reset_all()
    yield
