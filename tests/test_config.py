"""repro.config: the unified runtime-configuration surface.

Covers the api_redesign contract: env-var precedence at init, frozen
attribute surface, validated update()/override() scoping, plan-cache
invalidation on plan-affecting changes, the post-import env-mutation
deprecation shim, and the legacy module-constant aliases
(ops.INTERPRET / ops.VMEM_BUDGET_BYTES, mamba2.CHUNK,
attention.BLOCKWISE_KV_THRESHOLD, transformer.SCAN_UNROLL).
"""

import os
import warnings

import pytest

import repro
from repro.core.config import FIELDS, GlobalConfig, config
from repro.core.im2col_ref import ConvDims
from repro.kernels import ops

D = ConvDims(B=1, C=4, H_i=8, W_i=8, N=4, K_h=3, K_w=3, S=2, P_h=1, P_w=1)


@pytest.fixture(autouse=True)
def _restore_config():
    saved = config.snapshot()
    yield
    config.update(**saved)


# ---------------------------------------------------------------------------
# Construction / env precedence
# ---------------------------------------------------------------------------

def test_defaults_without_env():
    c = GlobalConfig(env={})
    assert c.interpret is True
    assert c.vmem_budget_bytes == 14 * 1024 * 1024
    assert c.remat is None
    assert c.ssd_chunk == 128 and c.blockwise_kv_threshold == 1024
    assert c.scan_unroll == 1


def test_env_initialization_wins_over_defaults():
    c = GlobalConfig(env={"BPIM2COL_INTERPRET": "0",
                          "REPRO_VMEM_BUDGET_BYTES": "1048576",
                          "REPRO_SSD_CHUNK": "64",
                          "REPRO_REMAT": "block"})
    assert c.interpret is False
    assert c.vmem_budget_bytes == 1 << 20
    assert c.ssd_chunk == 64
    assert c.remat == "block"


@pytest.mark.parametrize("raw,expect", [
    ("1", True), ("true", True), ("yes", True), ("", True),
    ("0", False), ("false", False), ("no", False), ("off", False),
    ("FALSE", False), ("Off", False),
])
def test_interpret_env_parsing_matches_historical_rule(raw, expect):
    assert GlobalConfig(env={"BPIM2COL_INTERPRET": raw}).interpret is expect


def test_interpret_resolves_from_the_backend_on_first_read(monkeypatch):
    """No env var: the field stays unresolved until read, then follows the
    backend -- the CPU interprets, a TPU compiles."""
    import jax
    assert FIELDS["interpret"].default is None
    c = GlobalConfig(env={})
    assert c._values["interpret"] is None          # nothing asked at init
    assert c.interpret is (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert GlobalConfig(env={}).interpret is False


def test_interpreting_on_a_tpu_backend_raises(monkeypatch):
    import jax
    from repro.kernels import tap_gemm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="interpret mode on a TPU"):
        tap_gemm.resolve_interpret(True)
    assert tap_gemm.resolve_interpret(False) is False


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir_is_fixed_or_the_env(monkeypatch, env_dir):
    """Entry points set the checkout's fixed cache dir only when
    JAX_COMPILATION_CACHE_DIR is unset; jax reads the variable itself.
    Either way the key covers op metadata (the named scopes)."""
    import jax
    from repro.core import compile_cache
    set_calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: set_calls.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    got = compile_cache.enable_compile_cache()
    metadata = ("jax_compilation_cache_include_metadata_in_key", True)
    if env_dir is None:
        assert got == compile_cache.CHECKOUT_CACHE_DIR
        assert got.endswith(os.path.join(".cache", "jax"))
        assert set_calls == [("jax_compilation_cache_dir", got), metadata]
    else:
        assert got == env_dir and set_calls == [metadata]


def test_repro_config_is_the_singleton():
    assert repro.config is config


def test_snapshot_is_a_plain_copy():
    snap = config.snapshot()
    assert set(snap) == set(FIELDS)
    snap["vmem_budget_bytes"] = -1          # mutating the copy changes
    assert config.vmem_budget_bytes != -1   # nothing


# ---------------------------------------------------------------------------
# Frozen surface + validation
# ---------------------------------------------------------------------------

def test_direct_assignment_raises():
    with pytest.raises(AttributeError, match="frozen"):
        config.vmem_budget_bytes = 1


def test_unknown_field_read_and_update_raise():
    with pytest.raises(AttributeError, match="no field"):
        config.not_a_field
    with pytest.raises(ValueError, match="unknown config field"):
        config.update(not_a_field=1)


@pytest.mark.parametrize("kw", [
    {"vmem_budget_bytes": "big"},
    {"interpret": "yes"},
    {"ssd_chunk": 0},
    {"blockwise_kv_threshold": -1},
    {"scan_unroll": 0},
    {"remat": 7},
    {"fault_seed": 1.5},
    {"telemetry": "yes"},
    {"trace_path": 7},
])
def test_update_validates(kw):
    with pytest.raises(ValueError):
        config.update(**kw)


# ---------------------------------------------------------------------------
# update() / override() semantics
# ---------------------------------------------------------------------------

def test_override_scopes_and_restores_on_exception():
    before = config.vmem_budget_bytes
    chunk = config.ssd_chunk
    with config.override(vmem_budget_bytes=1 << 20, ssd_chunk=chunk + 1):
        assert config.vmem_budget_bytes == 1 << 20
        assert config.ssd_chunk == chunk + 1
    assert config.vmem_budget_bytes == before
    assert config.ssd_chunk == chunk
    with pytest.raises(RuntimeError):
        with config.override(vmem_budget_bytes=1 << 20):
            raise RuntimeError("boom")
    assert config.vmem_budget_bytes == before


def test_update_invalidates_plan_cache_on_budget_change():
    ops.forward_plan(D)
    assert ops.tile_plan_cache_info()["forward_plan"].currsize >= 1
    config.update(vmem_budget_bytes=config.vmem_budget_bytes + 1)
    assert ops.tile_plan_cache_info()["forward_plan"].currsize == 0


def test_update_same_value_does_not_invalidate():
    ops.forward_plan(D)
    size = ops.tile_plan_cache_info()["forward_plan"].currsize
    assert size >= 1
    config.update(vmem_budget_bytes=config.vmem_budget_bytes)
    assert ops.tile_plan_cache_info()["forward_plan"].currsize == size


def test_non_plan_field_update_does_not_invalidate():
    ops.forward_plan(D)
    size = ops.tile_plan_cache_info()["forward_plan"].currsize
    config.update(ssd_chunk=64)
    assert ops.tile_plan_cache_info()["forward_plan"].currsize == size


# ---------------------------------------------------------------------------
# Post-import env mutation: deprecated but working
# ---------------------------------------------------------------------------

def test_env_mutation_after_init_warns_and_applies():
    env = {"REPRO_SSD_CHUNK": "128"}
    c = GlobalConfig(env=env)
    assert c.ssd_chunk == 128
    env["REPRO_SSD_CHUNK"] = "256"
    with pytest.warns(DeprecationWarning, match="REPRO_SSD_CHUNK"):
        assert c.ssd_chunk == 256
    with warnings.catch_warnings():         # adopted: no repeat warning
        warnings.simplefilter("error")
        assert c.ssd_chunk == 256


def test_env_deletion_after_init_restores_default():
    env = {"REPRO_SCAN_UNROLL": "4"}
    c = GlobalConfig(env=env)
    assert c.scan_unroll == 4
    del env["REPRO_SCAN_UNROLL"]
    with pytest.warns(DeprecationWarning):
        assert c.scan_unroll == 1


def test_update_supersedes_stale_env():
    """An explicit update() wins over the env var it absorbed -- the next
    read must not 'restore' the stale env value."""
    env = {"REPRO_SSD_CHUNK": "64"}
    c = GlobalConfig(env=env)
    c.update(ssd_chunk=32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert c.ssd_chunk == 32


# ---------------------------------------------------------------------------
# Legacy module-constant aliases
# ---------------------------------------------------------------------------

def test_ops_legacy_globals_read_through_config():
    assert ops.INTERPRET == config.interpret
    assert ops.VMEM_BUDGET_BYTES == config.vmem_budget_bytes
    with config.override(vmem_budget_bytes=1 << 20):
        assert ops.VMEM_BUDGET_BYTES == 1 << 20


def test_ops_legacy_global_write_warns_and_forwards():
    old = config.vmem_budget_bytes
    with pytest.warns(DeprecationWarning, match="VMEM_BUDGET_BYTES"):
        ops.VMEM_BUDGET_BYTES = 1 << 20
    assert config.vmem_budget_bytes == 1 << 20
    with pytest.warns(DeprecationWarning, match="INTERPRET"):
        ops.INTERPRET = config.interpret
    config.update(vmem_budget_bytes=old)


def test_model_constants_are_config_lookups():
    from repro.models import attention, mamba2, transformer
    with config.override(ssd_chunk=64, scan_unroll=8,
                         blockwise_kv_threshold=2048):
        assert mamba2.CHUNK == 64
        assert transformer.SCAN_UNROLL == 8
        assert attention.BLOCKWISE_KV_THRESHOLD == 2048
    assert mamba2.CHUNK == config.ssd_chunk


def test_unknown_module_attr_still_raises():
    from repro.models import mamba2
    with pytest.raises(AttributeError):
        mamba2.NOT_A_CONSTANT
    with pytest.raises(AttributeError):
        ops.NOT_A_CONSTANT
