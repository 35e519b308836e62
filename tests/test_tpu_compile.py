"""Compile rehearsal of the tap-GEMM kernels for a TPU v5e.

Each case lowers one conv pass -- ``tap_gemm`` (forward), ``tap_gemm_phased``
(fused input grad) or ``tap_wgrad`` (weight grad) -- through the public
``ops`` wrappers with ``interpret=False`` and compiles it with Mosaic for a
described (not attached) v5e chip, at the published widths of the paper's
Table II layers and of the 7x7 ImageNet stem.  Mosaic refuses here what
interpret mode accepts: unaligned windows, sub-tile channel slices, VMEM
over the limit.  Nothing runs, so this says nothing about results or time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and each
test worker imports every test file.
"""

import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import paper_cnn
from repro.core.config import config
from repro.kernels import ops

#: (H_i, C, N, K, S, P): Table II, then the 7x7 stride-2 stem of
#: ResNet/DenseNet at 224x224.
LAYERS = [*paper_cnn.TABLE2_LAYERS, (224, 3, 64, 7, 2, 3)]

#: seconds one case may take, compilation included.
COMPILE_LIMIT_S = 30.0


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # The TPU compiler otherwise writes its logs outside the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache off meanwhile.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _pass(role: str, d):
    """(fn, operand shapes) of one pass of layer ``d``."""
    x = (d.B, d.C, d.H_i, d.W_i)
    w = (d.N, d.C, d.K_h, d.K_w)
    dy = (d.B, d.N, d.H_o, d.W_o)
    if role == "forward":
        return (lambda a, b: ops.conv2d_forward(a, b, d)), (x, w)
    if role == "input_grad":
        return (lambda a, b: ops.conv2d_input_grad(a, b, d)), (dy, w)
    return (lambda a, b: ops.conv2d_weight_grad(a, b, d)), (x, dy)


def _plan_fits(role: str, d) -> bool:
    if role == "input_grad":
        return ops.input_grad_plan(d) is not None
    return {"forward": ops.forward_plan,
            "weight_grad": ops.weight_grad_plan}[role](d).fits


@pytest.mark.parametrize("role", ["forward", "input_grad", "weight_grad"])
@pytest.mark.parametrize("layer", LAYERS,
                         ids=[f"{h}x{h}-C{c}-N{n}-K{k}-S{s}"
                              for h, c, n, k, s, _ in LAYERS])
def test_tap_kernel_compiles_for_v5e(layer, role, one_chip):
    d = paper_cnn.dims(layer)
    fn, shapes = _pass(role, d)
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    with config.override(interpret=False):
        assert _plan_fits(role, d), (role, layer)
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        elapsed = time.perf_counter() - t0
    assert "tpu_custom_call" in compiled.as_text(), (role, layer)
    assert elapsed < COMPILE_LIMIT_S, (role, layer, elapsed)
