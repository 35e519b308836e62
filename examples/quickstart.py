"""Quickstart: the paper's BP-im2col in 60 seconds.

    PYTHONPATH=src python examples/quickstart.py

Walks through:
  1. a strided conv layer's backprop zero-space problem (sparsity numbers),
  2. Algorithm 1/2 implicit address mapping == explicit zero-spaced lowering,
  3. gradients from the implicit engines == jax.grad ground truth,
  4. the traffic/bandwidth savings the paper reports.
"""

import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bpim2col as bp
from repro.core.compile_cache import enable_compile_cache
from repro.core import im2col_ref as ref
from repro.core import phase_decomp as ph
from repro.core.im2col_ref import ConvDims

enable_compile_cache()

# A conv layer from the paper's Table II (scaled-down channels for CPU).
d = ConvDims(B=2, C=8, H_i=28, W_i=28, N=16, K_h=3, K_w=3, S=2, P_h=1, P_w=1)
print(f"layer: H={d.H_i} C={d.C} N={d.N} K={d.K_h} S={d.S} P={d.P_h}"
      f" -> H_o={d.H_o}")

# 1. the zero-space problem
print(f"\nzero-spaced loss map: {d.H_o}x{d.W_o} -> {d.H_o3}x{d.W_o3} "
      f"({d.zero_space_sparsity_loss():.1%} zeros)")
print(f"lowered matrix B sparsity (loss calc):  "
      f"{bp.lowered_sparsity_loss(d):.1%}  <- paper: 75%..93.91%")
print(f"zero-inserted dY sparsity (grad calc):  "
      f"{bp.lowered_sparsity_grad(d):.1%}  <- paper: 74.8%..93.6%")

# 2. Algorithm 1: implicit gather == explicit zero-spaced lowering
rng = np.random.RandomState(0)
dy = jnp.asarray(rng.randn(d.B, d.N, d.H_o, d.W_o), jnp.float32)
implicit = bp.gather_lowered_B_loss(dy, d)
explicit = ref.im2col(ref.zero_insert_pad(dy, d), d.K_h, d.K_w, 1).T
np.testing.assert_allclose(implicit, explicit, rtol=1e-6)
print("\nAlgorithm 1 implicit lowering == explicit zero-spaced lowering  OK")

# 3. gradients match jax.grad exactly
x = jnp.asarray(rng.randn(d.B, d.C, d.H_i, d.W_i), jnp.float32)
w = jnp.asarray(rng.randn(d.N, d.C, d.K_h, d.K_w), jnp.float32)
di_ref, dw_ref = ref.conv_grads_lax(x, w, dy, d)
np.testing.assert_allclose(bp.input_grad_implicit(dy, w, d), di_ref,
                           rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(ph.weight_grad_phase(x, dy, d), dw_ref,
                           rtol=2e-3, atol=2e-3)
print("BP-im2col gradients == jax.grad                                  OK")

# 4. traffic savings
t = ref.reorg_traffic_elems_loss(d)
o = bp.bp_traffic_elems_loss(d)
print(f"\ntraditional: reorg {t['reorg_read']+t['reorg_write']:,} elems, "
      f"off-chip stream {t['offchip_stream']:,}, "
      f"buffer stream {t['buffer_stream']:,}")
print(f"BP-im2col:   reorg 0 elems, off-chip stream {o['offchip_stream']:,},"
      f" buffer stream {o['buffer_stream']:,}")
print(f"buffer-bandwidth reduction: "
      f"{1 - o['buffer_stream']/t['buffer_stream']:.1%} "
      f"(paper: >= 70.6%)")
print(f"extra backprop storage eliminated: {t['extra_storage']:,} elems "
      f"(paper: >= 74.78% reduction)")
