"""ResNet v1.5 (``repro.models.resnet``) against its plain float32
reference (``repro.models.resnet_ref``) on seeded random weights.

The small networks keep the published structure -- 7x7/2 stem, BN, 3x3/2
max pool, bottlenecks with the stride on the 3x3 conv and projected first
blocks, global pool and fc -- at an eighth of the widths, 32x32 images and
batch 4.  At batch 2 the last stage's BN (a 1x1 plane) would normalize
over two values, where the float32 reference itself lies 2e-3 from a
float64 evaluation; at batch 4 it lies at most 5.4e-4 from it.  Policy ``lax`` runs
XLA's conv autodiff; ``auto`` sends the strided convs to the tap-GEMM
kernels (interpreted on the CPU) and the stride-1 convs to the dense path.
"""

import dataclasses
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import resnet as R
from repro.models import resnet_ref as RR
from repro.optim import adamw
from repro.train import train_step as TS

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from bench import reference as BR  # noqa: E402

#: the published network's parameters, fc bias included (He et al. Table 1;
#: torchvision ``resnet50``).
RESNET50_PARAMS = 25_557_032

#: logits, element by element (max |p - r| / max |r|): each passes through
#: 17+ convs and BNs of a few hundred products each, ~1e-6 of float32
#: rounding; 1e-4 leaves room for the BN amplification below.
LOGITS_TOL = 1e-4
#: loss, relative: a mean of 4 log-softmaxes of those logits.
LOSS_TOL = 1e-5
#: each gradient leaf (max |g - r| / max |r|): the backward pass through
#: BN subtracts the batch means of products, which cancels to a few digits
#: where the plane is small (1x1 in the last stage, 4 values per channel).
#: On these draws the float32 reference itself lies up to 5.4e-4 from a
#: float64 evaluation (and the program up to 3.8e-4), so about twice that.
GRAD_TOL = 1e-3


def _small(stages=(1, 1, 1, 1), policy="lax") -> R.ResNetConfig:
    return R.ResNetConfig(stem_width=8, widths=(8, 16, 32, 64),
                          stages=stages, num_classes=10, conv_policy=policy)


def _data(cfg, seed=0, batch=4, size=32):
    kp, kx, ky = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = R.init_resnet(kp, cfg)
    # Seeded BN affine parameters away from 1 and 0, as the benchmark's.
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    params = jax.tree.unflatten(tree, [
        a + 0.1 * jax.random.normal(jax.random.fold_in(kp, n), a.shape)
        if getattr(path[-1], "key", "") in ("gamma", "beta") else a
        for n, (path, a) in enumerate(flat)])
    batch = {"image": jax.random.normal(kx, (batch, R.IMAGE_CHANNELS,
                                             size, size)),
             "label": jax.random.randint(ky, (batch,), 0, cfg.num_classes)}
    return params, batch


def _leaf_gaps(got, want):
    return {jax.tree_util.keystr(k): float(jnp.max(jnp.abs(g - w))
                                           / jnp.max(jnp.abs(w)))
            for (k, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                 jax.tree.leaves(want))}


@pytest.mark.parametrize("policy", ["lax", "auto"])
@pytest.mark.parametrize("stages", [(1, 1, 1, 1), (2, 1, 1, 1)],
                         ids=["projected", "identity_block"])
def test_matches_reference(stages, policy):
    cfg = _small(stages, policy)
    params, batch = _data(cfg)
    logits = jax.jit(lambda p: R.resnet_apply(p, batch["image"], cfg))(params)
    want_logits = jax.jit(lambda p: RR.apply(p, batch["image"]))(params)
    gap = float(jnp.max(jnp.abs(logits - want_logits))
                / jnp.max(jnp.abs(want_logits)))
    assert gap < LOGITS_TOL, gap

    compiled = jax.jit(jax.value_and_grad(
        lambda p: R.resnet_loss(p, batch, cfg), has_aux=True)
    ).lower(params).compile()
    (loss, _), grads = compiled(params)
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: RR.loss(p, batch)))(params)
    assert abs(float(loss) - float(want_loss)) < LOSS_TOL * float(want_loss)
    gaps = _leaf_gaps(grads, want_grads)
    assert len(gaps) == len(jax.tree.leaves(want_grads))
    worst = max(gaps, key=gaps.get)
    assert gaps[worst] < GRAD_TOL, (worst, gaps[worst])
    _check_scopes(compiled.as_text())


def _check_scopes(hlo: str):
    """The norm, pool and head scopes name device ops of the compiled
    step, forward and backward, as the profiler later sees them."""
    from bench import norm, scopes
    from repro.obs.trace import HEAD_SCOPE, NORM_SCOPE, POOL_SCOPE
    paths = scopes.from_hlo_text(hlo).values()
    tokens = [set(re.split(r"[/();]", p)) for p in paths]
    for scope in (NORM_SCOPE, POOL_SCOPE, HEAD_SCOPE):
        assert any(scope in t for t in tokens), scope
    assert any("transpose" in p and NORM_SCOPE in p for p in paths)
    assert any(norm.is_norm(p) for p in paths)


def test_one_train_step_matches_reference_adamw():
    """``make_train_step`` with ``resnet_loss`` against the reference's
    gradient and the AdamW written out in ``bench.reference``.  ``eps`` is
    raised from 1e-8 so that the first update, otherwise the sign of each
    gradient element, is smooth in the gradient: an element that is zero to
    rounding would flip."""
    cfg = _small()
    params, batch = _data(cfg, seed=1)
    opt = {"peak_lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-3,
           "weight_decay": 0.1, "clip_norm": 1.0, "warmup": 1,
           "total_steps": 100, "final_frac": 0.1}
    step = TS.make_train_step(
        cfg, adamw.AdamWConfig(peak_lr=opt["peak_lr"], b1=opt["b1"],
                               b2=opt["b2"], eps=opt["eps"],
                               weight_decay=opt["weight_decay"],
                               clip_norm=opt["clip_norm"]),
        total_steps=opt["total_steps"], warmup=opt["warmup"],
        schedule_name="cosine", loss=R.resnet_loss)
    new, _, metrics = jax.jit(step)(params, adamw.init_state(params), batch,
                                    0)
    grads = jax.jit(jax.grad(lambda p: RR.loss(p, batch)))(params)
    want, _ = BR.adamw_step(params, grads, BR.adamw_init(params), 1, opt)
    assert np.isfinite(float(metrics["loss"]))
    moved = jax.tree.map(lambda a, b: a - b, new, params)
    want_moved = jax.tree.map(lambda a, b: a - b, want, params)
    gaps = _leaf_gaps(moved, want_moved)
    worst = max(gaps, key=gaps.get)
    assert gaps[worst] < GRAD_TOL, (worst, gaps[worst])


def test_published_network_counts():
    """ResNet-50 v1.5 at its published size, from shapes alone: the
    parameter count, 53 convs each with its BN, and 16 blocks, each ending
    in a residual add."""
    cfg = R.ResNetConfig(conv_policy="lax")
    shapes = jax.eval_shape(lambda: R.init_resnet(jax.random.PRNGKey(0),
                                                   cfg))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree.leaves(shapes)) == RESNET50_PARAMS
    assert [len(s) for s in shapes["stages"]] == [3, 4, 6, 3]
    assert shapes["fc"]["w"].shape == (2048, 1000)
    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    convs = [p for p in paths if p.endswith("['w']") and "fc" not in p]
    norms = [p for p in paths if p.endswith("['gamma']")]
    assert len(convs) == len(norms) == 53
    assert len(list(R.blocks(cfg))) == 16
    x = jax.ShapeDtypeStruct((2, 3, 224, 224), jnp.float32)
    out = jax.eval_shape(lambda p, x: R.resnet_apply(p, x, cfg), shapes, x)
    assert out.shape == (2, 1000)


def test_strides_sit_on_the_3x3_conv():
    """v1.5: a downsampling block strides its 3x3 conv and projection,
    never its first 1x1 conv."""
    cfg = R.ResNetConfig()
    strided = [(s, i) for s, i, _, _, stride, _ in R.blocks(cfg)
               if stride == 2]
    assert strided == [(1, 0), (2, 0), (3, 0)]
    assert [i for _, i, *_, projected in R.blocks(cfg) if projected] == \
        [0, 0, 0, 0]


def test_max_pool_and_bn_match_definitions():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 9, 9))
    pooled = R.max_pool(x)
    assert pooled.shape == (2, 3, 5, 5)
    padded = np.pad(np.asarray(x), ((0, 0), (0, 0), (1, 1), (1, 1)),
                    constant_values=-np.inf)
    want = np.array([[[[padded[b, c, 2 * i:2 * i + 3, 2 * j:2 * j + 3].max()
                        for j in range(5)] for i in range(5)]
                      for c in range(3)] for b in range(2)])
    np.testing.assert_array_equal(np.asarray(pooled), want)
    p = {"gamma": jnp.array([1.0, 2.0, 0.5]), "beta": jnp.array([0., 1., -1.])}
    y = np.asarray(R.batch_norm(p, x, 1e-5))
    xn = np.asarray(x, np.float64)
    mu = xn.mean((0, 2, 3), keepdims=True)
    var = xn.var((0, 2, 3), keepdims=True)          # biased
    want = ((xn - mu) / np.sqrt(var + 1e-5) * np.array([1, 2, .5])[:, None,
                                                                    None]
            + np.array([0, 1, -1])[:, None, None])
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)


def test_config_is_a_train_step_model():
    cfg = dataclasses.replace(_small(), conv_mode="bp_phase")
    assert cfg.conv_engine_policy == "bp_phase"
    assert {"name", "conv_policy", "conv_mode"} <= {
        f.name for f in dataclasses.fields(R.ResNetConfig)}

