"""ResNet v1.5 image classifier trained through ``make_train_step``.

He et al., "Deep Residual Learning for Image Recognition" (arXiv:1512.03385,
Table 1), with the stride of each downsampling bottleneck on its 3x3 conv
(v1.5, as ``torchvision.models.resnet50``):

- stem: 7x7/2 conv, BN, ReLU, 3x3/2 max pool (padding 1);
- stages of bottleneck blocks: 1x1 -> 3x3 -> 1x1 convs, BN after each,
  ReLU after the first two; the first block of a stage adds a 1x1
  projection with BN on the shortcut; ReLU after the residual add;
- head: global average pool, fc with bias, softmax cross-entropy.

Every conv goes through ``layers.conv2d_apply``, so the per-pass engine
policy, the tile planner and lane packing apply to it as to any other conv.
BatchNorm runs in training mode: batch statistics over (B, H, W), biased
variance, learnable scale ``gamma`` and shift ``beta``; running statistics
are not tracked (they only feed evaluation).

Each BN, with the residual add and the ReLU that follow it, runs under the
named scope ``trace.NORM_SCOPE``; the max pool under ``POOL_SCOPE``; global
pool, fc and loss under ``HEAD_SCOPE``.

``ResNetConfig`` carries ``name`` / ``conv_policy`` / ``conv_mode`` like
``AutoencoderConfig``, so ``make_train_step(cfg, ..., loss=resnet_loss)``
trains it.  ``repro.models.resnet_ref`` is the plain float32 reference.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.obs.trace import HEAD_SCOPE, NORM_SCOPE, POOL_SCOPE


#: RGB images in, float32 parameters.
IMAGE_CHANNELS = 3
#: a bottleneck's output is this many times its inner width.
EXPANSION = 4
BN_EPS = 1e-5


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    """ResNet-50 v1.5 by default.  ``widths`` are the bottlenecks' inner
    widths per stage; a block's output is ``EXPANSION`` times wider."""

    name: str = "resnet50"
    num_classes: int = 1000
    stem_width: int = 64
    stages: tuple[int, ...] = (3, 4, 6, 3)
    widths: tuple[int, ...] = (64, 128, 256, 512)
    conv_policy: str = "auto"
    conv_mode: Optional[str] = None

    @property
    def conv_engine_policy(self) -> str:
        if self.conv_mode is not None:
            return self.conv_mode
        return self.conv_policy


def blocks(cfg: ResNetConfig):
    """``(stage, index, c_in, width, stride, projected)`` of every
    bottleneck, in order."""
    c_in = cfg.stem_width
    for s, (n, width) in enumerate(zip(cfg.stages, cfg.widths)):
        for i in range(n):
            stride = 2 if s > 0 and i == 0 else 1
            yield s, i, c_in, width, stride, i == 0
            c_in = width * EXPANSION


def _init_bn(c: int):
    return {"gamma": jnp.ones((c,)), "beta": jnp.zeros((c,))}


def init_resnet(key, cfg: ResNetConfig):
    """Float32 conv and fc weights N(0, 1/fan_in), BN gamma 1 and beta 0,
    fc bias 0.  Tree: ``{"stem": {"conv", "bn"}, "stages": [[block, ...], ...],
    "fc": {"w": (features, classes), "b"}}``; a block holds ``conv1..3``,
    ``bn1..3`` and, when projected, ``proj`` and ``proj_bn``."""
    dt = jnp.float32
    todo = list(blocks(cfg))
    keys = iter(jax.random.split(key, 2 + 4 * len(todo)))
    params = {"stem": {"conv": L.init_conv2d(next(keys), IMAGE_CHANNELS,
                                             cfg.stem_width, 7, dt),
                       "bn": _init_bn(cfg.stem_width)},
              "stages": [[] for _ in cfg.stages]}
    for s, _, c_in, width, _, projected in todo:
        c_out = width * EXPANSION
        b = {"conv1": L.init_conv2d(next(keys), c_in, width, 1, dt),
             "bn1": _init_bn(width),
             "conv2": L.init_conv2d(next(keys), width, width, 3, dt),
             "bn2": _init_bn(width),
             "conv3": L.init_conv2d(next(keys), width, c_out, 1, dt),
             "bn3": _init_bn(c_out)}
        if projected:
            b["proj"] = L.init_conv2d(next(keys), c_in, c_out, 1, dt)
            b["proj_bn"] = _init_bn(c_out)
        params["stages"][s].append(b)
    features = cfg.widths[-1] * EXPANSION
    params["fc"] = {"w": jax.random.normal(next(keys), (features,
                                                        cfg.num_classes))
                    * features ** -0.5,
                    "b": jnp.zeros((cfg.num_classes,))}
    return params


def batch_norm(p, x, eps: float = BN_EPS):
    """Training-mode BN of x (B, C, H, W): statistics over (B, H, W),
    biased variance."""
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    return y * p["gamma"][None, :, None, None] + p["beta"][None, :, None, None]


def _norm(p, x, relu: bool = True, residual=None):
    """BN, then the residual add and the ReLU where given, in the norm
    scope."""
    with jax.named_scope(NORM_SCOPE):
        y = batch_norm(p, x)
        if residual is not None:
            y = y + residual
        return jax.nn.relu(y) if relu else y


def max_pool(x):
    """3x3/2 max pool with padding 1 (the stem's)."""
    with jax.named_scope(POOL_SCOPE):
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                                 (1, 1, 2, 2),
                                 ((0, 0), (0, 0), (1, 1), (1, 1)))


def _bottleneck(p, x, stride: int, policy):
    conv = lambda q, h, s=1, pad=0: L.conv2d_apply(
        q, h, stride=s, padding=pad, policy=policy)
    h = _norm(p["bn1"], conv(p["conv1"], x))
    h = _norm(p["bn2"], conv(p["conv2"], h, stride, 1))
    shortcut = x
    if "proj" in p:
        shortcut = _norm(p["proj_bn"], conv(p["proj"], x, stride),
                         relu=False)
    return _norm(p["bn3"], conv(p["conv3"], h), residual=shortcut)


def resnet_apply(params, x, cfg: ResNetConfig):
    """x (B, C, H, W) -> logits (B, classes), every conv under the
    config's engine policy."""
    policy = cfg.conv_engine_policy
    stem = params["stem"]
    h = L.conv2d_apply(stem["conv"], x, stride=2, padding=3, policy=policy)
    h = max_pool(_norm(stem["bn"], h))
    for (*_, stride, _), p in zip(
            blocks(cfg), (b for stage in params["stages"] for b in stage)):
        h = _bottleneck(p, h, stride, policy)
    with jax.named_scope(HEAD_SCOPE):
        feats = jnp.mean(h, axis=(2, 3))
        return feats @ params["fc"]["w"] + params["fc"]["b"]


def cross_entropy(logits, labels):
    """Mean softmax cross-entropy of integer ``labels``."""
    with jax.named_scope(HEAD_SCOPE):
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - picked)


def resnet_loss(params, batch, cfg: ResNetConfig):
    """Mean softmax cross-entropy of ``batch["image"]`` against
    ``batch["label"]`` -- the ``loss=`` plugin for ``make_train_step``."""
    logits = resnet_apply(params, batch["image"], cfg)
    loss = cross_entropy(logits.astype(jnp.float32), batch["label"])
    return loss, {"loss": loss}
