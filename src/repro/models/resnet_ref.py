"""Plain float32 reference of ``repro.models.resnet``: ResNet v1.5 in
straightforward ``jax.numpy`` and ``lax.conv_general_dilated``, with no
conv engines, planner or kernels, every matmul and conv at
``Precision.HIGHEST`` under ``jax.default_matmul_precision("highest")``.

It takes the program's parameter tree (``resnet.init_resnet``), so the two
can be compared leaf by leaf.  The stride of a block sits on its 3x3 conv
and its projection: the first block of every stage after the first.

Departures from the paper (He et al., arXiv:1512.03385):

- v1.5 stride placement (torchvision ``resnet50``): the paper strides the
  first 1x1 conv of a downsampling block;
- BatchNorm in training mode only, from the batch's statistics (biased
  variance, eps 1e-5); no running statistics, which only feed
  evaluation;
- the loss is the mean softmax cross-entropy alone: no weight decay term
  (the optimizer applies it) and no label smoothing or augmentation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_DN = ("NCHW", "OIHW", "NCHW")
_HI = lax.Precision.HIGHEST
EPS = 1e-5


def conv(x, w, stride: int, pad: int):
    return lax.conv_general_dilated(x, w, (stride, stride),
                                    [(pad, pad), (pad, pad)],
                                    dimension_numbers=_DN, precision=_HI)


def batch_norm(p, x):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=(0, 2, 3), keepdims=True)
    y = (x - mean) / jnp.sqrt(var + EPS)
    return y * p["gamma"][None, :, None, None] + p["beta"][None, :, None, None]


def max_pool(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                             (1, 1, 2, 2), ((0, 0), (0, 0), (1, 1), (1, 1)))


def bottleneck(p, x, stride: int):
    h = jax.nn.relu(batch_norm(p["bn1"], conv(x, p["conv1"]["w"], 1, 0)))
    h = jax.nn.relu(batch_norm(p["bn2"], conv(h, p["conv2"]["w"], stride, 1)))
    h = batch_norm(p["bn3"], conv(h, p["conv3"]["w"], 1, 0))
    if "proj" in p:
        x = batch_norm(p["proj_bn"], conv(x, p["proj"]["w"], stride, 0))
    return jax.nn.relu(h + x)


def apply(params, x):
    """x (B, C, H, W) -> logits (B, classes)."""
    with jax.default_matmul_precision("highest"):
        stem = params["stem"]
        h = jax.nn.relu(batch_norm(stem["bn"],
                                   conv(x, stem["conv"]["w"], 2, 3)))
        h = max_pool(h)
        for s, stage in enumerate(params["stages"]):
            for i, p in enumerate(stage):
                h = bottleneck(p, h, 2 if s > 0 and i == 0 else 1)
        feats = jnp.mean(h, axis=(2, 3))
        return jnp.dot(feats, params["fc"]["w"], precision=_HI) \
            + params["fc"]["b"]


def loss(params, batch):
    """Mean softmax cross-entropy of ``batch["image"]`` against the integer
    ``batch["label"]``."""
    logits = apply(params, batch["image"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["label"][:, None],
                                         axis=-1))
