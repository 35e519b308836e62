"""A whole ResNet v1.5 through the program's own train step
(``make_train_step`` with softmax cross-entropy and AdamW).

The program is ``repro.models.resnet``: every conv through the program's
conv dispatch, BatchNorm in training mode, the stem's max pool and the fc
head.  The weights are the benchmark's, in the program's tree: conv and fc
weights (and the fc bias) N(0, 1/fan_in), BN ``gamma`` 1 + 0.1 N(0, 1) and
``beta`` 0.1 N(0, 1).  ``bench.gen`` draws floats only, so each batch's
``label`` is a float draw that the step turns into a class index: its
bits, as an unsigned integer, modulo the number of classes -- exact on
any backend, so the reference reads the same labels.

The reference below is the same network written out with
``bench.reference``'s convs (``mode`` sets their precision) and its AdamW;
it imports nothing of the program.  The loss plugin is the program's
``resnet_loss`` written out, so that it also returns the logits, which are
compared element by element for the first step.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import compare
from bench import reference as R
from bench.flops import PASSES, Conv

#: the scale of the seeded part of BN's ``gamma`` (around 1) and ``beta``.
BN_SCALE = 0.1


def labels(draw, classes: int):
    """Class indices from a float32 draw: its bits modulo ``classes``."""
    return (lax.bitcast_convert_type(draw, jnp.uint32)
            % jnp.uint32(classes)).astype(jnp.int32)


def _loss(params, batch, cfg):
    """``resnet_loss`` that also returns the logits."""
    from repro.models import resnet as M
    logits = M.resnet_apply(params, batch["image"], cfg)
    loss = M.cross_entropy(logits, batch["label"])
    return loss, {"loss": loss, "logits": logits}


def _bn_tree(c: int):
    return {"gamma": ((c,), BN_SCALE), "beta": ((c,), BN_SCALE)}


def _conv_tree(l: Conv):
    return {"w": ((l.N, l.C, l.K, l.K), (l.C * l.K * l.K) ** -0.5)}


def with_gamma_around_one(weights):
    """The drawn tree with 1 added to every BN ``gamma``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a + 1 if getattr(path[-1], "key", None) == "gamma"
        else a, weights)


class ResNetTrain:
    def __init__(self, config: dict, traffic: dict, policy: str):
        from repro.models import resnet as M
        c = config
        self.cfg = c
        self.opt = traffic["optimizer"]
        self.model = M.ResNetConfig(
            num_classes=c["num_classes"], stem_width=c["stem_width"],
            stages=tuple(c["stages"]), widths=tuple(c["widths"]),
            conv_policy=policy)
        b, h = c["batch"], c["image_size"]
        self.stem = Conv(b, c["image_channels"], h, c["stem_width"], 7, 2, 3)
        h = (self.stem.H_o - 1) // 2 + 1            # the 3x3/2 max pool
        # Per block: (conv1, conv2, conv3, projection or None), the stride
        # on the 3x3 conv and the projection (v1.5).
        self.blocks = [[] for _ in c["stages"]]
        c_in = c["stem_width"]
        for s, (n, width) in enumerate(zip(c["stages"], c["widths"])):
            c_out = width * c["expansion"]
            for i in range(n):
                stride = 2 if s > 0 and i == 0 else 1
                conv2 = Conv(b, width, h, width, 3, stride, 1)
                self.blocks[s].append((
                    Conv(b, c_in, h, width, 1, 1, 0), conv2,
                    Conv(b, width, conv2.H_o, c_out, 1, 1, 0),
                    Conv(b, c_in, h, c_out, 1, stride, 0) if i == 0
                    else None))
                h, c_in = conv2.H_o, c_out
        self.features = c_in
        self._step = None

    def convs(self) -> list[Conv]:
        """Every conv of the network, the stem first."""
        return [self.stem] + [l for stage in self.blocks for block in stage
                              for l in block if l is not None]

    def weights_tree(self):
        stages = []
        for stage in self.blocks:
            out = []
            for conv1, conv2, conv3, proj in stage:
                b = {"conv1": _conv_tree(conv1), "bn1": _bn_tree(conv1.N),
                     "conv2": _conv_tree(conv2), "bn2": _bn_tree(conv2.N),
                     "conv3": _conv_tree(conv3), "bn3": _bn_tree(conv3.N)}
                if proj is not None:
                    b["proj"] = _conv_tree(proj)
                    b["proj_bn"] = _bn_tree(proj.N)
                out.append(b)
            stages.append(out)
        f, k = self.features, self.cfg["num_classes"]
        return {"stem": {"conv": _conv_tree(self.stem),
                         "bn": _bn_tree(self.stem.N)},
                "stages": stages,
                "fc": {"w": ((f, k), f ** -0.5), "b": ((k,), f ** -0.5)}}

    def batch_tree(self):
        c = self.cfg
        return {"image": ((c["batch"], c["image_channels"], c["image_size"],
                           c["image_size"]), 1.0),
                "label": ((c["batch"],), 1.0)}

    def _train_step(self):
        from repro.optim import adamw
        from repro.train import train_step as TS
        o = self.opt
        return TS.make_train_step(
            self.model, adamw.AdamWConfig(
                peak_lr=o["peak_lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"], clip_norm=o["clip_norm"]),
            total_steps=o["total_steps"], warmup=o["warmup"],
            schedule_name=o["schedule"], loss=_loss)

    def init_state(self, weights):
        from repro.optim import adamw
        params = with_gamma_around_one(weights)
        return params, adamw.init_state(params)

    def program(self, state, batch, i):
        if self._step is None:
            self._step = self._train_step()
        batch = {"image": batch["image"],
                 "label": labels(batch["label"], self.cfg["num_classes"])}
        params, opt, metrics = self._step(state[0], state[1], batch, i)
        return (params, opt), {"loss": metrics["loss"][None],
                               "logits": metrics["logits"]}

    def observe(self, states, outs):
        (p0, _), (_, o1), (p3, _) = states
        f64 = lambda t: [np.asarray(a, np.float64) for a in jax.tree.leaves(t)]
        grad = [m / (1 - self.opt["b1"]) for m in f64(o1["m"])]
        return {
            "loss": np.stack([o["loss"] for o in outs]),
            "grad": compare.norms(grad),
            "gproj": compare.project(grad),
            "change": compare.norms([a - b for a, b in
                                     zip(f64(p3), f64(p0))]),
            # Step 1's only: AdamW's first update is the sign of each
            # gradient element, so later steps' logits move by more than
            # rounding.
            "y": [outs[0]["logits"]],
        }

    def reference(self, weights, batches, mode: str):
        params = with_gamma_around_one(jax.tree.map(jnp.asarray, weights))
        opt = R.adamw_init(params)
        states, outs = [(params, opt)], []
        k = self.cfg["num_classes"]
        for t, batch in enumerate(batches, start=1):
            y = labels(jnp.asarray(batch["label"]), k)
            (loss, logits), grads = _ref_loss_grad(
                params, batch["image"], y, eps=self.cfg["bn_eps"], mode=mode)
            params, opt = R.adamw_step(params, grads, opt, t, self.opt)
            outs.append({"loss": np.asarray(loss)[None],
                         "logits": np.asarray(logits)})
            states.append((params, opt))
        host = lambda s: jax.tree.map(np.asarray, s)
        return self.observe((host(states[0]), host(states[1]),
                             host(states[3])), outs)

    def passes(self):
        out = []
        for l in self.convs():
            key = (l.B, l.C, l.H, l.H, l.N, l.K, l.K, l.S, l.S)
            for p in PASSES:
                needed = p != "input_grad" or l is not self.stem
                out.append(((p, False, key), l, p, needed))
        return out


# ---------------------------------------------------------------------------
# The reference network
# ---------------------------------------------------------------------------

def _batch_norm(p, x, eps: float):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=(0, 2, 3), keepdims=True)
    y = (x - mean) / jnp.sqrt(var + eps)
    return y * p["gamma"][None, :, None, None] + p["beta"][None, :, None, None]


def apply(params, x, eps: float, mode: str):
    """The reference ResNet's logits of ``x``: every conv and the fc at
    ``mode`` (``bench.reference.bilinear``)."""
    def conv(h, q, S, P):
        return R.bilinear(partial(R.conv, S=S, P=P), mode)(h, q["w"])

    fc = R.bilinear(partial(jnp.dot, precision=lax.Precision.HIGHEST), mode)
    stem = params["stem"]
    h = jax.nn.relu(_batch_norm(stem["bn"], conv(x, stem["conv"], 2, 3), eps))
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          ((0, 0), (0, 0), (1, 1), (1, 1)))
    for s, stage in enumerate(params["stages"]):
        for i, p in enumerate(stage):
            stride = 2 if s > 0 and i == 0 else 1
            y = jax.nn.relu(_batch_norm(p["bn1"], conv(h, p["conv1"], 1, 0),
                                        eps))
            y = jax.nn.relu(_batch_norm(p["bn2"],
                                        conv(y, p["conv2"], stride, 1), eps))
            y = _batch_norm(p["bn3"], conv(y, p["conv3"], 1, 0), eps)
            if "proj" in p:
                h = _batch_norm(p["proj_bn"], conv(h, p["proj"], stride, 0),
                                eps)
            h = jax.nn.relu(y + h)
    return fc(jnp.mean(h, axis=(2, 3)), params["fc"]["w"]) + params["fc"]["b"]


@partial(jax.jit, static_argnames=("eps", "mode"))
def _ref_loss_grad(params, x, y, *, eps, mode):
    def loss(params):
        logits = apply(params, x, eps, mode)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1)), \
            logits
    return jax.value_and_grad(loss, has_aux=True)(params)


def build(config: dict, traffic: dict, policy: str) -> ResNetTrain:
    return ResNetTrain(config, traffic, policy)
