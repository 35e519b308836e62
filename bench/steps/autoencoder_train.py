"""The conv -> transposed-conv autoencoder through the program's own train
step (``make_train_step`` with the reconstruction loss and AdamW).

Encoder level i: conv, stride 2, then ReLU.  Decoder, mirrored: transposed
conv, stride 2, ReLU after every one but the last.  The loss is the mean
squared error between the reconstruction and the image.  The weights are
the benchmark's, in the program's layout: ``{"enc": [{"w": (C_out, C_in,
K, K)}], "dec": [{"w": (C_in, C_out, K, K)}]}``.  The loss plugin is the
program's ``autoencoder_loss`` written out, so that it also returns the
reconstruction, which is compared element by element for the first step.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare
from bench import reference as R
from bench.flops import PASSES, Conv

def _loss(params, batch, cfg):
    """``autoencoder_loss`` that also returns the reconstruction."""
    from repro.models import model as M
    x = batch["image"]
    x_hat = M.autoencoder_apply(params, x, cfg)
    mse = jnp.mean(jnp.square(x_hat - x))
    return mse, {"mse": mse, "loss": mse, "x_hat": x_hat}


class AutoencoderTrain:
    def __init__(self, config: dict, traffic: dict, policy: str):
        self.cfg = config
        self.opt = traffic["optimizer"]
        self.policy = policy
        c = config
        self.chans = (c["image_channels"],
                      *c["channels_per_level"][:c["levels"]])
        self.k, self.S = c["kernel"], c["stride"]
        self.P, self.out_pad = c["padding"], c["output_padding"]
        b, h = c["batch"], c["image_size"]
        self.enc, self.dec = [], []
        for i in range(len(self.chans) - 1):
            self.enc.append(Conv(b, self.chans[i], h, self.chans[i + 1],
                                 self.k, self.S, self.P))
            h = self.enc[-1].H_o
        for i in reversed(range(len(self.chans) - 1)):
            self.dec.append(Conv(b, self.chans[i + 1], h, self.chans[i],
                                 self.k, self.S, self.P, transposed=True,
                                 out_pad=self.out_pad))
            h = self.dec[-1].H_o
        self._step = None

    def weights_tree(self):
        fan = lambda l: (l.C * l.K * l.K) ** -0.5
        return {"enc": [{"w": ((l.N, l.C, l.K, l.K), fan(l))}
                        for l in self.enc],
                "dec": [{"w": ((l.C, l.N, l.K, l.K), fan(l))}
                        for l in self.dec]}

    def batch_tree(self):
        c = self.cfg
        return {"image": ((c["batch"], c["image_channels"], c["image_size"],
                           c["image_size"]), 1.0)}

    def _train_step(self):
        from repro.models import model as M
        from repro.optim import adamw
        from repro.train import train_step as TS
        o = self.opt
        model = M.AutoencoderConfig(
            c_in=self.chans[0], widths=tuple(self.chans[1:]), k=self.k,
            conv_policy=self.policy)
        return TS.make_train_step(
            model, adamw.AdamWConfig(
                peak_lr=o["peak_lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"], clip_norm=o["clip_norm"]),
            total_steps=o["total_steps"], warmup=o["warmup"],
            schedule_name=o["schedule"], loss=_loss)

    def init_state(self, weights):
        from repro.optim import adamw
        return weights, adamw.init_state(weights)

    def program(self, state, batch, i):
        if self._step is None:
            self._step = self._train_step()
        params, opt, metrics = self._step(state[0], state[1], batch, i)
        return (params, opt), {"loss": metrics["loss"][None],
                               "x_hat": metrics["x_hat"]}

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
            # gradient element, so an element that is zero to rounding
            # flips, and the later reconstructions move by ~1e-4.
            "y": [outs[0]["x_hat"]],
        }

    def reference(self, weights, batches, mode: str):
        params = jax.tree.map(jnp.asarray, weights)
        opt = R.adamw_init(params)
        states, outs = [(params, opt)], []
        geom = (self.S, self.P, self.out_pad)
        for t, batch in enumerate(batches, start=1):
            x = batch["image"]
            (loss, x_hat), grads = _ref_loss_grad(params, x, geom=geom,
                                                  mode=mode)
            params, opt = R.adamw_step(params, grads, opt, t, self.opt)
            outs.append({"loss": np.asarray(loss)[None],
                         "x_hat": np.asarray(x_hat)})
            states.append((params, opt))
        host = lambda s: jax.tree.map(np.asarray, s)
        return self.observe((host(states[0]), host(states[1]),
                             host(states[3])), outs)

    def passes(self):
        out = []
        for l in self.enc:
            key = (l.B, l.C, l.H, l.H, l.N, l.K, l.K, l.S, l.S)
            for p in PASSES:
                needed = p != "input_grad" or l is not self.enc[0]
                out.append(((p, False, key), l, p, needed))
        for l in self.dec:
            # The program keys a transposed conv by its mirror regular
            # conv: input (B, C_out, H_o, H_o), C_out -> C_in channels.
            key = (l.B, l.N, l.H_o, l.H_o, l.C, l.K, l.K, l.S, l.S)
            for p in PASSES:
                out.append(((p, True, key), l, p, True))
        return out


def apply(params, x, geom, mode: str):
    """The reference autoencoder's reconstruction of ``x``."""
    S, P, out_pad = geom
    conv = R.bilinear(partial(R.conv, S=S, P=P), mode)
    conv_t = R.bilinear(partial(R.conv_transpose, S=S, P=P, out_pad=out_pad),
                        mode)
    h = x
    for p in params["enc"]:
        h = jax.nn.relu(conv(h, p["w"]))
    for i, p in enumerate(params["dec"]):
        h = conv_t(h, p["w"])
        if i < len(params["dec"]) - 1:
            h = jax.nn.relu(h)
    return h


@partial(jax.jit, static_argnames=("geom", "mode"))
def _ref_loss_grad(params, x, *, geom, mode):
    def loss(params):
        x_hat = apply(params, x, geom, mode)
        return jnp.mean(jnp.square(x_hat - x)), x_hat
    return jax.value_and_grad(loss, has_aux=True)(params)


def build(config: dict, traffic: dict, policy: str) -> AutoencoderTrain:
    return AutoencoderTrain(config, traffic, policy)
