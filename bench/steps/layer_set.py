"""A set of conv layers trained side by side, each as its own layer.

One step: every layer's forward, its weight gradient, and its input
gradient unless its input is the image; then plain SGD on every weight.
The gradients are the pull-back of ``g``, the seeded upstream gradient,
through the conv (``jax.vjp``), so the weight gradient is the weight-grad
pass of ``g`` and the input gradient the input-grad pass.  The step returns
every layer's whole output and input gradient: nothing is reduced for the
check, and no pass can be left out unseen.
"""

from __future__ import annotations

from functools import partial

import jax
import numpy as np

from bench import compare
from bench import reference as R
from bench.flops import PASSES, Conv


class LayerSet:
    def __init__(self, config: dict, traffic: dict, policy: str):
        b = config["batch"]
        self.layers = [Conv(b, c, h, n, k, s, p)
                       for h, c, n, k, s, p in
                       config["layer_sets"][traffic["layer_set"]]]
        image = (config["image_channels"], config["image_size"])
        self.needs_dx = [(l.C, l.H) != image for l in self.layers]
        self.lr = traffic["optimizer"]["lr"]
        self.policy = policy

    def weights_tree(self):
        return tuple(((l.N, l.C, l.K, l.K), (l.C * l.K * l.K) ** -0.5)
                     for l in self.layers)

    def batch_tree(self):
        return {"x": tuple(((l.B, l.C, l.H, l.H), 1.0) for l in self.layers),
                "g": tuple(((l.B, l.N, l.H_o, l.H_o), 1.0)
                           for l in self.layers)}

    def init_state(self, weights):
        return tuple(weights)

    def program(self, state, batch, i):
        from repro.core import conv as C
        from repro.core.convspec import ConvSpec
        new, ys, dxs = [], [], []
        for l, dx_needed, w, x, g in zip(self.layers, self.needs_dx, state,
                                         batch["x"], batch["g"]):
            spec = ConvSpec.make(stride=l.S, padding=l.P)

            def f(w, x, spec=spec):
                return C.conv2d(x, w, spec, self.policy)

            if dx_needed:
                y, pull = jax.vjp(f, w, x)
                dw, dx = pull(g)
                dxs.append(dx)
            else:
                y, pull = jax.vjp(partial(f, x=x), w)
                (dw,) = pull(g)
            ys.append(y)
            new.append(w - self.lr * dw)
        return tuple(new), {"y": tuple(ys), "dx": tuple(dxs)}

    def observe(self, states, outs):
        s0, s1, s3 = ([np.asarray(w, np.float64) for w in s] for s in states)
        grad = [(a - b) / self.lr for a, b in zip(s1, s0)]
        return {
            "grad": compare.norms(grad),
            "gproj": compare.project(grad),
            "change": compare.norms([a - b for a, b in zip(s3, s0)]),
            "y": [y for o in outs for y in o["y"]],
            "dx": [dx for o in outs for dx in o["dx"]],
        }

    def reference(self, weights, batches, mode: str):
        ws = [np.asarray(w) for w in weights]
        states, outs = [list(ws)], []
        for batch in batches:
            ys, dxs = [], []
            for j, l in enumerate(self.layers):
                x, g = batch["x"][j], batch["g"][j]
                y, dw, dx = _ref_layer(ws[j], x, g, S=l.S, P=l.P, mode=mode)
                ys.append(np.asarray(y))
                if self.needs_dx[j]:
                    dxs.append(np.asarray(dx))
                ws[j] = np.asarray(ws[j] - self.lr * dw)
            outs.append({"y": ys, "dx": dxs})
            states.append(list(ws))
        return self.observe((states[0], states[1], states[3]), outs)

    def passes(self):
        out = []
        for l, dx_needed in zip(self.layers, self.needs_dx):
            key = (l.B, l.C, l.H, l.H, l.N, l.K, l.K, l.S, l.S)
            for p in PASSES:
                out.append(((p, False, key), l, p,
                            p != "input_grad" or dx_needed))
        return out


@partial(jax.jit, static_argnames=("S", "P", "mode"))
def _ref_layer(w, x, g, *, S, P, mode):
    f = R.bilinear(partial(R.conv, S=S, P=P), mode)
    y, pull = jax.vjp(lambda w, x: f(x, w), w, x)
    dw, dx = pull(g)
    return y, dw, dx


def build(config: dict, traffic: dict, policy: str) -> LayerSet:
    return LayerSet(config, traffic, policy)
