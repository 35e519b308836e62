"""The plain float32 reference, and its control one precision below.

It imports nothing of the program.  Convs are ``lax.conv_general_dilated``
at ``Precision.HIGHEST``, gradients come from jax's own autodiff of them,
and the optimizers are written out from their papers: SGD, and AdamW
(Loshchilov & Hutter, arXiv:1711.05101) with global-norm clipping under a
linear-warmup cosine schedule.

``mode="high"`` is the control: every conv pass (forward, input grad,
weight grad) computed as three bfloat16 passes with float32 accumulation,
``hi*hi + hi*lo + lo*hi`` of the operands split into bfloat16 halves --
what ``Precision.HIGH`` does on a TPU, spelled out so that it runs the same
on any backend.  The halves are rounded with integer operations: XLA may
fold a float32 -> bfloat16 -> float32 round trip away (it allows excess
precision), which leaves ``lo`` zero and the control a single pass.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

MODES = ("highest", "high")
_DN = ("NCHW", "OIHW", "NCHW")


def conv(x, w, S: int, P: int):
    """Regular conv: x (B, C, H, W), w (N, C, K, K)."""
    return lax.conv_general_dilated(x, w, (S, S), [(P, P), (P, P)],
                                    dimension_numbers=_DN,
                                    precision=lax.Precision.HIGHEST)


def conv_transpose(x, w, S: int, P: int, out_pad: int):
    """Transposed conv: x (B, C_in, H, W), w (C_in, C_out, K, K); output
    side ``(H - 1) * S - 2P + K + out_pad``.  The input is dilated by S and
    correlated with the flipped kernel."""
    k = w.shape[-1]
    wt = jnp.flip(w, (-2, -1)).transpose(1, 0, 2, 3)
    pad = (k - 1 - P, k - 1 - P + out_pad)
    return lax.conv_general_dilated(x, wt, (1, 1), [pad, pad],
                                    lhs_dilation=(S, S),
                                    dimension_numbers=_DN,
                                    precision=lax.Precision.HIGHEST)


def _to_bf16(a):
    """``a`` rounded to the nearest bfloat16 (ties to even), as float32."""
    bits = lax.bitcast_convert_type(a, jnp.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return lax.bitcast_convert_type(bits, jnp.float32)


def _halves(a):
    hi = _to_bf16(a)
    return hi, _to_bf16(a - hi)


def _three_pass(f, a, b):
    (ah, al), (bh, bl) = _halves(a), _halves(b)
    return f(ah, bh) + f(ah, bl) + f(al, bh)


def bilinear(f, mode: str):
    """``f(a, b)`` (bilinear, float32) with each of its three passes at
    ``mode``.  ``highest`` is ``f`` itself, differentiated by jax."""
    if mode == "highest":
        return f
    if mode != "high":
        raise ValueError(f"mode {mode!r} not in {MODES}")

    @jax.custom_vjp
    def g(a, b):
        return _three_pass(f, a, b)

    def g_fwd(a, b):
        return g(a, b), (a, b)

    def g_bwd(res, dy):
        a, b = res
        da = _three_pass(
            lambda d, bb: jax.vjp(lambda aa: f(aa, bb), a)[1](d)[0], dy, b)
        db = _three_pass(
            lambda aa, d: jax.vjp(lambda bb: f(aa, bb), b)[1](d)[0], a, dy)
        return da, db

    g.defvjp(g_fwd, g_bwd)
    return g


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

def cosine_lr(t: int, opt: dict) -> float:
    """Learning rate at optimizer step ``t`` (1-based): linear warmup over
    ``warmup`` steps, then a cosine from ``peak_lr`` down to
    ``final_frac * peak_lr`` at ``total_steps``."""
    peak, warm, total = opt["peak_lr"], opt["warmup"], opt["total_steps"]
    if t < warm:
        return peak * t / max(warm, 1)
    prog = min(max((t - warm) / max(total - warm, 1), 0.0), 1.0)
    frac = opt["final_frac"]
    return peak * (frac + (1 - frac) * 0.5 * (1 + math.cos(math.pi * prog)))


def adamw_init(params):
    return {"m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(jnp.zeros_like, params)}


@partial(jax.jit, static_argnames=("opt_items",))
def _adamw(params, grads, state, lr, t, *, opt_items: tuple):
    opt = dict(opt_items)
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"],
                     grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    new = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + wd * p), params, m, v)
    return new, {"m": m, "v": v}


def adamw_step(params, grads, state, t: int, opt: dict):
    """One AdamW update at 1-based step ``t``."""
    items = tuple(sorted((k, v) for k, v in opt.items()
                         if isinstance(v, (int, float))))
    return _adamw(params, grads, state, jnp.float32(cosine_lr(t, opt)),
                  jnp.float32(t), opt_items=items)
