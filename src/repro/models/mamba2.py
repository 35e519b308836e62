"""Mamba2 (SSD, arXiv:2405.21060) block: chunked state-space duality.

Training/prefill uses the chunked SSD algorithm: within-chunk quadratic
(attention-like) term + across-chunk linear state recurrence, so memory is
O(L*Q + L/Q * state) instead of O(L * state) for the naive scan.  Decode is
the O(1) recurrent update.

The causal depthwise Conv1D (width ``ssm_conv``) routes through
repro.core.depthwise_causal_conv1d -- the layer that hosts the paper's
BP-im2col engine inside this architecture.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import depthwise_causal_conv1d
from repro.core.config import config
from repro.models import layers as L


def __getattr__(name):
    # Deprecated alias for the pre-config module constant; the SSD chunk
    # length now lives at repro.config.ssd_chunk (read per call, so tests
    # can override it without reload tricks).
    if name == "CHUNK":
        return config.ssd_chunk
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def d_inner(cfg: ArchConfig) -> int:
    return cfg.ssm_expand * cfg.d_model


def n_heads(cfg: ArchConfig) -> int:
    return d_inner(cfg) // cfg.ssm_head_dim


def init_mamba2(key, cfg: ArchConfig, nl=None):
    di, h, ds = d_inner(cfg), n_heads(cfg), cfg.ssm_state
    ks = jax.random.split(key, 4)
    shape = lambda *s: s if nl is None else (nl, *s)
    # in_proj packs [z, x, B, C, dt]
    proj_out = 2 * di + 2 * ds + h
    return {
        "in_proj": L.init_linear(ks[0], cfg.d_model, proj_out, cfg.dtype, nl),
        "conv_w": {"w": (jax.random.normal(ks[1], shape(cfg.ssm_conv,
                                                        di + 2 * ds),
                                           jnp.float32) * 0.2).astype(cfg.dtype)},
        "a_log": {"w": jnp.zeros(shape(h), jnp.float32)},
        "dt_bias": {"w": jnp.zeros(shape(h), jnp.float32)},
        "d_skip": {"w": jnp.ones(shape(h), jnp.float32)},
        "norm": L.init_rmsnorm(di, cfg.dtype, nl),
        "out_proj": L.init_linear(ks[2], di, cfg.d_model, cfg.dtype, nl,
                                  scale=di ** -0.5),
    }


def _ssd_chunked(xh, dt, a_log, B, C):
    """Chunked SSD.

    xh (B,L,H,P)  dt (B,L,H)  a_log (H,)  B,C (B,L,S)  ->  y (B,L,H,P)
    """
    b, l, h, p = xh.shape
    s = B.shape[-1]
    # SSD chunk length: intra-chunk (quadratic) work scales ~Q per token,
    # the inter-chunk state recurrence ~1/Q -- a perf-iteration lever.
    q = min(config.ssd_chunk, l)
    assert l % q == 0, f"seq {l} not divisible by chunk {q}"
    nc = l // q

    la = dt * (-jnp.exp(a_log))[None, None, :]              # log a_t  (B,L,H)
    la = la.reshape(b, nc, q, h)
    dt_r = dt.reshape(b, nc, q, h)
    xr = xh.reshape(b, nc, q, h, p)
    Br = B.reshape(b, nc, q, s)
    Cr = C.reshape(b, nc, q, s)
    cum = jnp.cumsum(la, axis=2)                            # (B,nc,Q,H)

    # ---- intra-chunk (quadratic within chunk) ----
    cb = jnp.einsum("bnis,bnjs->bnij", Cr, Br)              # (B,nc,Q,Q)
    decay = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (B,nc,Qi,Qj,H)
    mask = jnp.tril(jnp.ones((q, q), bool))
    # Mask BEFORE exp: above the diagonal decay > 0 and exp overflows, and
    # where() would send 0 * inf = NaN back through the masked branch.
    att = jnp.exp(jnp.where(mask[None, None, :, :, None], decay, -jnp.inf))
    att = att * cb[..., None] * dt_r[:, :, None, :, :]      # (B,nc,Qi,Qj,H)
    y_intra = jnp.einsum("bnijh,bnjhp->bnihp", att.astype(xr.dtype), xr)

    # ---- chunk states & inter-chunk recurrence ----
    last = cum[:, :, -1:, :]                                # (B,nc,1,H)
    state_w = jnp.exp(last - cum) * dt_r                    # (B,nc,Q,H)
    states = jnp.einsum("bnqs,bnqh,bnqhp->bnhps",
                        Br, state_w.astype(xr.dtype), xr)   # (B,nc,H,P,S)
    chunk_decay = jnp.exp(last[:, :, 0, :])                 # (B,nc,H)

    def scan_fn(carry, inp):
        st, dec = inp                                       # (B,H,P,S),(B,H)
        new = carry * dec[:, :, None, None] + st
        return new, carry                                   # emit PREVIOUS

    init = jnp.zeros((b, h, p, s), xr.dtype)
    _, prev_states = jax.lax.scan(
        scan_fn, init,
        (states.transpose(1, 0, 2, 3, 4),
         chunk_decay.transpose(1, 0, 2).astype(xr.dtype)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)      # (B,nc,H,P,S)

    y_inter = jnp.einsum("bnqs,bnqh,bnhps->bnqhp",
                         Cr, jnp.exp(cum).astype(xr.dtype), prev_states)
    y = (y_intra + y_inter).reshape(b, l, h, p)
    return y


def mamba2_block(p, x, cfg: ArchConfig):
    """Full-sequence forward. x (B, L, D) -> (B, L, D)."""
    b, l, d = x.shape
    di, h, ds, dh = d_inner(cfg), n_heads(cfg), cfg.ssm_state, cfg.ssm_head_dim
    zxbcdt = L.linear(p["in_proj"], x)
    z, xs, Bc, Cc, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + ds, 2 * di + 2 * ds], axis=-1)
    conv_in = jnp.concatenate([xs, Bc, Cc], axis=-1)        # (B,L,di+2S)
    conv_out = depthwise_causal_conv1d(conv_in, p["conv_w"]["w"],
                                       policy=cfg.conv_engine_policy)
    conv_out = jax.nn.silu(conv_out)
    xs, Bc, Cc = jnp.split(conv_out, [di, di + ds], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"]["w"][None, None, :])
    xh = xs.reshape(b, l, h, dh)
    y = _ssd_chunked(xh, dt, p["a_log"]["w"], Bc.astype(xh.dtype),
                     Cc.astype(xh.dtype))
    y = y + xh * p["d_skip"]["w"][None, None, :, None].astype(xh.dtype)
    y = y.reshape(b, l, di)
    y = L.rmsnorm(p["norm"], y * jax.nn.silu(z))
    return L.linear(p["out_proj"], y)


def mamba2_init_state(cfg: ArchConfig, batch: int, nl: int):
    di, h, ds, dh = d_inner(cfg), n_heads(cfg), cfg.ssm_state, cfg.ssm_head_dim
    return {
        "ssm": jnp.zeros((nl, batch, h, dh, ds), cfg.adtype),
        "conv": jnp.zeros((nl, batch, cfg.ssm_conv - 1, di + 2 * ds),
                          cfg.adtype),
    }


def mamba2_decode(p, x, ssm_state, conv_state, cfg: ArchConfig):
    """Single-token recurrent step.  x (B,1,D)."""
    b = x.shape[0]
    di, h, ds, dh = d_inner(cfg), n_heads(cfg), cfg.ssm_state, cfg.ssm_head_dim
    zxbcdt = L.linear(p["in_proj"], x)[:, 0]                # (B, proj)
    z, xs, Bc, Cc, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + ds, 2 * di + 2 * ds], axis=-1)
    conv_in = jnp.concatenate([xs, Bc, Cc], axis=-1)        # (B, di+2S)
    hist = jnp.concatenate([conv_state,
                            conv_in[:, None, :].astype(conv_state.dtype)],
                           axis=1)                          # (B, K, ch)
    w = p["conv_w"]["w"].astype(hist.dtype)                 # (K, ch)
    conv_out = jax.nn.silu(jnp.einsum("bkc,kc->bc", hist, w))
    new_conv_state = hist[:, 1:]
    xs, Bc, Cc = jnp.split(conv_out, [di, di + ds], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"]["w"][None])
    a = jnp.exp(dt * (-jnp.exp(p["a_log"]["w"]))[None])     # (B,H)
    xh = xs.reshape(b, h, dh)
    upd = jnp.einsum("bh,bhp,bs->bhps", dt.astype(xh.dtype), xh,
                     Bc.astype(xh.dtype))
    new_ssm = ssm_state * a[:, :, None, None].astype(ssm_state.dtype) \
        + upd.astype(ssm_state.dtype)
    y = jnp.einsum("bhps,bs->bhp", new_ssm.astype(xh.dtype),
                   Cc.astype(xh.dtype))
    y = y + xh * p["d_skip"]["w"][None, :, None].astype(xh.dtype)
    y = y.reshape(b, di)
    y = L.rmsnorm(p["norm"], y * jax.nn.silu(z))
    out = L.linear(p["out_proj"], y)[:, None, :]
    return out, new_ssm, new_conv_state
