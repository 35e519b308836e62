"""Per-architecture smoke tests (reduced configs): one forward/train step on
CPU asserting output shapes + no NaNs, plus decode-vs-forward consistency."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_arch_ids, get_config, get_smoke_config
from repro.configs.base import applicable_shapes
from repro.models import build_model
from repro.optim import adamw
from repro.train import train_step as TS


def _smoke_batch(cfg, rng, B=2, L=32):
    batch = {}
    if cfg.family == "audio":
        batch["frontend"] = jnp.asarray(
            rng.standard_normal((B, L, cfg.d_frontend)), jnp.float32)
        batch["targets"] = jnp.zeros((B, L), jnp.int32)
    else:
        lt = L - (cfg.frontend_tokens if cfg.family == "vlm" else 0)
        batch["tokens"] = jnp.ones((B, lt), jnp.int32)
        batch["targets"] = jnp.zeros((B, lt), jnp.int32)
        if cfg.family == "vlm":
            batch["frontend"] = jnp.asarray(
                rng.standard_normal((B, cfg.frontend_tokens, cfg.d_frontend)),
                jnp.float32)
    return batch


@pytest.mark.parametrize("arch", all_arch_ids())
class TestArchSmoke:
    def test_forward_shapes_no_nan(self, arch):
        cfg = get_smoke_config(arch)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        B, L = 2, 32
        batch = _smoke_batch(cfg, rng, B, L)
        logits, aux = m.forward(params, batch)
        lpred = batch["targets"].shape[1]
        assert logits.shape == (B, lpred, cfg.vocab)
        assert np.isfinite(np.asarray(logits, np.float32)).all()

    def test_one_train_step(self, arch):
        cfg = get_smoke_config(arch)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        opt = adamw.init_state(params)
        step_fn = TS.make_train_step(cfg, adamw.AdamWConfig(peak_lr=1e-3),
                                     total_steps=10, warmup=1)
        rng = np.random.default_rng(1)
        batch = _smoke_batch(cfg, rng)
        params2, opt2, metrics = jax.jit(step_fn)(params, opt, batch,
                                                  jnp.int32(0))
        assert np.isfinite(float(metrics["loss"]))
        assert np.isfinite(float(metrics["grad_norm"]))
        # params actually changed
        delta = sum(float(jnp.abs(a - b).sum()) for a, b in zip(
            jax.tree.leaves(params), jax.tree.leaves(params2)))
        assert delta > 0

    def test_full_config_exact_dims(self, arch):
        """The FULL config carries the exact published dims (never built on
        CPU, only eval_shape'd by the dry-run)."""
        cfg = get_config(arch)
        assert cfg.n_layers >= 32
        assert cfg.vocab > 500
        shapes = applicable_shapes(cfg)
        assert "train_4k" in shapes and "prefill_32k" in shapes
        if cfg.is_encoder_only:
            assert "decode_32k" not in shapes
        if not cfg.supports_long_context:
            assert "long_500k" not in shapes


@pytest.mark.parametrize("arch", ["smollm_360m", "mamba2_370m",
                                  "recurrentgemma_9b", "deepseek_v3_671b",
                                  "moonshot_v1_16b_a3b", "granite_3_8b"])
def test_decode_matches_forward(arch):
    """Token-by-token decode reproduces the full-sequence forward logits."""
    cfg = dataclasses.replace(get_smoke_config(arch), capacity_factor=8.0)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(42))
    B, L = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0, cfg.vocab)
    logits_full, _ = m.forward(params, {"tokens": toks, "targets": toks})
    cache = m.init_cache(B, L)
    outs = []
    for t in range(L):
        lg, cache = m.decode_step(params, cache, toks[:, t], jnp.int32(t))
        outs.append(lg)
    logits_dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(logits_full, np.float32),
                               np.asarray(logits_dec, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_blockwise_attention_matches_dense():
    """The flash-style blockwise SDPA must equal dense attention."""
    from repro.models import attention as A
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(2, 64, 4, 16), jnp.float32)
    k = jnp.asarray(r.randn(2, 64, 2, 16), jnp.float32)
    v = jnp.asarray(r.randn(2, 64, 2, 16), jnp.float32)
    for causal in (True, False):
        for window in (None, 16):
            dense = A._sdpa_dense(q, k, v, causal=causal, window=window,
                                  q_offset=0, kv_len=None, scale=0.25)
            blk = A._sdpa_blockwise(q, k, v, causal=causal, window=window,
                                    q_offset=0, kv_len=None, scale=0.25)
            np.testing.assert_allclose(dense, blk, rtol=2e-4, atol=2e-4,
                                       err_msg=f"causal={causal} w={window}")


def test_mamba2_chunked_matches_naive_scan():
    """Chunked SSD == naive O(L) recurrence."""
    from repro.models import mamba2 as M2
    cfg = get_smoke_config("mamba2_370m")
    b, l, h, p, s = 1, 256, 2, 8, 4
    r = np.random.RandomState(0)
    xh = jnp.asarray(r.randn(b, l, h, p), jnp.float32)
    dt = jnp.asarray(np.abs(r.randn(b, l, h)) * 0.1, jnp.float32)
    a_log = jnp.asarray(r.randn(h) * 0.1, jnp.float32)
    B = jnp.asarray(r.randn(b, l, s), jnp.float32)
    C = jnp.asarray(r.randn(b, l, s), jnp.float32)
    got = M2._ssd_chunked(xh, dt, a_log, B, C)
    # naive recurrence
    a = np.exp(np.asarray(dt) * (-np.exp(np.asarray(a_log)))[None, None])
    state = np.zeros((b, h, p, s))
    ys = []
    for t in range(l):
        upd = np.einsum("bh,bhp,bs->bhps", np.asarray(dt)[:, t], np.asarray(xh)[:, t],
                        np.asarray(B)[:, t])
        state = state * a[:, t][:, :, None, None] + upd
        ys.append(np.einsum("bhps,bs->bhp", state, np.asarray(C)[:, t]))
    want = np.stack(ys, axis=1)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_mamba2_chunked_grads_stay_finite():
    """Above the diagonal of a chunk the decay exponent is positive and
    overflows for long chunks; masking it after the exp sent 0 * inf = NaN
    into every gradient once seq reached the 128-token chunk."""
    from repro.models import mamba2 as M2
    b, l, h, p, s = 1, 128, 2, 8, 4
    r = np.random.RandomState(1)
    xh = jnp.asarray(r.randn(b, l, h, p), jnp.float32)
    dt = jnp.asarray(np.abs(r.randn(b, l, h)) + 1.0, jnp.float32)
    a_log = jnp.asarray(r.randn(h) + 1.0, jnp.float32)
    B = jnp.asarray(r.randn(b, l, s), jnp.float32)
    C = jnp.asarray(r.randn(b, l, s), jnp.float32)
    grads = jax.grad(lambda *a: jnp.sum(M2._ssd_chunked(*a) ** 2),
                     argnums=(0, 1, 2, 3, 4))(xh, dt, a_log, B, C)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()


def test_window_skip_attention_matches_dense():
    """Perf-iteration path: O(L*W) local-window schedule == dense attention."""
    from repro.models import attention as A
    r = np.random.RandomState(3)
    for (l, w, hk, g) in [(128, 16, 2, 2), (96, 32, 1, 4), (100, 16, 1, 1)]:
        h = hk * g
        q = jnp.asarray(r.randn(2, l, h, 8), jnp.float32)
        k = jnp.asarray(r.randn(2, l, hk, 8), jnp.float32)
        v = jnp.asarray(r.randn(2, l, hk, 8), jnp.float32)
        want = A._sdpa_dense(q, k, v, causal=True, window=w, q_offset=0,
                             kv_len=None, scale=0.35)
        got = A._sdpa_local_window(q, k, v, window=w, scale=0.35)
        np.testing.assert_allclose(want, got, rtol=2e-4, atol=2e-4)


def test_autoencoder_trains_through_make_train_step():
    """The conv -> conv_transpose autoencoder (PR 5): forward shapes, the
    decoder's transposed convs dispatching through the engines (``*_T``
    events), and a few REAL ``make_train_step`` steps (the ``loss=``
    plugin) reducing the reconstruction MSE under a mixed policy."""
    from repro.core import dispatch_events, reset_dispatch_events
    from repro.models import model as M

    cfg = M.AutoencoderConfig(c_in=2, widths=(4, 8), k=3,
                              conv_policy="auto")
    params = M.init_autoencoder(jax.random.PRNGKey(0), cfg)
    # Smooth low-frequency images (a learnable reconstruction target).
    r = np.random.RandomState(0)
    yy, xx = np.meshgrid(np.arange(8), np.arange(8), indexing="ij")
    imgs = np.stack([np.sin(2 * np.pi * f * yy / 8 + p)
                     * np.cos(2 * np.pi * g * xx / 8 + q)
                     for f, g, p, q in
                     [(1, 1, 0.3, 0.1), (1, 2, 1.0, 0.5),
                      (2, 1, 0.0, 2.0), (1, 1, 2.0, 1.2)]])
    x = jnp.asarray(imgs.reshape(2, 2, 8, 8), jnp.float32)
    reset_dispatch_events()
    y = M.autoencoder_apply(params, x, cfg)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    ev = dispatch_events()
    assert sum(v for k, v in ev.items() if k.startswith("forward_T:")) == 2

    step_fn = jax.jit(TS.make_train_step(
        cfg, adamw.AdamWConfig(peak_lr=2e-2, weight_decay=0.0),
        total_steps=60, warmup=1, loss=M.autoencoder_loss,
        conv_policy="fwd=pallas,dgrad=bp_phase,wgrad=bp_im2col"))
    opt = adamw.init_state(params)
    batch = {"image": x}
    first = last = None
    for step in range(60):
        params, opt, metrics = step_fn(params, opt, batch, jnp.int32(step))
        last = float(metrics["mse"])
        first = last if first is None else first
    assert np.isfinite(last)
    assert last < first * 0.5, (first, last)
