"""Train a conv -> conv_transpose autoencoder with a selectable conv-backprop
engine policy -- the transposed-conv-as-forward workload (decoders, GAN
generators, upsampling heads), end-to-end through ``make_train_step``.

    PYTHONPATH=src python examples/train_autoencoder_bp.py --policy auto
    PYTHONPATH=src python examples/train_autoencoder_bp.py \
        --policy fwd=pallas,dgrad=bp_phase,wgrad=bp_im2col --steps 200

Policies: a uniform engine name (lax | traditional | bp_im2col | bp_phase |
pallas), "auto" (per-pass shape-dependent selection), or an explicit
per-pass string fwd=...,dgrad=...,wgrad=...  The decoder's stride-2
``conv2d_transpose`` layers run zero-insertion-free on every
transpose-native engine (the stride IS the zero-insertion the paper's
transposed mode skips); "traditional" physically materializes the
zero-spaced input -- the paper's baseline -- and reaches the same losses.

Unlike ``train_cnn_bp.py``'s hand-rolled SGD loop, this example drives the
REAL training stack: ``repro.train.make_train_step`` with the
``loss=autoencoder_loss`` plugin, AdamW, LR schedule, and
``conv_policy=`` threading the per-pass engines into every conv and
conv_transpose of the model.
"""

import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.core.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.optim import adamw
from repro.train import make_train_step


def synthetic_images(rng, n, c=3, size=16):
    """Learnable reconstruction task: smooth low-frequency blobs (a few
    random Fourier modes per image), not raw noise."""
    yy, xx = np.meshgrid(np.arange(size), np.arange(size), indexing="ij")
    out = np.zeros((n, c, size, size), np.float32)
    for i in range(n):
        for ch in range(c):
            fy, fx = rng.randint(1, 4, 2)
            py, px = rng.rand(2) * 2 * np.pi
            amp = rng.rand() + 0.5
            out[i, ch] = amp * np.sin(2 * np.pi * fy * yy / size + py) \
                * np.cos(2 * np.pi * fx * xx / size + px)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="auto",
                    help="engine policy: a uniform engine name, 'auto' "
                         "(per-pass shape-dependent selection), or a "
                         "per-pass string fwd=...,dgrad=...,wgrad=...")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--size", type=int, default=16)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--mse-floor", type=float, default=0.05,
                    help="final reconstruction MSE must fall below this")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = M.AutoencoderConfig(c_in=3, widths=(16, 32), k=3,
                              conv_policy=args.policy)
    params = M.init_autoencoder(jax.random.PRNGKey(0), cfg)
    opt_state = adamw.init_state(params)
    step_fn = jax.jit(make_train_step(
        cfg, adamw.AdamWConfig(peak_lr=args.lr, weight_decay=0.0),
        total_steps=args.steps, warmup=max(1, args.steps // 10),
        loss=M.autoencoder_loss, conv_policy=args.policy))

    rng = np.random.RandomState(0)
    t0 = time.perf_counter()
    mse = float("nan")
    for step in range(args.steps):
        batch = {"image": synthetic_images(rng, args.batch, cfg.c_in,
                                           args.size)}
        params, opt_state, metrics = step_fn(params, opt_state, batch, step)
        mse = float(metrics["mse"])
        if step % 20 == 0 or step == args.steps - 1:
            print(f"[{args.policy}] step={step:4d} mse={mse:.5f}")
    dt = time.perf_counter() - t0
    print(f"[{args.policy}] done in {dt:.1f}s  final_mse={mse:.5f}")
    assert mse < args.mse_floor, (
        f"autoencoder failed to learn: mse {mse:.5f} >= {args.mse_floor}")


if __name__ == "__main__":
    main()
