"""Where does the 196 ms north-star epoch actually go, per op?

Scan-slope timing (op inside a fori_loop in ONE program, slope between
two trip counts — per-op device time net of host dispatch,
docs/perf.md §1) of each layer's forward and backward as
the vmapped federation runs them: n=64 nodes, batch 224, bf16 compute.

Measured round-4 results (bench chip, TPU v5e, n=64, batch 224).
Noise caveat: on the round-4 machine each probe's k=2/k=8 totals sat
near a ~110 ms per-dispatch floor, so single-digit values carry
+-3 ms run-to-run scatter — the END-TO-END A/B (209 -> 165 ms/epoch, below) is the
ground truth; these attribute it:

    conv1 fwd (grouped, Cin=1)    ~13.5 ms  (~1.3% of bf16 peak!)
    conv1 fwd im2col               ~7.0 ms  (~2x faster)
    conv1 fwd shift-MAC           ~10-12 ms (no win)
    conv1 wgrad (grouped)          ~4.8 ms  (cotangent carried, fwd
                                             excluded — an earlier
                                             version double-counted)
    conv1 dgrad (grouped)          ~2.7-4 ms (NOT run by the real
                                             program: first layer)
    conv2 fwd (grouped, Cin=32)    ~3.6-10 ms
    conv2 dgrad / wgrad            ~0.5-3.4 / ~7.6 ms
    dense1 fwd                     ~1.6 ms
    conv1 im2col dx+dw            ~18.5 ms  (dx dominates: the
                                             patches-transpose
                                             scatter-add — also NOT
                                             run by the real program)

conv1 under the grouped lowering costs ~18 ms of the ~65 ms step
(fwd + wgrad; no first-layer dx). The federation's vmapped per-node
conv weights lower to feature_group_count=64 grouped convolutions;
with Cin=1 each group contracts only 25 — a degenerate shape whose
grouped-conv lowering barely uses the MXU. conv2's groups contract
800 and are fine. The fix (models/cnn.py PatchConv): express
small-contraction convs as conv_general_dilated_patches + matmul,
which XLA maps to a well-tiled batched GEMM — measured
209 -> 165 ms/epoch end-to-end (1.27x). Whole-model im2col loses
(conv2's patches are an 800-wide materialization, exp_im2col.py);
the win is im2col for conv1 ONLY, and only its fwd + dw (its dx
would cost a scatter-add the first layer never needs).

All operands ride the fori_loop carry (nothing closed over): big
closed-over arrays are baked into the HLO as constants and inflate
the program the compiler is handed.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np


def slope(body, carry0, k1=2, k2=8, reps=3):
    """ms per body-run: fori_loop(k) timed at two trip counts, slope.
    ``body(carry) -> carry`` with every operand inside the carry.

    Sync via a host transfer of the first carry leaf, NOT
    block_until_ready: on a wedged backend (observed once after a
    transport error) block_until_ready returns instantly on errored
    buffers and the probe silently times nothing — a transfer surfaces
    the error instead."""

    def run(k):
        @jax.jit
        def prog(c):
            return jax.lax.fori_loop(0, k, lambda i, c: body(c), c)

        def sync(out):
            leaf = jax.tree.leaves(out)[0]
            return float(jnp.sum(leaf.astype(jnp.float32)))

        sync(prog(carry0))
        times = []
        for _ in range(reps):
            t0 = time.monotonic()
            out = prog(carry0)
            sync(out)
            times.append(time.monotonic() - t0)
        return float(np.median(times))

    t1, t2 = run(k1), run(k2)
    if t2 < 1.2 * t1:
        print(f"  [suspect slope: k{k1}={t1 * 1000:.1f}ms "
              f"k{k2}={t2 * 1000:.1f}ms — body may be DCE'd or "
              "backend wedged]", flush=True)
    return (t2 - t1) / (k2 - k1) * 1000


def main() -> None:
    n, b = 64, 224
    key = jax.random.PRNGKey(0)
    dt = jnp.bfloat16

    x1 = jax.random.normal(key, (n, b, 28, 28, 1), dt)       # conv1 in
    w1 = jax.random.normal(key, (n, 5, 5, 1, 32), dt)
    x2 = jax.random.normal(key, (n, b, 14, 14, 32), dt)      # conv2 in
    w2 = jax.random.normal(key, (n, 5, 5, 32, 64), dt)
    xd = jax.random.normal(key, (n, b, 3136), dt)            # dense1 in
    wd = jax.random.normal(key, (n, 3136, 2048), dt)

    def conv(x, w):
        # per-node weights, exactly as the federation's vmapped learner
        return jax.vmap(
            lambda xx, ww: jax.lax.conv_general_dilated(
                xx, ww, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        )(x, w)

    def patches(x, k=5):
        return jax.vmap(
            lambda xx: jax.lax.conv_general_dilated_patches(
                xx, (k, k), (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        )(x)

    def probe(tag, body, carry0):
        try:
            ms = slope(body, carry0)
            print(f"{tag:28s} {ms:7.2f} ms", flush=True)
        except Exception as e:
            print(f"{tag:28s} FAILED {e!r}"[:160], flush=True)

    # ---- forwards ---------------------------------------------------
    # every body consumes ALL of the op's output (mean over the new
    # channels) — slicing to [..., :1] lets XLA compute only that
    # slice of the matmul/conv and the probe times a fraction of the op
    probe("conv1 fwd grouped",
          lambda c: (conv(c[0], c[1]).mean(-1, keepdims=True) + c[0],
                     c[1]), (x1, w1))
    probe("conv2 fwd grouped",
          lambda c: (conv(c[0], c[1]).mean(-1, keepdims=True) + c[0],
                     c[1]), (x2, w2))
    probe("dense1 fwd",
          lambda c: (jnp.einsum("nbk,nkh->nbh", c[0], c[1])
                     .mean(-1, keepdims=True) + c[0], c[1]), (xd, wd))

    # conv1 alternatives
    def conv1_im2col(c):
        x, w = c
        p = patches(x)  # [n, b, 28, 28, 25]
        out = jnp.einsum("nbhwk,nkc->nbhwc", p, w.reshape(n, 25, 32))
        return out.mean(-1, keepdims=True) + x, w

    probe("conv1 fwd im2col", conv1_im2col, (x1, w1))

    def conv1_shifts(c):
        x, w = c
        xpad = jnp.pad(x[..., 0], ((0, 0), (0, 0), (2, 2), (2, 2)))
        out = jnp.zeros(x.shape[:-1] + (32,), x.dtype)
        for dy in range(5):
            for dx in range(5):
                win = xpad[:, :, dy:dy + 28, dx:dx + 28]
                out = out + (win[..., None]
                             * w[:, dy, dx, 0][:, None, None, None, :])
        return out.mean(-1, keepdims=True) + x, w

    probe("conv1 fwd shift-MAC", conv1_shifts, (x1, w1))

    # ---- backwards --------------------------------------------------
    def g_conv_x(c):
        x, w = c
        _, vjp = jax.vjp(lambda xx: conv(xx, w), x)
        cot = jnp.broadcast_to(x[..., :1], x.shape[:-1] + (w.shape[-1],))
        return vjp(cot)[0] + x, w

    def g_conv_w(c):
        # cotangent rides the CARRY (precomputed once outside): a
        # `cot = conv(x, w)` inside the body would add a full forward
        # to every "wgrad" number. The vjp's own primal is DCE'd (its
        # output is unused and conv wgrad needs no output residual).
        x, w, cot = c
        _, vjp = jax.vjp(lambda ww: conv(x, ww), w)
        dw = vjp(cot)[0]
        return x, dw + w, cot + jnp.broadcast_to(
            dw.sum((1, 2, 3))[:, None, None, None, :], cot.shape)

    probe("conv1 dgrad grouped", g_conv_x, (x1, w1))
    probe("conv1 wgrad grouped", g_conv_w,
          (x1, w1, jax.jit(conv)(x1, w1)))
    probe("conv2 dgrad grouped", g_conv_x, (x2, w2))
    probe("conv2 wgrad grouped", g_conv_w,
          (x2, w2, jax.jit(conv)(x2, w2)))

    def g_conv1_im2col(c):
        """dx+dw through the im2col formulation, cotangent carried"""
        x, w, cot = c

        def f(xx, ww):
            p = patches(xx)
            return jnp.einsum("nbhwk,nkc->nbhwc", p, ww.reshape(n, 25, 32))

        _, vjp = jax.vjp(f, x, w)
        dx, dw = vjp(cot)
        return dx + x, dw + w, cot + jnp.broadcast_to(
            dw.sum((1, 2, 3))[:, None, None, None, :], cot.shape)

    probe("conv1 im2col dx+dw", g_conv1_im2col,
          (x1, w1, jax.jit(conv)(x1, w1)))


if __name__ == "__main__":
    main()
