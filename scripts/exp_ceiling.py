"""Round-5 MFU-ceiling measurement (VERDICT r4 #1).

Per-op scan-slope timings of every significant op in the north-star
round at the ROUND-5 headline configuration (n=64 nodes, batch 336,
bf16 params/grads/momentum, PatchConv conv1), next to each op's
analytic floor:

- compute floor  = FLOPs / (197 TF/s * tile_eff), where tile_eff is
  the fraction of the 128x128 MXU the op's GEMM tiles can fill
  ((K/128ceil)*(N/128ceil) for weights-stationary [K,N]);
- memory floor   = HBM bytes moved / 819 GB/s.

The per-op achievable time is max(compute, memory); summing those over
the round's ops gives the achievable round time and therefore the
achievable MFU that docs/perf.md §6 derives. Also probes a 4-node
block-diagonal packing of conv1 (trades 4x FLOPs for 16x better tile
fill) to decide whether the conv1 tile penalty is closeable.

Usage: python scripts/exp_ceiling.py
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

PEAK_TF = 197e12  # v5e bf16
HBM_GBS = 819e9


def slope(body, carry0, k1=2, k2=8, reps=3):
    """ms per body-run (scripts/exp_op_breakdown.py harness)."""

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
        print(f"  [suspect slope: k{k1}={t1*1000:.1f} k{k2}={t2*1000:.1f}]",
              flush=True)
    return (t2 - t1) / (k2 - k1) * 1000


def tile_eff(k, n):
    import math
    return (k / (128 * math.ceil(k / 128))) * (n / (128 * math.ceil(n / 128)))


def main() -> None:
    n, b = 64, 336
    key = jax.random.PRNGKey(0)
    dt = jnp.bfloat16

    x1 = jax.random.normal(key, (n, b, 28, 28, 1), dt)
    w1 = jax.random.normal(key, (n, 5, 5, 1, 32), dt)
    x2 = jax.random.normal(key, (n, b, 14, 14, 32), dt)
    w2 = jax.random.normal(key, (n, 5, 5, 32, 64), dt)
    xd = jax.random.normal(key, (n, b, 3136), dt)
    wd = jax.random.normal(key, (n, 3136, 2048), dt)
    xe = jax.random.normal(key, (n, b, 2048), dt)
    we = jax.random.normal(key, (n, 2048, 62), dt)

    def conv(x, w):
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

    rows = []

    def probe(tag, body, carry0, flops, bytes_moved, eff):
        try:
            ms = slope(body, carry0)
        except Exception as e:
            print(f"{tag:24s} FAILED {e!r}"[:140], flush=True)
            return
        comp = flops / (PEAK_TF * eff) * 1e3
        mem = bytes_moved / HBM_GBS * 1e3
        floor = max(comp, mem)
        rows.append((tag, ms, comp, mem, floor))
        print(f"{tag:24s} {ms:7.2f} ms   floor {floor:6.2f} "
              f"(mxu {comp:5.2f} / hbm {mem:5.2f})", flush=True)

    S = b * n  # samples per step federation-wide

    # ---- conv1 as the model runs it (PatchConv: patches + matmul) ----
    def c1_fwd(c):
        x, w = c
        p = patches(x)
        out = jnp.einsum("nbhwk,nkc->nbhwc", p, w.reshape(n, 25, 32))
        return out.mean(-1, keepdims=True) + x, w

    probe("conv1 fwd patches", c1_fwd, (x1, w1),
          flops=S * 784 * 25 * 32 * 2,
          bytes_moved=S * 784 * (1 + 25 + 32) * 2,  # x read, p w+r? p fused
          eff=tile_eff(25, 32))

    def c1_wgrad(c):
        x, w, cot = c

        def f(ww):
            p = patches(x)
            return jnp.einsum("nbhwk,nkc->nbhwc", p, ww.reshape(n, 25, 32))

        _, vjp = jax.vjp(f, w)
        dw = vjp(cot)[0]
        return x, dw + w, cot + jnp.broadcast_to(
            dw.sum((1, 2, 3))[:, None, None, None, :], cot.shape)

    cot1 = jax.jit(lambda x, w: conv(x, w))(x1, w1)
    probe("conv1 wgrad patches", c1_wgrad, (x1, w1, cot1),
          flops=S * 784 * 25 * 32 * 2,
          bytes_moved=S * 784 * (25 + 32) * 2,
          eff=tile_eff(25, 32))

    # ---- conv1 4-node block-diagonal packing candidate ---------------
    g = n // 4
    eye4 = jnp.eye(4, dtype=dt)

    def c1_packed(c):
        x, w = c
        p = patches(x).reshape(g, 4, b * 784, 25)
        pb = jnp.einsum("gimk,ij->gmjk", p, eye4).reshape(g, b * 784, 100)
        wg = w.reshape(g, 4, 25, 32)
        wb = jnp.einsum("gikc,ij->gjkic", wg, eye4).reshape(g, 100, 128)
        ob = jnp.einsum("gmk,gkc->gmc", pb, wb)  # [g, b*784, 128]
        out = ob.reshape(g, 4, b, 784, 4, 32)
        out = jnp.einsum("gjbmic,ij->gibmc", out, eye4)
        out = out.reshape(n, b, 28, 28, 32)
        return out.mean(-1, keepdims=True) + x, w

    probe("conv1 fwd packed4", c1_packed, (x1, w1),
          flops=S * 784 * 100 * 128 * 2,
          bytes_moved=S * 784 * (25 + 100 + 128 + 32) * 2,
          eff=tile_eff(100, 128))

    # ---- conv2 (grouped lowering, as the model runs it) --------------
    def c2_fwd(c):
        return (conv(c[0], c[1]).mean(-1, keepdims=True) + c[0], c[1])

    probe("conv2 fwd grouped", c2_fwd, (x2, w2),
          flops=S * 196 * 800 * 64 * 2,
          bytes_moved=S * 196 * (32 + 64) * 2,
          eff=tile_eff(800, 64))

    cot2 = jax.jit(lambda x, w: conv(x, w))(x2, w2)

    def c2_dgrad(c):
        x, w, cot = c
        _, vjp = jax.vjp(lambda xx: conv(xx, w), x)
        return vjp(cot)[0] + x, w, cot

    probe("conv2 dgrad grouped", c2_dgrad, (x2, w2, cot2),
          flops=S * 196 * 800 * 64 * 2,
          bytes_moved=S * 196 * (64 + 32) * 2,
          eff=tile_eff(64, 800))

    def c2_wgrad(c):
        x, w, cot = c
        _, vjp = jax.vjp(lambda ww: conv(x, ww), w)
        dw = vjp(cot)[0]
        return x, dw + w, cot + jnp.broadcast_to(
            dw.sum((1, 2, 3))[:, None, None, None, :], cot.shape)

    probe("conv2 wgrad grouped", c2_wgrad, (x2, w2, cot2),
          flops=S * 196 * 800 * 64 * 2,
          bytes_moved=S * 196 * (64 + 32) * 2,
          eff=tile_eff(800, 64))

    # ---- dense layers -------------------------------------------------
    def d1_fwd(c):
        return (jnp.einsum("nbk,nkh->nbh", c[0], c[1])
                .mean(-1, keepdims=True) + c[0], c[1])

    probe("dense1 fwd", d1_fwd, (xd, wd),
          flops=S * 3136 * 2048 * 2,
          bytes_moved=(S * (3136 + 2048) + n * 3136 * 2048) * 2,
          eff=tile_eff(3136, 2048))

    cotd = jax.jit(lambda a, w: jnp.einsum("nbk,nkh->nbh", a, w))(xd, wd)

    def d1_grads(c):
        a, w, cot = c
        _, vjp = jax.vjp(lambda aa, ww: jnp.einsum("nbk,nkh->nbh", aa, ww),
                         a, w)
        da, dw = vjp(cot)
        return da + a, dw + w, cot

    probe("dense1 dgrad+wgrad", d1_grads, (xd, wd, cotd),
          flops=2 * S * 3136 * 2048 * 2,
          bytes_moved=2 * (S * (3136 + 2048) + n * 3136 * 2048) * 2,
          eff=tile_eff(2048, 3136))

    # ---- dense1 backward SPLIT (round 6): which half owes the 7.5 ms?
    # The combined probe cannot say whether XLA's dgrad ([b,2048] @
    # w^T, weight re-streamed) or wgrad (a^T @ cot, activation
    # re-streamed) carries the overage — the fused Pallas kernel
    # (ops.pallas_gemm.dense_bwd) only pays off if the split shows the
    # re-streaming, not the MXU, is the cost. Diagnostic only: the
    # split probes are excluded from the round-composition sum (the
    # combined probe above stays the composition's line item).
    def d1_dgrad(c):
        a, w, cot = c
        _, vjp = jax.vjp(lambda aa: jnp.einsum("nbk,nkh->nbh", aa, w), a)
        return vjp(cot)[0] + a, w, cot

    probe("dense1 dgrad only", d1_dgrad, (xd, wd, cotd),
          flops=S * 3136 * 2048 * 2,
          bytes_moved=(S * (2048 + 3136) + n * 3136 * 2048) * 2,
          eff=tile_eff(2048, 3136))

    def d1_wgrad(c):
        a, w, cot = c
        _, vjp = jax.vjp(lambda ww: jnp.einsum("nbk,nkh->nbh", a, ww), w)
        return a, vjp(cot)[0] + w, cot

    probe("dense1 wgrad only", d1_wgrad, (xd, wd, cotd),
          flops=S * 3136 * 2048 * 2,
          bytes_moved=(S * (3136 + 2048) + n * 3136 * 2048) * 2,
          eff=tile_eff(3136, 2048))

    # ---- Pallas kernel candidates at the same shapes (round 6) -------
    # TPU-only: interpret mode is a correctness tool, these shapes
    # would take minutes per probe on CPU. probe() already catches
    # Mosaic lowering failures and prints FAILED instead of dying.
    if jax.default_backend() == "tpu":
        from p2pfl_tpu.ops import pallas_gemm

        def c1_pallas_fwd(c):
            x, w = c
            p = patches(x).reshape(n, b * 784, 25)
            out = jax.vmap(pallas_gemm.patches_matmul)(
                p, w.reshape(n, 25, 32))
            out = out.reshape(n, b, 28, 28, 32)
            return out.mean(-1, keepdims=True) + x, w

        probe("conv1 fwd pallas", c1_pallas_fwd, (x1, w1),
              flops=S * 784 * 25 * 32 * 2,
              bytes_moved=S * 784 * (1 + 25 + 32) * 2,
              eff=tile_eff(25, 32))

        def c1_pallas_wgrad(c):
            x, w, cot = c

            def f(ww):
                p = patches(x).reshape(n, b * 784, 25)
                out = jax.vmap(pallas_gemm.patches_matmul)(
                    p, ww.reshape(n, 25, 32))
                return out.reshape(n, b, 28, 28, 32)

            _, vjp = jax.vjp(f, w)
            dw = vjp(cot)[0]
            return x, dw + w, cot + jnp.broadcast_to(
                dw.sum((1, 2, 3))[:, None, None, None, :], cot.shape)

        probe("conv1 wgrad pallas", c1_pallas_wgrad, (x1, w1, cot1),
              flops=S * 784 * 25 * 32 * 2,
              bytes_moved=S * 784 * (25 + 32) * 2,
              eff=tile_eff(25, 32))

        def d1_pallas_bwd(c):
            a, w, cot = c
            da, dw = jax.vmap(pallas_gemm.dense_bwd)(a, w, cot)
            return da + a, dw.astype(w.dtype) + w, cot

        probe("dense1 bwd pallas", d1_pallas_bwd, (xd, wd, cotd),
              flops=2 * S * 3136 * 2048 * 2,
              bytes_moved=(S * (3136 + 2048) + n * 3136 * 2048) * 2,
              eff=tile_eff(2048, 3136))
    else:
        print("(pallas kernel probes skipped: backend is "
              f"{jax.default_backend()}, kernels target TPU Mosaic)",
              flush=True)

    def d2_fwd(c):
        return (jnp.einsum("nbk,nkh->nbh", c[0], c[1])
                .mean(-1, keepdims=True) + c[0], c[1])

    probe("dense2 fwd", d2_fwd, (xe, we),
          flops=S * 2048 * 62 * 2,
          bytes_moved=S * (2048 + 62) * 2,
          eff=tile_eff(2048, 62))

    # ---- optimizer state stream (params+grads+momentum, all bf16) ----
    import optax
    P = 6_430_000  # ~params per node
    params = jax.random.normal(key, (n, P // 64, 64), dt)
    grads = jax.random.normal(key, (n, P // 64, 64), dt)
    tx = optax.sgd(0.05, momentum=0.9, accumulator_dtype=dt)
    opt = jax.jit(tx.init)(params)

    def sgd_step(c):
        p, g, o = c
        up, o = tx.update(g, o, p)
        p = optax.apply_updates(p, up)
        return p, g, o

    state_bytes = (n * P * 2) * 5  # p r+w, m r+w, g r
    probe("sgd update stream", sgd_step, (params, grads, opt),
          flops=n * P * 4, bytes_moved=state_bytes, eff=1.0)

    # round 17: the fused Pallas SGD stream at the same state shapes —
    # one M-streamed pass over params/trace/grads vs optax's
    # per-transform tree traversals. TPU-only like the GEMM probes.
    if jax.default_backend() == "tpu":
        from p2pfl_tpu.ops import pallas_gemm

        def sgd_fused_pallas(c):
            p, g, o = c

            def f(pp, mm, gg):
                return pallas_gemm.sgd_accum(pp, mm, gg, 0.05,
                                             momentum=0.9)

            p2, m2 = jax.vmap(f)(p, o[0].trace, g)
            return p2, g, (o[0]._replace(trace=m2), o[1])

        probe("sgd update fused pallas", sgd_fused_pallas,
              (params, grads, opt),
              flops=n * P * 4, bytes_moved=state_bytes, eff=1.0)

    # ---- FedAvg mixing einsum (bf16 stack) ---------------------------
    mix = jnp.abs(jax.random.normal(key, (n, n), jnp.float32))
    mixn = (mix / mix.sum(1, keepdims=True)).astype(dt)

    def mix_step(c):
        p, w = c
        flat = p.reshape(n, -1)
        out = jax.lax.dot(w, flat, preferred_element_type=jnp.float32)
        return out.reshape(p.shape).astype(p.dtype), w

    probe("fedavg mix einsum", mix_step, (params, mixn),
          flops=n * n * P * 2, bytes_moved=n * P * 2 * 2,
          eff=tile_eff(64, 128))

    # ---- LoRA adapter GEMMs at vit32 widths (round 19) ---------------
    # The adapter-only federation's extra per-step compute: the rank-r
    # bottleneck pair x@A [T,d]@[d,r] then @B [T,r]@[r,d] at ViT-Tiny's
    # attention width (192) and MLP width (768), 16 nodes vmapped,
    # T = 115 batch x 64 tokens (the lora bench phase's shapes). The
    # thin [.,r] tiles fill at most r/128 of the MXU lanes — these rows
    # price that tax against the HBM floor. Diagnostic only: vit-shaped
    # ops have no line in the femnist round composition below.
    T = 115 * 64
    nl = 16
    for d in (192, 768):
        for r in (4, 8, 16):
            xl = jax.random.normal(key, (nl, T, d), dt)
            al = jax.random.normal(key, (nl, d, r), dt)
            bl = jax.random.normal(key, (nl, r, d), dt)

            def lora_fwd(c):
                x, a, bb = c
                y = jnp.einsum("ntr,nrd->ntd",
                               jnp.einsum("ntd,ndr->ntr", x, a), bb)
                return y + x, a, bb

            probe(f"lora gemm d{d} r{r}", lora_fwd, (xl, al, bl),
                  flops=nl * T * 2 * d * r * 2,
                  bytes_moved=nl * (2 * T * d + T * r + 2 * d * r) * 2,
                  eff=tile_eff(d, r))

    # ---- summary ------------------------------------------------------
    print("\nround composition (2 steps/epoch at b336):")
    diagnostic = ("conv1 fwd packed4", "fedavg mix einsum",
                  "dense1 dgrad only", "dense1 wgrad only",
                  "conv1 fwd pallas", "conv1 wgrad pallas",
                  "dense1 bwd pallas",
                  "sgd update fused pallas")
    per_step = [r for r in rows if r[0] not in diagnostic
                and not r[0].startswith("lora ")]
    meas = sum(r[1] for r in per_step)
    floor = sum(r[4] for r in per_step)
    print(f"  per-step measured sum {meas:.1f} ms, achievable floor "
          f"{floor:.1f} ms")


if __name__ == "__main__":
    main()
