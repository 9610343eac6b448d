"""Sequence-parallel attention over a mesh axis.

Two standard schemes, both pure-JAX collectives (XLA schedules them
over ICI):

- **Ring attention** (`ring_self_attention`): each device holds a
  sequence shard of Q, K, V. K/V blocks rotate around the ring with
  ``ppermute`` while flash-style running-softmax statistics (row max m,
  row sum l) accumulate the output — O(seq/n) memory per device and
  the K/V transfer overlaps with the block matmuls.
- **Ulysses** (`ulysses_attention`): ``all_to_all`` swaps the sharded
  axis from sequence to heads, runs ordinary full-sequence attention
  on head shards, and swaps back — cheaper for many-head models on
  small meshes.

Use inside ``shard_map`` with the sequence axis sharded over
``axis_name``. No counterpart exists in the reference (no attention
models at all — SURVEY.md §5.7); this is the long-context capability
the TPU build adds, wired into models.vit.ViT via ``seq_axis``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def reference_attention(q, k, v):
    """Plain softmax attention ([b, s, h, d] layout) — the on-device
    block used inside the sequence-parallel schemes and the parity
    oracle for attention tests.

    This IS the hot attention path: the round-5 crossover measurements
    (docs/perf.md §5b) showed the hand-tiled Pallas flash kernel
    losing to this XLA block 1.5-1.7x at every shard length up to 4096
    on the bench chip, so the kernel was removed in round 6.
    """
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / (d**0.5)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _block_attn(q, k, v, m, l, o, scale):
    """One blockwise-softmax accumulation step (flash-attention update).

    q: [b, sq, h, d]; k, v: [b, sk, h, d];
    m, l: [b, h, sq] running max / sum; o: [b, h, sq, d] accumulator.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p.astype(v.dtype), v
    ).astype(jnp.float32)
    return m_new, l_new, o_new


def ring_self_attention(q, k, v, axis_name: str):
    """Ring attention: q/k/v are this device's sequence shards
    [batch, seq_shard, heads, head_dim]; returns the local output shard.
    """
    n = jax.lax.axis_size(axis_name)
    b, sq, h, d = q.shape
    scale = 1.0 / (d**0.5)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # mark accumulators device-varying so the fori_loop carry types match
    # the collective-produced outputs (vma tracking)
    vary = lambda x: jax.lax.pcast(x, (axis_name,), to="varying")
    m = vary(jnp.full((b, h, sq), -jnp.inf, jnp.float32))
    l = vary(jnp.zeros((b, h, sq), jnp.float32))
    o = vary(jnp.zeros((b, h, sq, d), jnp.float32))

    def body(i, carry):
        m, l, o, k, v = carry
        m, l, o = _block_attn(q, k, v, m, l, o, scale)
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        return m, l, o, k, v

    m, l, o, k, v = jax.lax.fori_loop(0, n, body, (m, l, o, k, v))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [b, sq, h, d]


def ulysses_attention(q, k, v, axis_name: str):
    """Ulysses-style: all_to_all seq→heads, full attention, heads→seq.

    Requires heads divisible by the axis size. q/k/v: sequence shards
    [b, s_shard, h, d]; attention itself sees [b, s_full, h_shard, d].
    """
    n = jax.lax.axis_size(axis_name)
    b, s, h, d = q.shape
    if h % n:
        raise ValueError(f"heads ({h}) must divide over axis size ({n})")

    def seq_to_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qf, kf, vf = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    scale = 1.0 / (d**0.5)
    s_mat = jnp.einsum("bqhd,bkhd->bhqk", qf, kf).astype(jnp.float32) * scale
    p = jax.nn.softmax(s_mat, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vf.dtype), vf)
    return heads_to_seq(out).astype(q.dtype)
