"""Sequence-parallel attention: ring/Ulysses vs dense reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from p2pfl_tpu.ops import ring_self_attention, ulysses_attention


def _dense_attention(q, k, v):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / d**0.5
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    shape = (2, 32, 8, 8)  # [b, s, h, d]; s (and for Ulysses h) shard over 8
    return tuple(
        jnp.asarray(rng.normal(size=shape).astype(np.float32)) for _ in range(3)
    )


def _sharded(attn):
    """The attention fn under shard_map with the sequence axis over
    all devices — one wiring shared by the forward and gradient tests."""
    mesh = Mesh(np.asarray(jax.devices()), ("sp",))
    return jax.shard_map(
        lambda a, b, c: attn(a, b, c, "sp"),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
        check_vma=False,
    )


@pytest.mark.parametrize("attn", [ring_self_attention, ulysses_attention])
def test_sequence_parallel_matches_dense(qkv, attn, n_devices):
    q, k, v = qkv
    out = jax.jit(_sharded(attn))(q, k, v)
    ref = _dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("attn", [ring_self_attention, ulysses_attention])
def test_sequence_parallel_gradients_match_dense(qkv, attn, n_devices):
    """Training THROUGH the sequence-parallel path: gradients w.r.t.
    q/k/v under shard_map (ppermute / all_to_all collectives on the
    backward pass) must match the dense oracle's."""
    q, k, v = qkv
    sharded = _sharded(attn)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            jnp.tanh(fn(q, k, v).astype(jnp.float32))
        )

    gs = jax.jit(jax.grad(loss(sharded), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss(_dense_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_vit_with_ring_attention_axis(n_devices):
    """ViT(seq_axis=...) runs under shard_map — the long-context path."""
    from p2pfl_tpu.models import get_model

    mesh = Mesh(np.asarray(jax.devices()), ("sp",))
    model = get_model("vit-tiny", dim=32, depth=1, heads=2, patch=4,
                      seq_axis="sp")
    x = jnp.zeros((2, 32, 32, 3))
    # init without the mesh (seq_axis only affects attention internals
    # via collectives, so init must also run inside shard_map)
    fwd = jax.shard_map(
        lambda xx: model.init_with_output(jax.random.PRNGKey(0), xx)[0],
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
    )
    out = jax.jit(fwd)(x)
    assert out.shape == (2, 10)
