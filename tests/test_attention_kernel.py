"""Attention's fused kernel (``ops/flash_attention.py``) against its parity
oracle, the tiled form of ``models/ling.py`` ``causal_attention``, on the
CPU through Pallas's interpreter: the same kernels the TPU compiles, run
step by step. Toy shapes, bfloat16 operands as in every cell, under the
``vmap`` over nodes a round puts around them."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2pfl_tpu.models import ling
from p2pfl_tpu.ops import flash_attention as fa

BF16 = jnp.bfloat16


@pytest.mark.parametrize(
    "T, heads, kv_heads, D, Dv, window, block, tile", [
        (300, 2, 2, 64, 64, None, 128, 128),  # a group of 1; T padded
        (384, 6, 2, 128, 128, 100, 128, 128),  # a group of 3, a window
        (256, 2, 2, 192, 128, None, 128, 128),  # MLA's key width apart
        (512, 8, 2, 64, 64, None, 128, 256),  # LFM2's group of 4 at 64
        (700, 4, 2, 128, 128, 200, 128, 256),  # a window, T padded
        (256, 8, 1, 256, 256, None, 128, 128),  # Qwen3-Next's group of 8 at 256
    ], ids=["group1-pad", "group3-window", "mla-widths", "group4-d64",
            "window-pad", "group8-d256"])
def test_the_kernel_is_the_tiled_form(T, heads, kv_heads, D, Dv, window,
                                      block, tile):
    """Value, output and the gradients to q, k and v, each pass of the
    kernel at the same block and tile as the tiled form: the same pairs
    in the same order, so the same running maximum and the same rounding
    of the probabilities to bfloat16.

    Tolerances: the float32 output and value differ only by the order
    of float32 sums inside a product (1e-5 relative). The gradients leave
    in bfloat16, and the kernel sums the keys' and values' gradients over
    a group head by head where the tiled form sums them inside one
    product over the group's rows: one or two bfloat16 roundings apart,
    so 2^-7 of the largest element."""
    nodes = 2
    ks = jax.random.split(jax.random.PRNGKey(T + heads), 4)
    shaped = lambda key, h, d: jax.random.normal(
        key, (nodes, 1, T, h, d)).astype(BF16)
    q, k = shaped(ks[0], heads, D), shaped(ks[1], kv_heads, D)
    v, weigh = shaped(ks[2], kv_heads, Dv), shaped(ks[3], heads, Dv)
    common = dict(scale=D ** -0.5, window=window, scope="mla.attn",
                  out_dtype=jnp.float32)
    kernel = functools.partial(fa.flash_attention, block=block, tile=tile,
                               interpret=True, **common)
    tiled = functools.partial(ling._tiled, block=block, tile=tile,
                              group=heads // kv_heads, **common)

    def both(attention):
        f = jax.value_and_grad(lambda q, k, v, weigh: jnp.sum(
            weigh.astype(jnp.float32) * attention(q, k, v)),
            argnums=(0, 1, 2))
        return (jax.jit(jax.vmap(attention))(q, k, v),
                jax.jit(jax.vmap(f))(q, k, v, weigh))

    (o, (value, grads)), (o_want, (value_want, want)) = both(kernel), both(
        tiled)
    np.testing.assert_allclose(o, o_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(value, value_want, rtol=1e-5)
    for got, ref in zip(grads, want):
        assert got.dtype == ref.dtype == BF16
        got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
        np.testing.assert_allclose(
            got, ref, rtol=0, atol=2 ** -7 * float(jnp.max(jnp.abs(ref))))


@pytest.mark.parametrize("T, block, tile, window", [
    (1024, 256, 512, None), (1024, 256, 512, 300), (768, 128, 256, 64)])
def test_the_kernels_pairs_are_the_tiled_forms(T, block, tile, window):
    """:func:`visits` lists what the tiled form's loops meet, by block for
    the forward and the queries' gradient and by tile for the keys' and
    values', each run of an outer index marked first and last, and a mask
    asked for exactly where some row of the block cannot see some key of
    the tile."""
    tiles_met = lambda i: ling._tiles_met(i, block, tile)
    first = lambda i: ling._first_tile(i, block, tile, window)
    want = {(i, j) for i in range(T // block)
            for j in range(first(i), tiles_met(i))}
    outer, inner, flags = fa.visits(T, block, tile, window)
    assert list(zip(outer, inner)) == sorted(want)
    outer_t, inner_t, flags_t = fa.visits(T, block, tile, window, True)
    assert list(zip(inner_t, outer_t)) == sorted(
        want, key=lambda p: (p[1], p[0]))
    for o, i, f in ((outer, inner, flags), (outer_t, inner_t, flags_t)):
        assert [bool(x & fa.FIRST) for x in f] == [
            n == 0 or o[n] != o[n - 1] for n in range(len(o))]
        assert [bool(x & fa.LAST) for x in f] == [
            n == len(o) - 1 or o[n] != o[n + 1] for n in range(len(o))]
    for i, j, f in zip(outer, inner, flags):
        seen = np.asarray(fa._seen(i, j, block, tile, window))
        assert bool(f & fa.MASKED) == (not seen.all())
