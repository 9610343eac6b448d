"""Plain reference of a DeiT-Ti-width vision transformer (DeiT,
arXiv:2012.12877, Table 1: dim 192, 12 layers, 3 heads, MLP 768) on
32x32 inputs at patch 4: pre-LayerNorm blocks, learned position
embedding, mean pooling of the 64 tokens (no class token), linear head.
float32, ``highest`` matmul precision, no kernels, no remat.

Departures from the paper, all as the configuration file states them:
no class or distillation token (mean pooling), GELU in its tanh form,
LayerNorm epsilon 1e-6.

Imports nothing of the program. ``q`` is applied to every matmul
operand (see ``femnist_cnn``). Per-layer weights carry a leading
[layers] axis and the blocks run under one ``lax.scan``.
"""

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
L, D, H, K, F, T, C = 12, 192, 3, 64, 768, 64, 10

SHAPES = {
    "patch_w": (4, 4, 3, D), "patch_b": (D,), "pos": (1, T, D),
    "ln1_s": (L, D), "ln1_b": (L, D), "ln2_s": (L, D), "ln2_b": (L, D),
    "q_w": (L, D, H, K), "q_b": (L, H, K), "k_w": (L, D, H, K),
    "k_b": (L, H, K), "v_w": (L, D, H, K), "v_b": (L, H, K),
    "o_w": (L, H, K, D), "o_b": (L, D),
    "mlp1_w": (L, D, F), "mlp1_b": (L, F),
    "mlp2_w": (L, F, D), "mlp2_b": (L, D),
    "lnf_s": (D,), "lnf_b": (D,), "head_w": (D, C), "head_b": (C,),
}
_FAN_IN = {"patch_w": 48, "q_w": D, "k_w": D, "v_w": D, "o_w": D,
           "mlp1_w": D, "mlp2_w": F, "head_w": D}
_LAYER_KEYS = [k for k, s in SHAPES.items() if s[0] == L and len(s) > 1
               and k not in ("pos",)]


def init(key, sizes=None):
    out = {}
    for i, (name, shape) in enumerate(sorted(SHAPES.items())):
        if name in _FAN_IN:
            out[name] = jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32
            ) / math.sqrt(_FAN_IN[name])
        elif name == "pos":
            out[name] = 0.02 * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        elif name.endswith("_s"):
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = jnp.zeros(shape, jnp.float32)
    return out


def _ln(x, s, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * s + b


def _block(x, w, q):
    y = _ln(x, w["ln1_s"], w["ln1_b"])
    qq = jnp.einsum("btd,dhk->bthk", q(y), q(w["q_w"]), precision=HI) + w["q_b"]
    kk = jnp.einsum("btd,dhk->bthk", q(y), q(w["k_w"]), precision=HI) + w["k_b"]
    vv = jnp.einsum("btd,dhk->bthk", q(y), q(w["v_w"]), precision=HI) + w["v_b"]
    s = jnp.einsum("bqhk,bshk->bhqs", q(qq), q(kk), precision=HI) / math.sqrt(K)
    a = jax.nn.softmax(s, axis=-1)
    y = jnp.einsum("bhqs,bshk->bqhk", q(a), q(vv), precision=HI)
    y = jnp.einsum("bqhk,hkd->bqd", q(y), q(w["o_w"]), precision=HI) + w["o_b"]
    x = x + y
    y = _ln(x, w["ln2_s"], w["ln2_b"])
    y = jnp.einsum("btd,df->btf", q(y), q(w["mlp1_w"]), precision=HI) + w["mlp1_b"]
    y = jax.nn.gelu(y, approximate=True)
    y = jnp.einsum("btf,fd->btd", q(y), q(w["mlp2_w"]), precision=HI) + w["mlp2_b"]
    return x + y


def forward(p, x, q=lambda a: a):
    """x: [B, 32, 32, 3] float32 -> logits [B, 10] float32."""
    x = jax.lax.conv_general_dilated(
        q(x), q(p["patch_w"]), (4, 4), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
    x = x + p["patch_b"]
    x = x.reshape(x.shape[0], -1, D) + p["pos"]
    layers = {k: p[k] for k in _LAYER_KEYS}
    x, _ = jax.lax.scan(lambda c, w: (_block(c, w, q), None), x, layers)
    x = _ln(x, p["lnf_s"], p["lnf_b"])
    x = jnp.mean(x, axis=1)
    return jnp.dot(q(x), q(p["head_w"]), precision=HI) + p["head_b"]
