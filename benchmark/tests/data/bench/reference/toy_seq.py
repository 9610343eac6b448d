"""Fixture: a toy next-token model, for what the federation reference
asks of a sequence configuration's module. Token ids [B, T] -> an
embedding, one causal mixing layer over the positions, logits at every
position [B, T, V]. A label a position; ``IGNORE`` marks a position that
bears none (padding, a document's last token). The loss is the module's
own: a row's cross-entropy is the mean over its labelled positions, a
batch's the mean over its kept rows.
"""

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
V, T, D = 11, 6, 8
IGNORE = -1
SHAPES = {"embed": (V, D), "mix": (T, T), "out_w": (D, V), "out_b": (V,)}


def init(key):
    out = {}
    for i, (name, shape) in enumerate(sorted(SHAPES.items())):
        if name == "out_b":
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32
            ) / math.sqrt(shape[0])
    return out


def forward(p, x, q=lambda a: a):
    h = p["embed"][x]
    causal = jnp.tril(jnp.ones((T, T), jnp.float32))
    h = h + jnp.einsum("ts,bsd->btd", q(p["mix"] * causal), q(h), precision=HI)
    return jnp.einsum("btd,dv->btv", q(jnp.tanh(h)), q(p["out_w"]),
                      precision=HI) + p["out_b"]


def loss(logits, y, mask):
    labelled = (y != IGNORE).astype(jnp.float32)
    picked = jnp.take_along_axis(
        logits, jnp.maximum(y, 0)[..., None], axis=-1)[..., 0]
    ce = (jax.nn.logsumexp(logits, axis=-1) - picked) * labelled
    rows = jnp.sum(ce, axis=-1) / jnp.maximum(jnp.sum(labelled, axis=-1), 1.0)
    kept = mask.astype(jnp.float32)
    return jnp.sum(rows * kept) / jnp.maximum(jnp.sum(kept), 1.0)
