"""Laguna-S-2.1 (``laguna``): grouped-query softmax attention, full in
every fourth layer and over a sliding window in the others, with a head
count that changes from layer to layer, over a dense SwiGLU FFN (layer
0) and a softmax top-k expert FFN with one shared expert (the rest).

Pre-norm residual blocks, ``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``,
RMSNorm eps 1e-6, no biases, a final RMSNorm and an untied head. Layer
``l`` has ``heads[l]`` query heads over ``kv_heads`` key/value heads, a
sigmoid gate a head on the attention output, and one of two rotary
embeddings: YaRN over the first half of each head in a full layer, the
plain one over the whole head in a window layer. Every size is a keyword
argument: the published widths come from the scenario's ``model.kwargs``
(``benchmark/configs/laguna-s-2.1.json``), the defaults are a toy for
the CPU tests.

Meant to be trained as a FROZEN base under per-node adapters
(``learning/lora.py``), as Ling is, and built from Ling's parts: the
tiled :func:`~p2pfl_tpu.models.ling.causal_attention` (here with a
window and with query heads grouped over key heads), the held experts'
sorted grouped products (``ExpertFFN``, here with this model's router),
``RMSNorm``, ``DenseFFN`` and the chunked head and loss (``CausalLM``).
What is Laguna's own is in this file: the block, the two rotary
embeddings, the per-layer head counts, the gate, the router. The
equations, each assumption and what the public config leaves open are
written down in ``benchmark/reference/laguna_s.py``, the plain reference
this module is compared with.

One chip holds its share of a deployment: ``experts_held`` of the
``n_experts`` the router scores (experts ``expert_offset ..``), and a
slice of the vocabulary; what the absent experts would add is left out.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from p2pfl_tpu.models.base import register_lora_targets, register_model
from p2pfl_tpu.models.ling import (F32, HI, CausalLM, DenseFFN, ExpertFFN,
                                   RMSNorm, _dense, causal_attention)

FULL, WINDOW = "full_attention", "sliding_attention"
#: the scope of each kind of layer's score and value products: their
#: device time and their trace-time tile record are read by it
ATTN_SCOPE = {FULL: "gqa.attn", WINDOW: "swa.attn"}


def rope_inv_freq(rotary, theta):
    """``theta^(-2i / rotary)``, ``i < rotary / 2``."""
    return theta ** (-np.arange(0, rotary, 2, dtype=np.float64) / rotary)


def yarn_inv_freq(rotary, theta, factor, original, beta_fast, beta_slow):
    """YaRN's frequencies: the plain ones (``extra``) where a channel
    turns more than ``beta_fast`` times over the ``original`` positions,
    those over ``factor`` (``inter``) where it turns fewer than
    ``beta_slow`` times, a linear ramp over the channel index between."""
    extra = rope_inv_freq(rotary, theta)
    inter = extra / factor
    turns_at = lambda n: rotary * math.log(original / (2 * math.pi * n)) / (
        2 * math.log(theta))
    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), rotary - 1)
    ramp = np.clip((np.arange(rotary // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def rope_half(x, inv_freq, factor=1.0):
    """Rotary embedding over the first ``2 len(inv_freq)`` channels of
    the last axis, pairs ``(x_i, x_(i + R/2))``; the channels past them
    pass through. Cos and sin both times ``factor``. ``x`` [B, T, H, D],
    positions ``0 .. T - 1``, float32."""
    half = inv_freq.shape[0]
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] \
        * jnp.asarray(inv_freq, F32)[None, :]
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., 2 * half:]], axis=-1)


def route_softmax(x, frozen, *, top_k, scale):
    """Softmax over ALL experts, the ``top_k`` largest, weights ``scale *
    p_i / sum_chosen p_j``; no groups, no selection bias. float32
    throughout. Returns the chosen ids and weights, [N, top_k]."""
    p = jax.nn.softmax(jnp.dot(x.astype(F32), frozen["router"].astype(F32),
                               precision=HI), axis=-1)
    w, idx = jax.lax.top_k(p, top_k)
    return idx, scale * w / jnp.sum(w, axis=1, keepdims=True)


class LagunaAttention(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    window: int | None  # None: every earlier position
    inv_freq: Any  # [rotary / 2], a tuple of floats
    rope_factor: float
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        B, T, _ = x.shape
        H, G, D = self.heads, self.kv_heads, self.head_dim
        q = _dense(H * D, "attn_q", self)(x).reshape(B, T, H, D)
        k = _dense(G * D, "attn_k", self)(x).reshape(B, T, G, D)
        v = _dense(G * D, "attn_v", self)(x).reshape(B, T, G, D)
        gate = jax.nn.sigmoid(_dense(H, "attn_g", self)(x).astype(F32))
        turn = lambda a: rope_half(
            a.astype(F32), np.asarray(self.inv_freq), self.rope_factor
        ).astype(self.dtype)
        # the barriers keep what crosses attention's change of layout in
        # ``dtype``: left to itself XLA moves the float32 side of the
        # rotary embedding and of the gate across the transposes, forward
        # and on the way back (1.2 GB an array at 72 heads of 128 over 4 x
        # 8192 positions, three of them live at the round program's peak)
        pin = jax.lax.optimization_barrier
        scope = ATTN_SCOPE[FULL if self.window is None else WINDOW]
        q, k = pin(turn(q)), turn(k)
        with jax.named_scope(scope):
            o = pin(causal_attention(q, k, v, D ** -0.5, window=self.window,
                                     scope=scope, out_dtype=self.dtype))
        o = (o * gate[..., None]).astype(self.dtype).reshape(B, T, H * D)
        return _dense(x.shape[-1], "attn_o", self)(o)


class LagunaBlock(nn.Module):
    """One layer. With ``cfg["remat"]`` the attention half and the FFN
    half are each recomputed on the way back, on their own: neither's
    way back holds what the other kept (under one checkpoint a layer,
    72 heads' queries and outputs, 0.6 GB an array, stayed beside an
    expert block's float32 rows and the round program did not fit the
    chip), at one more saved ``[B, T, d]`` a layer and no more
    recomputation."""

    kind: str  # FULL | WINDOW
    sparse: bool
    heads: int
    cfg: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        kw = dict(dtype=c["dtype"], param_dtype=c["param_dtype"])

        def attend(block, x):
            h = RMSNorm(c["eps"], name="attn_norm", parent=block, **kw)(x)
            return x + LagunaAttention(
                self.heads, c["kv_heads"], c["head_dim"],
                c["window"] if self.kind == WINDOW else None,
                *c["rope"][self.kind], name="attn", parent=block, **kw)(h)

        def feed(block, x):
            h = RMSNorm(c["eps"], name="ffn_norm", parent=block, **kw)(x)
            if not self.sparse:
                return x + DenseFFN(c["dense_width"], name="ffn",
                                    parent=block, **kw)(h), None
            y, stats = ExpertFFN(
                c["n_experts"], c["experts_held"], c["expert_offset"],
                c["expert_width"], c["shared_width"], c["top_k"],
                router=functools.partial(route_softmax, top_k=c["top_k"],
                                         scale=c["route_scale"]),
                name="moe", parent=block, **kw)(h)
            return x + y, stats

        if c["remat"]:
            attend, feed = nn.remat(attend), nn.remat(feed)
        return feed(self, attend(self, x))


class LagunaLM(CausalLM):
    """Laguna-S-2.1: one layer an entry of ``layer_types``, its FFN and
    head count by the same index; every size a keyword argument."""

    vocab: int = 64
    hidden: int = 32
    layer_types: tuple = (FULL, WINDOW, WINDOW, FULL)
    mlp_layer_types: tuple = ("dense", "sparse", "sparse", "sparse")
    heads: tuple = (4, 6, 6, 4)  # query heads, a layer
    kv_heads: int = 2
    head_dim: int = 16
    window: int = 8
    # full layers: YaRN over ``rotary_full`` of each head
    theta_full: float = 500000.0
    rotary_full: float = 0.5
    yarn_factor: float = 128.0
    yarn_original: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4852030263919618
    # window layers: the plain embedding over ``rotary_window`` of it
    theta_window: float = 10000.0
    rotary_window: float = 1.0
    dense_width: int = 48
    n_experts: int = 16
    experts_held: int = 4
    expert_offset: int = 0
    expert_width: int = 8
    shared_width: int = 8
    top_k: int = 4
    route_scale: float = 2.5
    eps: float = 1e-6
    loss_chunk: int = 1024
    remat: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def setup(self):
        if not len(self.layer_types) == len(self.mlp_layer_types) \
                == len(self.heads):
            raise ValueError("layer_types, mlp_layer_types and heads give "
                             "one entry a layer")
        cfg = {f: getattr(self, f) for f in (
            "kv_heads", "head_dim", "window", "dense_width", "n_experts",
            "experts_held", "expert_offset", "expert_width", "shared_width",
            "top_k", "route_scale", "eps", "remat", "dtype", "param_dtype")}
        rotary = lambda share: int(self.head_dim * share) // 2 * 2
        cfg["rope"] = {
            FULL: (tuple(yarn_inv_freq(
                rotary(self.rotary_full), self.theta_full, self.yarn_factor,
                self.yarn_original, self.yarn_beta_fast, self.yarn_beta_slow)),
                self.yarn_attention_factor),
            WINDOW: (tuple(rope_inv_freq(rotary(self.rotary_window),
                                         self.theta_window)), 1.0)}
        self.setup_ends()
        self.blocks = [
            LagunaBlock(kind, mlp == "sparse", heads, cfg, name=f"layer_{i}")
            for i, (kind, mlp, heads) in enumerate(zip(
                self.layer_types, self.mlp_layer_types, self.heads))]


@register_model("laguna-s-2.1", "laguna")
def _laguna(num_classes: int | None = None, **kw) -> LagunaLM:
    del num_classes  # the vocabulary is the model's own
    # a scenario file gives the per-layer lists as lists
    return LagunaLM(**{k: tuple(v) if isinstance(v, list) else v
                       for k, v in kw.items()})


# adapters ride on attention's projections q, k, v, o of every layer;
# every kernel is a plain [d_in, d_out]
register_lora_targets(
    "laguna-s-2.1", "laguna",
    default=("attn_q", "attn_k", "attn_v", "attn_o"),
)
