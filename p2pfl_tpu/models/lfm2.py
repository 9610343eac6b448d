"""LFM2-8B-A1B (``lfm2_moe``): doubly gated short convolutions among
grouped-query softmax attention layers, over a dense SwiGLU FFN (the
leading layers) and a biased sigmoid top-k expert FFN with NO shared
expert (the rest), under a head tied to the embedding.

Pre-norm residual blocks, ``h = x + Op(RMSNorm(x)); y = h +
FFN(RMSNorm(h))``, RMSNorm eps 1e-5, no biases, a final RMSNorm, logits
``h E^T`` over the embedding's own matrix. Layer ``l`` is a convolution
layer where ``layer_types[l]`` is ``conv`` and an attention layer where
it is ``full_attention``; the first ``dense_layers`` layers have the
dense FFN. Every size is a keyword argument: the published widths come
from the scenario's ``model.kwargs``
(``benchmark/configs/lfm2-8b-a1b.json``), the defaults are a toy for the
CPU tests.

- Convolution layer: ``[B | C | X] = x W_in``; ``u = B * X``; a
  depthwise causal convolution of ``taps`` taps over ``u``, no bias, no
  activation; ``out = (C * conv(u)) W_out``. No softmax, no state beyond
  ``taps - 1`` positions.
- Attention layer: ``heads`` query heads over ``kv_heads`` key/value
  heads, an RMSNorm with a learned scale on every query and key head
  before the rotary embedding (over the whole head, half-split pairs),
  causal softmax; no gate, no window.

Meant to be trained as a FROZEN base under per-node adapters
(``learning/lora.py``), as Ling and Laguna are, and built from their
parts: the one causal convolution (:func:`~p2pfl_tpu.models.ling
.causal_conv`), the tiled :func:`~p2pfl_tpu.models.ling.causal_attention`
(here at heads of 64), the held experts' sorted grouped products
(``ExpertFFN`` with :func:`~p2pfl_tpu.models.ling.route` over ONE group,
every expert held, ``shared_width`` 0), ``rope_half``, ``RMSNorm``,
``DenseFFN`` and the chunked head and loss (``CausalLM`` with
``tie_head``). What is LFM2's own is in this file: the short-convolution
mixer, the attention with its two head norms, the block, the layer
list. The equations and each assumption are written down in
``benchmark/reference/lfm2_moe.py``, the plain reference this module is
compared with.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from p2pfl_tpu.models.base import register_lora_targets, register_model
from p2pfl_tpu.models.laguna import rope_half, rope_inv_freq
from p2pfl_tpu.models.ling import (F32, CausalLM, DenseFFN, ExpertFFN,
                                   RMSNorm, _dense, causal_attention,
                                   causal_conv, rms_norm)

CONV, FULL = "conv", "full_attention"


class ShortConvMixer(nn.Module):
    """``(C * conv(B * X)) W_out`` with ``[B | C | X] = x W_in``. The two
    projections are per node under the round's ``vmap`` (adapters ride
    on them); the two products and the convolution between them, scope
    ``lfm2.conv``, are float32 from the projection's output to the cast
    before ``W_out`` (one elementwise chain: what crosses the chip's
    memory is ``dtype`` on both sides)."""

    taps: int = 3
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        bcx = _dense(3 * d, "conv_in", self)(x)
        taps = self.param(
            "conv_taps", nn.initializers.normal(1.0 / math.sqrt(self.taps)),
            (self.taps, d), self.param_dtype)
        with jax.named_scope("lfm2.conv"):
            b, c, u = jnp.split(bcx.astype(F32), 3, axis=-1)
            y = (c * causal_conv(b * u, taps.astype(F32))).astype(self.dtype)
        return _dense(d, "conv_out", self)(y)


class Lfm2Attention(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    inv_freq: Any  # [head_dim / 2], a tuple of floats
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        B, T, _ = x.shape
        H, G, D = self.heads, self.kv_heads, self.head_dim
        q = _dense(H * D, "attn_q", self)(x).reshape(B, T, H, D)
        k = _dense(G * D, "attn_k", self)(x).reshape(B, T, G, D)
        v = _dense(G * D, "attn_v", self)(x).reshape(B, T, G, D)
        scale = lambda name: self.param(name, nn.initializers.ones, (D,),
                                        self.param_dtype)
        # a norm a head, then the rotary embedding, both float32
        turn = lambda a, s: rope_half(
            rms_norm(a, s, self.eps), np.asarray(self.inv_freq)
        ).astype(self.dtype)
        # the barriers keep what crosses attention's change of layout in
        # ``dtype``, as in ``LagunaAttention``
        pin = jax.lax.optimization_barrier
        q, k = pin(turn(q, scale("q_norm"))), turn(k, scale("k_norm"))
        with jax.named_scope("gqa.attn"):
            o = pin(causal_attention(q, k, v, D ** -0.5, scope="gqa.attn",
                                     out_dtype=self.dtype))
        return _dense(x.shape[-1], "attn_o", self)(o.reshape(B, T, H * D))


class Lfm2Block(nn.Module):
    """One layer. With ``cfg["remat"]`` the operator half and the FFN
    half are each recomputed on the way back, on their own, as
    ``LagunaBlock``'s are: neither's way back holds what the other
    kept."""

    kind: str  # CONV | FULL
    sparse: bool
    cfg: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        kw = dict(dtype=c["dtype"], param_dtype=c["param_dtype"])

        def mix(block, x):
            h = RMSNorm(c["eps"], name="operator_norm", parent=block, **kw)(x)
            if self.kind == CONV:
                return x + ShortConvMixer(c["taps"], name="conv",
                                          parent=block, **kw)(h)
            return x + Lfm2Attention(
                c["heads"], c["kv_heads"], c["head_dim"], c["inv_freq"],
                c["eps"], name="attn", parent=block, **kw)(h)

        def feed(block, x):
            h = RMSNorm(c["eps"], name="ffn_norm", parent=block, **kw)(x)
            if not self.sparse:
                return x + DenseFFN(c["dense_width"], name="ffn",
                                    parent=block, **kw)(h), None
            # the plain biased sigmoid top-k: ``route`` over one group
            y, stats = ExpertFFN(
                c["n_experts"], c["experts_held"], c["expert_offset"],
                c["expert_width"], 0, c["top_k"], 1, 1, c["route_scale"],
                name="moe", parent=block, **kw)(h)
            return x + y, stats

        if c["remat"]:
            mix, feed = nn.remat(mix), nn.remat(feed)
        return feed(self, mix(self, x))


class Lfm2LM(CausalLM):
    """LFM2-8B-A1B: one layer an entry of ``layer_types``, the first
    ``dense_layers`` of them over the dense FFN; every size a keyword
    argument."""

    vocab: int = 64
    hidden: int = 32
    layer_types: tuple = (CONV, FULL, CONV, CONV)
    dense_layers: int = 1
    heads: int = 4
    kv_heads: int = 2
    head_dim: int = 8
    theta: float = 1e6
    taps: int = 3
    dense_width: int = 48
    n_experts: int = 8
    experts_held: int = 8
    expert_offset: int = 0
    expert_width: int = 8
    top_k: int = 2
    route_scale: float = 1.0
    eps: float = 1e-5
    loss_chunk: int = 128
    remat: bool = True
    tie_head: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def setup(self):
        unknown = set(self.layer_types) - {CONV, FULL}
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}; a layer "
                             f"is {CONV!r} or {FULL!r}")
        cfg = {f: getattr(self, f) for f in (
            "heads", "kv_heads", "head_dim", "taps", "dense_width",
            "n_experts", "experts_held", "expert_offset", "expert_width",
            "top_k", "route_scale", "eps", "remat", "dtype", "param_dtype")}
        cfg["inv_freq"] = tuple(rope_inv_freq(self.head_dim, self.theta))
        self.setup_ends()
        self.blocks = [
            Lfm2Block(kind, i >= self.dense_layers, cfg, name=f"layer_{i}")
            for i, kind in enumerate(self.layer_types)]


@register_model("lfm2-8b-a1b", "lfm2_moe")
def _lfm2(num_classes: int | None = None, **kw) -> Lfm2LM:
    del num_classes  # the vocabulary is the model's own
    # a scenario file gives the layer list as a list
    return Lfm2LM(**{k: tuple(v) if isinstance(v, list) else v
                     for k, v in kw.items()})


# adapters ride on the convolution layers' two projections and on
# attention's q, k, v, o; every kernel is a plain [d_in, d_out]
register_lora_targets(
    "lfm2-8b-a1b", "lfm2_moe",
    default=("conv_in", "conv_out", "attn_q", "attn_k", "attn_v", "attn_o"),
)
