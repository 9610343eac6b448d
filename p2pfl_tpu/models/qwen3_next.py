"""Qwen3-Next-80B-A3B (``qwen3_next``): Gated DeltaNet layers among gated
grouped-query attention layers, every layer over a softmax top-k expert
FFN with one shared expert under a sigmoid gate.

Pre-norm residual blocks, ``x += Mixer(N0(x)); x += MoE(N0(x))``, with
the zero-centred RMSNorm ``N0(x) = x / rms(x) * (1 + w)`` (eps 1e-6), no
biases, a final ``N0`` and an untied head. Layer ``i`` of the published
stack (0-indexed) is an attention layer where ``(i + 1) %
full_attention_interval == 0`` and a Gated DeltaNet layer otherwise.
Every size is a keyword argument: the published widths come from the
scenario's ``model.kwargs`` (``benchmark/configs/qwen3-next-80b-a3b.json``),
the defaults are a toy for the CPU tests.

- Gated DeltaNet (:class:`GatedDeltaNet`): ``[q, k, v, z] = x W_qkvz``
  (q, k ``key_heads`` heads, v, z ``value_heads`` heads, all of
  ``linear_head_dim``), ``[b, a] = x W_ba``; q, k, v through one causal
  depthwise convolution of ``conv`` taps over their concatenation and
  SiLU; q and k of unit length a head, q scaled by ``head_dim^-0.5``;
  ``beta = sigmoid(b)``, ``g = -exp(A_log_h) softplus(a + dt_bias_h)``,
  ONE log-decay a value head and position, unbounded below; value head
  ``h`` reads key head ``h // (value_heads / key_heads)``; the delta
  rule; the output ``(RMSNorm(o) * w * SiLU(z)) W_out``, a plain norm
  weight of ``linear_head_dim`` shared by the heads.
- Gated attention (:class:`GatedAttention`): ``x W_q`` gives each head a
  query and an output gate of ``head_dim``; ``heads`` query heads over
  ``kv_heads`` key/value heads; ``N0`` on every query and key head; the
  rotate-half rotary embedding over the first ``partial_rotary`` of each
  head; causal softmax; ``(o * sigmoid(gate)) W_o``.
- Experts: softmax over all ``n_experts``, the ``top_k`` largest,
  renormalised; SwiGLU experts and a SwiGLU shared expert scaled by
  ``sigmoid(x w_sg)``.

Meant to be trained as a FROZEN base under per-node adapters
(``learning/lora.py``), as Ling, Laguna, LFM2 and Kimi Linear are, and
built from their parts: ``head_blocks``, ``causal_conv_silu``,
``rms_norm`` and :func:`~p2pfl_tpu.models.ling.kda_heads` with a decay a
head (its scalar form: ``K K^T`` and ``Q K^T`` a key head over whole
chunks, times the pairwise decays; scope ``gdn.scan``),
``causal_attention`` at heads of 256 (on the TPU the fused kernels of
``ops/flash_attention.py``, on the CPU the tiled form; scope
``gattn.attn``), ``ExpertFFN`` with Laguna's ``route_softmax`` and a gate
on its shared expert, ``rope_half`` and ``CausalLM``. What is Qwen3-Next's
own is in this file: the two mixers, ``N0``, the block and the layer
list. The equations and each assumption are written down in
``benchmark/reference/qwen3_next.py``, the plain reference this module
is compared with.

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
from p2pfl_tpu.models.kimi_linear import _log_uniform
from p2pfl_tpu.models.laguna import rope_half, rope_inv_freq, route_softmax
from p2pfl_tpu.models.ling import (F32, CausalLM, ExpertFFN, _dense,
                                   causal_attention, causal_conv_silu,
                                   head_blocks, kda_heads, rms_norm)

GDN, ATTN = "linear_attention", "full_attention"
#: the zero-centred norms' weights are seeded, not 0: a trained model's
#: are not 0, and at 0 a norm that forgot its ``1 +`` would still agree
#: with one that did not wherever the weight is left out
N0_INIT = nn.initializers.normal(0.1)


def zero_centred_norm(x, w, eps):
    """``x / rms(x) * (1 + w)``, float32."""
    return rms_norm(x, 1.0 + w.astype(F32), eps)


class ZeroCentredRMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", N0_INIT, (x.shape[-1],), self.param_dtype)
        return zero_centred_norm(x, w, self.eps).astype(self.dtype)


def unit(t):
    """Of unit length over the last axis (1e-6 under the root), float32."""
    t = t.astype(F32)
    return t * jax.lax.rsqrt(jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)


class GatedDeltaNet(nn.Module):
    """The projections ``in_proj_qkvz`` and ``out_proj`` are per node under
    the round's ``vmap`` (adapters ride on them), ``in_proj_ba`` is the
    base's; everything between them runs on a forward pass ONCE over the
    rows of all nodes, ``head_block`` key heads (and their value heads)
    at a time (``head_blocks``), as Ling's and Kimi Linear's mixers do.
    Inside a block the value heads lie under their key head, ``[B, T,
    key heads, group, ..]``, so that one cut of the head axis holds
    both."""

    key_heads: int
    value_heads: int
    head_dim: int
    conv: int = 4
    chunk: int = 64
    head_block: int = 4
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        B, T, _ = x.shape
        Hk, H, K = self.key_heads, self.value_heads, self.head_dim
        group = H // Hk
        qkvz = _dense(2 * (Hk + H) * K, "in_proj_qkvz", self)(x)
        q, k, v, z = jnp.split(qkvz, np.cumsum([Hk * K, Hk * K, H * K]),
                               axis=-1)
        b, a = jnp.split(_dense(2 * H, "in_proj_ba", self)(x), 2, axis=-1)
        taps = self.param(
            "conv1d", nn.initializers.normal(1.0 / math.sqrt(self.conv)),
            (self.conv, 2 * Hk * K + H * K), self.param_dtype
        ).astype(self.dtype)
        a_log = self.param("A_log", _log_uniform(0.0, 16.0), (H,),
                           self.param_dtype)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (H,),
                             self.param_dtype)
        scale = self.param("norm", nn.initializers.ones, (K,),
                           self.param_dtype)
        keyed = lambda y, lead: y.reshape(lead + (Hk, K))
        valued = lambda y, lead, w: y.reshape(lead + (Hk, group) + w)
        tq, tk, tv = jnp.split(taps, np.cumsum([Hk * K, Hk * K]), axis=-1)

        def heads(q, k, v, z, a, b, tq, tk, tv, a_log, dt_bias, scale):
            # everything between the projections, a block of key heads
            # and their value heads at a time: what is float32 (the unit
            # q and k, the decays, the state, the output before its norm
            # and gate) is made and used up here
            rows, hb = q.shape[:2], q.shape[2]
            with jax.named_scope("gdn.conv"):
                conv = lambda y, t: causal_conv_silu(
                    y.reshape(rows + (-1,)), t.reshape(self.conv, -1)
                ).reshape(y.shape)
                q, k, v = conv(q, tq), conv(k, tk), conv(v, tv)
                q, k = unit(q) * K ** -0.5, unit(k)
            g = -jnp.exp(a_log.astype(F32)) * jax.nn.softplus(
                a.astype(F32) + dt_bias.astype(F32))
            beta = jax.nn.sigmoid(b.astype(F32))
            flat = lambda y: y.reshape(rows + (hb * group,) + y.shape[4:])
            with jax.named_scope("gdn.scan"):
                o = kda_heads(q, k, flat(v), flat(g), flat(beta),
                              chunk=self.chunk, dtype=self.dtype,
                              scope="gdn.scan")
            o = rms_norm(o.reshape(v.shape), scale, self.eps) \
                * jax.nn.silu(z.astype(F32))
            return o.astype(self.dtype).reshape(rows + (hb, group * K))

        # the base's tensors with a head axis, to be cut like the rows
        o = head_blocks(
            heads, self.head_block,
            (keyed(q, (B, T)), keyed(k, (B, T)), valued(v, (B, T), (K,)),
             valued(z, (B, T), (K,)), valued(a, (B, T), ()),
             valued(b, (B, T), ())),
            (keyed(tq, (1, self.conv)), keyed(tk, (1, self.conv)),
             valued(tv, (1, self.conv), (K,)), valued(a_log, (1, 1), ()),
             valued(dt_bias, (1, 1), ()),
             jnp.broadcast_to(scale, (1, 1, Hk, group, K))))
        return _dense(x.shape[-1], "out_proj", self)(o.reshape(B, T, H * K))


class GatedAttention(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    inv_freq: Any  # [rotary / 2], a tuple of floats
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        B, T, _ = x.shape
        H, G, D = self.heads, self.kv_heads, self.head_dim
        # a head's query, then its gate, as the published projection lays
        # them out
        q, gate = jnp.split(_dense(2 * H * D, "q_proj", self)(x).reshape(
            B, T, H, 2 * D), 2, axis=-1)
        k = _dense(G * D, "k_proj", self)(x).reshape(B, T, G, D)
        v = _dense(G * D, "v_proj", self)(x).reshape(B, T, G, D)
        weight = lambda name: self.param(name, N0_INIT, (D,), self.param_dtype)
        # N0 a head, then the rotary embedding, both float32
        turn = lambda a, w: rope_half(
            zero_centred_norm(a, w, self.eps), np.asarray(self.inv_freq)
        ).astype(self.dtype)
        # the barriers keep what crosses attention's change of layout in
        # ``dtype``, as in ``LagunaAttention``
        pin = jax.lax.optimization_barrier
        q, k = pin(turn(q, weight("q_norm"))), turn(k, weight("k_norm"))
        with jax.named_scope("gattn.attn"):
            o = pin(causal_attention(q, k, v, D ** -0.5, scope="gattn.attn",
                                     out_dtype=self.dtype))
        o = (o * jax.nn.sigmoid(gate.astype(F32))).astype(self.dtype)
        return _dense(x.shape[-1], "o_proj", self)(o.reshape(B, T, H * D))


class Qwen3NextBlock(nn.Module):
    """One layer; returns ``(x, the expert layer's counters)``. Under
    ``cfg["remat"]`` the whole layer is recomputed on the way back, as
    Kimi Linear's is."""

    kind: str  # GDN | ATTN
    cfg: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        kw = dict(dtype=c["dtype"], param_dtype=c["param_dtype"])
        h = ZeroCentredRMSNorm(c["eps"], name="input_norm", **kw)(x)
        if self.kind == ATTN:
            x = x + GatedAttention(
                c["heads"], c["kv_heads"], c["head_dim"], c["inv_freq"],
                c["eps"], name="attn", **kw)(h)
        else:
            x = x + GatedDeltaNet(
                c["key_heads"], c["value_heads"], c["linear_head_dim"],
                c["conv"], c["chunk"], c["head_block"], c["eps"], name="gdn",
                **kw)(h)
        h = ZeroCentredRMSNorm(c["eps"], name="post_attention_norm", **kw)(x)
        y, stats = ExpertFFN(
            c["n_experts"], c["experts_held"], c["expert_offset"],
            c["expert_width"], c["shared_width"], c["top_k"],
            router=functools.partial(route_softmax, top_k=c["top_k"],
                                     scale=1.0),
            shared_gate=True, name="moe", **kw)(h)
        return x + y, stats


class Qwen3NextLM(CausalLM):
    """Qwen3-Next-80B-A3B: ``layers`` published layers from
    ``first_layer`` on (0-indexed), every size a keyword argument."""

    vocab: int = 64
    hidden: int = 32
    layers: int = 4  # layers kept, taken from ``first_layer`` on
    first_layer: int = 0  # index in the published stack of the first
    full_attention_interval: int = 4
    key_heads: int = 2  # Gated DeltaNet's q and k heads
    value_heads: int = 4  # its v and z heads
    linear_head_dim: int = 16
    conv: int = 4
    chunk: int = 64
    head_block: int = 1  # key heads a block of the delta rule
    heads: int = 4  # attention's query heads
    kv_heads: int = 2
    head_dim: int = 16
    partial_rotary: float = 0.25
    theta: float = 1e7
    n_experts: int = 16
    experts_held: int = 4
    expert_offset: int = 0
    expert_width: int = 8
    shared_width: int = 8
    top_k: int = 4
    eps: float = 1e-6
    loss_chunk: int = 1024
    remat: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def setup(self):
        cfg = {f: getattr(self, f) for f in (
            "key_heads", "value_heads", "linear_head_dim", "conv", "chunk",
            "head_block", "heads", "kv_heads", "head_dim", "n_experts",
            "experts_held", "expert_offset", "expert_width", "shared_width",
            "top_k", "eps", "dtype", "param_dtype")}
        cfg["inv_freq"] = tuple(rope_inv_freq(
            int(self.head_dim * self.partial_rotary), self.theta))
        block = nn.remat(Qwen3NextBlock) if self.remat else Qwen3NextBlock
        self.setup_ends(norm=ZeroCentredRMSNorm)
        self.blocks = [
            block(ATTN if (i + 1) % self.full_attention_interval == 0
                  else GDN, cfg, name=f"layer_{i}")
            for i in range(self.first_layer, self.first_layer + self.layers)]


@register_model("qwen3-next-80b-a3b", "qwen3_next")
def _qwen3_next(num_classes: int | None = None, **kw) -> Qwen3NextLM:
    del num_classes  # the vocabulary is the model's own
    return Qwen3NextLM(**kw)


# adapters ride on the mixers' published projections: Gated DeltaNet's
# in_proj_qkvz and out_proj, attention's q (query and gate), k, v, o;
# every kernel is a plain [d_in, d_out]
register_lora_targets(
    "qwen3-next-80b-a3b", "qwen3_next",
    default=("in_proj_qkvz", "out_proj", "q_proj", "k_proj", "v_proj",
             "o_proj"),
)
