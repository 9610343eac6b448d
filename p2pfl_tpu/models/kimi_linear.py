"""Kimi-Linear-48B-A3B (``kimi_linear``): Kimi Delta Attention layers
among NoPE latent-attention layers, over a dense SwiGLU FFN (the leading
layer) and a biased sigmoid top-k expert FFN with one shared expert (the
rest).

Pre-norm residual blocks, ``x += Mixer(RMSNorm(x)); x += FFN(RMSNorm(x))``,
RMSNorm eps 1e-5, no biases but the output gate's, a final RMSNorm and an
untied head. The layer list comes from the config's two 1-indexed lists:
published layer ``l`` has a KDA mixer where ``l`` is in ``kda_layers`` and
an MLA mixer where it is in ``full_attn_layers``; a dense FFN where ``l <=
first_dense`` and the expert FFN otherwise. Every size is a keyword
argument: the published widths come from the scenario's ``model.kwargs``
(``benchmark/configs/kimi-linear-48b-a3b.json``), the defaults are a toy
for the CPU tests.

- KDA (:class:`KimiDeltaMixer`): q, k, v through a causal depthwise
  convolution of 4 and SiLU, q and k of unit length a head (q scaled by
  ``head_dim^-0.5``); the log-decay ``g = -exp(A_log_h) softplus(x W_fa
  W_fb + dt_bias)`` a channel, low rank and UNBOUNDED below; ``beta =
  sigmoid(x W_b)`` a head; the delta rule; the output ``(RMSNorm(o) *
  sigmoid(x W_ga W_gb + b_g)) W_o``, one gate a channel.
- MLA: Ling's ``MLAMixer`` with no rotary embedding (``mla_use_nope``: the
  64 "rope" dimensions enter the score as plain dimensions), no q/k norm
  and no output gate.

Meant to be trained as a FROZEN base under per-node adapters
(``learning/lora.py``), as Ling, Laguna and LFM2 are, and built from
Ling's parts: ``head_blocks``, ``causal_conv_silu``, ``rms_norm`` and
:func:`~p2pfl_tpu.models.ling.kda_heads` with no floor (its form that is
exact for any decay: the diagonal sub-blocks from pairwise differences,
scope ``kda.diag``), ``MLAMixer`` over ``causal_attention``,
``ExpertFFN`` with :func:`~p2pfl_tpu.models.ling.route` over one group,
``DenseFFN``, ``RMSNorm`` and ``CausalLM``. What is Kimi's own is in this
file: the KDA mixer with its two low-rank gates, the block, the layer
list and the counter ``kda.deep_decay_share``. The equations and each
assumption are written down in ``benchmark/reference/kimi_linear.py``, the
plain reference this module is compared with.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from p2pfl_tpu.models.base import register_lora_targets, register_model
from p2pfl_tpu.models.ling import (CLIP, F32, SUB, CausalLM, DenseFFN,
                                   ExpertFFN, MLAMixer, RMSNorm, _dense,
                                   causal_conv_silu, head_blocks, kda_heads,
                                   rms_norm)


def decay(fa, a_log, dt_bias):
    """The log-decay a channel, float32: ``-exp(A_log_h) softplus(fa +
    dt_bias)``; ``fa`` the low-rank pre-activation [.., H, K]."""
    return -jnp.exp(a_log.astype(F32)) * jax.nn.softplus(
        fa.astype(F32) + dt_bias.astype(F32))


def deep_decay_share(g):
    """The share, in %, of (sub-chunk, head, channel) of ``g`` [B, T, H,
    K] whose decay over a sub-chunk of ``SUB`` positions is deeper than
    ``CLIP``: where the bounded form of ``kda_heads`` would be wrong."""
    B, T, H, K = g.shape
    g = jnp.pad(g, ((0, 0), (0, -T % SUB), (0, 0), (0, 0)))
    over = jnp.sum(g.reshape(B, -1, SUB, H, K), axis=2) < -CLIP
    return 100.0 * jnp.mean(over.astype(F32))


def _log_uniform(low, high):
    def init(key, shape, dtype):
        return jnp.log(jax.random.uniform(key, shape, F32, low, high)
                       ).astype(dtype)
    return init


class KimiDeltaMixer(nn.Module):
    """Kimi Delta Attention. The projections q, k, v and o are per node
    under the round's ``vmap`` (adapters ride on them); the gates'
    low-rank projections are the base's; everything between them runs on
    a forward pass ONCE over the rows of all nodes, a block of 4 heads at
    a time (``head_blocks``), as Ling's mixer does. Returns the output and
    ``kda.deep_decay_share`` of this layer's decays."""

    heads: int
    head_dim: int
    conv: int = 4
    chunk: int = 64
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        B, T, _ = x.shape
        H, K = self.heads, self.head_dim
        taps_init = nn.initializers.normal(1.0 / math.sqrt(self.conv))
        heads_of = lambda y: y.reshape(B, T, H, K)
        qkv = [heads_of(_dense(H * K, f"kda_{n}", self)(x)) for n in "qkv"]
        taps = [self.param(f"{n}_conv", taps_init, (self.conv, H * K),
                           self.param_dtype).astype(self.dtype)
                .reshape(1, self.conv, H, K) for n in "qkv"]
        fa = heads_of(_dense(H * K, "kda_fb", self)(_dense(K, "kda_fa", self)(x)))
        a_log = self.param("A_log", _log_uniform(1.0, 16.0), (H,),
                           self.param_dtype)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (H * K,),
                             self.param_dtype)
        beta = jax.nn.sigmoid(_dense(H, "kda_b", self)(x).astype(F32))
        gate = heads_of(nn.Dense(
            H * K, use_bias=True, dtype=self.dtype,
            param_dtype=self.param_dtype, name="kda_gb",
            kernel_init=nn.initializers.lecun_normal())(
            _dense(K, "kda_ga", self)(x)))
        scale = self.param("o_norm", nn.initializers.ones, (K,),
                           self.param_dtype)
        a_log = a_log.reshape(1, 1, H, 1)
        dt_bias = dt_bias.reshape(1, 1, H, K)

        def heads(q, k, v, fa, beta, gate, tq, tk, tv, a_log, dt_bias, scale):
            # everything between the projections and the output
            # projection, a block of heads at a time, as in Ling's mixer
            with jax.named_scope("kda.conv"):
                conv = lambda y, t: causal_conv_silu(
                    y.reshape(y.shape[:2] + (-1,)), t.reshape(self.conv, -1)
                ).reshape(y.shape)
                unit = lambda t: t.astype(F32) * jax.lax.rsqrt(jnp.sum(
                    jnp.square(t.astype(F32)), -1, keepdims=True) + 1e-6)
                q, k, v = conv(q, tq), conv(k, tk), conv(v, tv)
                q, k = unit(q) * K ** -0.5, unit(k)
            g = decay(fa, a_log, dt_bias)
            with jax.named_scope("kda.scan"):
                # no floor: the form that is exact for any decay
                o = kda_heads(q, k, v, g, beta, chunk=self.chunk,
                              dtype=self.dtype)
            return (rms_norm(o, scale, self.eps)
                    * jax.nn.sigmoid(gate.astype(F32))).astype(self.dtype)

        # the scale is every head's: given a head axis, to be cut like the taps
        o = head_blocks(
            heads, 4, (*qkv, fa, beta, gate),
            (*taps, a_log, dt_bias, jnp.broadcast_to(scale, (1, 1, H, K))))
        deep = deep_decay_share(jax.lax.stop_gradient(
            decay(fa, a_log, dt_bias)))
        return _dense(x.shape[-1], "kda_o", self)(o.reshape(B, T, H * K)), deep


class KimiBlock(nn.Module):
    """One layer; returns ``(x, counters)``, the counters a dict:
    ``"deep"`` for a KDA layer, ``"moe"`` for an expert layer. Under
    ``cfg["remat"]`` the whole layer is recomputed on the way back, as
    Ling's is."""

    mixer: str  # "kda" | "mla"
    experts: bool
    cfg: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        kw = dict(dtype=c["dtype"], param_dtype=c["param_dtype"])
        counters = {}
        h = RMSNorm(c["eps"], name="mixer_norm", **kw)(x)
        if self.mixer == "mla":
            x = x + MLAMixer(
                c["heads"], c["nope"], c["rope"], c["v_dim"], c["kv_rank"],
                eps=c["eps"], use_nope=True, name="mla", **kw)(h)
        else:
            y, counters["deep"] = KimiDeltaMixer(
                c["heads"], c["head_dim"], c["conv"], c["kda_chunk"],
                c["eps"], name="kda", **kw)(h)
            x = x + y
        h = RMSNorm(c["eps"], name="ffn_norm", **kw)(x)
        if self.experts:
            # the plain biased sigmoid top-k: ``route`` over one group
            y, counters["moe"] = ExpertFFN(
                c["n_experts"], c["experts_held"], c["expert_offset"],
                c["expert_width"], c["shared_width"], c["top_k"], 1, 1,
                c["route_scale"], name="moe", **kw)(h)
            return x + y, counters
        return x + DenseFFN(c["dense_width"], name="ffn", **kw)(h), counters


class KimiLinearLM(CausalLM):
    """Kimi-Linear-48B-A3B: published layers ``1 .. layers`` (1-indexed,
    as the config's lists are), each in ``kda_layers`` or in
    ``full_attn_layers``; every size a keyword argument."""

    vocab: int = 64
    hidden: int = 32
    layers: int = 4
    kda_layers: tuple = (1, 2, 3)
    full_attn_layers: tuple = (4,)
    first_dense: int = 1  # published layers up to this one: the dense FFN
    heads: int = 2
    head_dim: int = 16  # KDA's key and value width, and its gates' rank
    nope: int = 16
    rope: int = 8
    v_dim: int = 16
    kv_rank: int = 12
    conv: int = 4
    kda_chunk: int = 64
    dense_width: int = 48
    n_experts: int = 16
    experts_held: int = 4
    expert_offset: int = 0
    expert_width: int = 8
    shared_width: int = 8
    top_k: int = 4
    route_scale: float = 2.446
    eps: float = 1e-5
    loss_chunk: int = 1024
    remat: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def setup(self):
        kinds = {}
        for l in range(1, self.layers + 1):
            kind = [name for name, listed in (
                ("kda", self.kda_layers), ("mla", self.full_attn_layers))
                if l in listed]
            if len(kind) != 1:
                raise ValueError(
                    f"published layer {l} is in {len(kind)} of kda_layers "
                    "and full_attn_layers; a layer is in one")
            kinds[l] = kind[0]
        cfg = {f: getattr(self, f) for f in (
            "heads", "head_dim", "nope", "rope", "v_dim", "kv_rank", "conv",
            "kda_chunk", "dense_width", "n_experts", "experts_held",
            "expert_offset", "expert_width", "shared_width", "top_k",
            "route_scale", "eps", "dtype", "param_dtype")}
        block = nn.remat(KimiBlock) if self.remat else KimiBlock
        self.setup_ends()
        self.blocks = [block(kind, l > self.first_dense, cfg, name=f"layer_{l}")
                       for l, kind in kinds.items()]

    def counters(self, stats):
        """The expert layers' counters and ``kda.deep_decay_share``
        [KDA layers]."""
        out = super().counters([s["moe"] for s in stats if "moe" in s])
        deep = [s["deep"] for s in stats if "deep" in s]
        if deep:
            out["kda.deep_decay_share"] = jnp.stack(deep)
        return out


@register_model("kimi-linear-48b-a3b", "kimi_linear")
def _kimi_linear(num_classes: int | None = None, **kw) -> KimiLinearLM:
    del num_classes  # the vocabulary is the model's own
    # a scenario file gives the layer lists as lists
    return KimiLinearLM(**{k: tuple(v) if isinstance(v, list) else v
                           for k, v in kw.items()})


# adapters ride on the mixers' projections, as Ling's: KDA's q, k, v, o
# and MLA's q, kv-down, kv-up, o; every kernel is a plain [d_in, d_out]
register_lora_targets(
    "kimi-linear-48b-a3b", "kimi_linear",
    default=("kda_q", "kda_k", "kda_v", "kda_o",
             "mla_q", "mla_dkv", "mla_ukv", "mla_o"),
)
