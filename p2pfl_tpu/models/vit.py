"""ViT for federated fine-tuning (BASELINE.json stretch config:
"ViT-Tiny federated fine-tune, 32 nodes, Krum/trimmed-mean").

No counterpart exists in the reference (its largest model is ResNet —
SURVEY.md §2.9); this is the attention workload that exercises the
sequence-parallel path in p2pfl_tpu.ops.ring_attention: set
``seq_axis`` to a mesh axis name and the attention runs blockwise over
sequence shards with ``ppermute`` K/V rotation over ICI.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from p2pfl_tpu.models.base import register_lora_targets, register_model


class TransformerBlock(nn.Module):
    dim: int
    heads: int
    mlp_ratio: int = 4
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    seq_axis: str | None = None  # mesh axis for ring attention

    def _qkv(self, y):
        head = (self.heads, self.dim // self.heads)
        return tuple(
            nn.DenseGeneral(head, dtype=self.dtype,
                            param_dtype=self.param_dtype, name=name)(y)
            for name in ("query", "key", "value")
        )

    @nn.compact
    def __call__(self, x):
        y = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype)(x)
        if self.seq_axis is not None:
            from p2pfl_tpu.ops.ring_attention import ring_self_attention

            attn = lambda q, k, v: ring_self_attention(
                q, k, v, axis_name=self.seq_axis
            )
            y = attn(*self._qkv(y))
            y = nn.DenseGeneral(self.dim, axis=(-2, -1), dtype=self.dtype,
                                param_dtype=self.param_dtype, name="out")(y)
        else:
            y = nn.MultiHeadDotProductAttention(
                num_heads=self.heads, dtype=self.dtype,
                param_dtype=self.param_dtype)(y, y)
        x = x + y
        y = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype)(x)
        y = nn.Dense(self.dim * self.mlp_ratio, dtype=self.dtype,
                     param_dtype=self.param_dtype)(y)
        y = nn.gelu(y)
        y = nn.Dense(self.dim, dtype=self.dtype, param_dtype=self.param_dtype)(y)
        return x + y


class _BlockStep(nn.Module):
    """``nn.scan`` adapter: ``(carry, _) -> (carry, None)`` around one
    (optionally rematted) TransformerBlock."""

    remat: bool = False
    block_kw: Any = None

    @nn.compact
    def __call__(self, x, _):
        cls = nn.remat(TransformerBlock) if self.remat else TransformerBlock
        return cls(**(self.block_kw or {}))(x), None


class ViT(nn.Module):
    """ViT-Tiny by default: patch 4 (CIFAR-scale), dim 192, 12 layers."""

    patch: int = 4
    dim: int = 192
    depth: int = 12
    heads: int = 3
    num_classes: int = 10
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    seq_axis: str | None = None
    remat: bool = False  # jax.checkpoint each block: trade recompute
    # for ~depth x less activation memory — lets a federation of many
    # ViT replicas (vmapped per-node weights) fit a single chip's HBM
    scan_layers: bool = False  # nn.scan over depth: XLA compiles ONE
    # block instead of `depth` unrolled copies (params gain a leading
    # [depth] axis) — cuts compile time ~depth x for deep stacks

    @nn.compact
    def __call__(self, x):
        if x.ndim == 3:
            x = x[..., None]
        x = x.astype(self.dtype)
        x = nn.Conv(self.dim, (self.patch, self.patch),
                    strides=(self.patch, self.patch), dtype=self.dtype,
                    param_dtype=self.param_dtype, name="patch_embed")(x)
        b, h, w, c = x.shape
        x = x.reshape(b, h * w, c)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (1, h * w, c), self.param_dtype)
        x = x + pos.astype(self.dtype)
        block_cls = nn.remat(TransformerBlock) if self.remat else TransformerBlock
        block_kw = dict(dim=self.dim, heads=self.heads, dtype=self.dtype,
                        param_dtype=self.param_dtype,
                        seq_axis=self.seq_axis)
        if self.scan_layers:
            scanned = nn.scan(
                _BlockStep,
                # "lora": the adapters' collection (learning.lora), one
                # pair a layer like the kernels they ride on
                variable_axes={"params": 0, "lora": 0},
                split_rngs={"params": True},
                length=self.depth,
            )
            x, _ = scanned(remat=self.remat, block_kw=block_kw,
                           name="blocks")(x, None)
        else:
            for _ in range(self.depth):
                x = block_cls(**block_kw)(x)
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype)(x)
        x = jnp.mean(x, axis=1)
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=self.param_dtype)(x)
        return x.astype(jnp.float32)


@register_model("vit-tiny", "vit")
def _vit_tiny(num_classes: int = 10, **kw) -> ViT:
    return ViT(num_classes=num_classes, **kw)


# Adapter targets (learning.lora): default is the classic q/v pair —
# the smallest split that fine-tunes attention. Axis specs give each
# kernel's (out_axes, base_ndim) view: q/k/v kernels are
# [dim, heads, head_dim] (two output axes), the out projection is
# [heads, head_dim, dim], MLP Dense kernels are plain [d_in, d_out],
# patch_embed is a Conv [kh, kw, cin, cout]. Under scan_layers every
# block kernel gains a leading [depth] axis, which the lora matmul
# broadcasts over — per-layer adapters in one contraction.
register_lora_targets(
    "vit-tiny", "vit",
    default=("query", "value"),
    specs={"query": (2, 3), "key": (2, 3), "value": (2, 3),
           "out": (1, 3), "Dense": (1, 2), "patch_embed": (1, 4)},
)
