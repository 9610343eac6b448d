"""Small convnets for MNIST / FEMNIST.

Capability parity with the reference's MNISTModelCNN
(fedstellar/learning/pytorch/mnist/models/cnn.py) and FEMNISTModelCNN
(femnist/models/cnn.py — the LEAF CNN: two 5×5 conv blocks + 2048-wide
dense, 62 classes). NHWC layout (XLA's native conv layout on TPU),
bfloat16 compute.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from p2pfl_tpu.models.base import register_model
from p2pfl_tpu.ops import pallas_gemm

#: contraction size (C_in * k * k) at or below which a conv runs as
#: patches + matmul instead of lax.conv. The federation vmaps per-node
#: conv weights, which XLA lowers to feature_group_count=n_nodes
#: grouped convolutions; for tiny per-group contractions (conv1 of the
#: LEAF CNN: C_in=1, 5x5 -> 25) that lowering runs at <1% of the MXU
#: (measured: 13.2 ms fwd + 22 ms bwd vs 6.9 + 12 for the patches
#: form at n=64, b=224 — scripts/exp_op_breakdown.py). Patches cost a
#: contraction-fold memory inflation, so only small contractions
#: qualify (conv2's 800-wide patches sank whole-model im2col,
#: scripts/exp_im2col.py — and, as a Pallas stream, asked a 16 GB v5e
#: for a 34.5 GB patches array at the 64-node north star, PERF.md).
PATCH_CONV_MAX_CONTRACTION = 64


class PatchConv(nn.Module):
    """nn.Conv-compatible conv expressed as im2col patches + matmul.

    Same parameter tree as ``nn.Conv`` (``kernel`` [kh, kw, cin, f] +
    ``bias`` [f]) so checkpoints, aggregators, and param-shape checks
    see no difference; only the lowering changes.
    """

    features: int
    kernel_size: tuple[int, int]
    use_bias: bool = True
    dtype: jnp.dtype | None = None  # None = inherit x.dtype (nn.Conv
    # semantics — a drop-in must not silently downcast f32 inputs)
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        kh, kw = self.kernel_size
        cin = x.shape[-1]
        dtype = self.dtype or x.dtype
        w = self.param("kernel", nn.initializers.lecun_normal(),
                       (kh, kw, cin, self.features), self.param_dtype)
        patches = jax.lax.conv_general_dilated_patches(
            x.astype(dtype), (kh, kw), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )  # [..., H, W, cin*kh*kw], channel-major patch order
        # patches order the feature dim as (cin, kh, kw); HWIO kernels
        # are (kh, kw, cin) -> transpose before flattening to match
        wf = (w.astype(dtype)
              .transpose(2, 0, 1, 3).reshape(cin * kh * kw, self.features))
        # the GEMM itself routes through the measured gate: Pallas
        # streams M over a VMEM-stationary [K, N] weight tile (fwd,
        # dgrad, wgrad — docs/perf.md §6.4), XLA otherwise. Bias and
        # the downstream relu/pool stay XLA either way: they fuse into
        # the pooling pass, so the kernel saves nothing by absorbing
        # them.
        flat = patches.reshape(-1, cin * kh * kw)
        if pallas_gemm.choose("patches", (flat.shape, wf.shape),
                              dtype) == "pallas":
            out = pallas_gemm.patches_matmul(flat, wf)
        else:
            out = flat @ wf
        out = out.reshape(patches.shape[:-1] + (self.features,))
        if self.use_bias:
            b = self.param("bias", nn.initializers.zeros,
                           (self.features,), self.param_dtype)
            out = out + b.astype(dtype)
        return out


class GatedDense(nn.Module):
    """nn.Dense-compatible layer whose BACKWARD routes through the
    measured Pallas gate.

    Same parameter tree, init and forward math as ``nn.Dense`` (XLA
    forward — it sits near its floor); when the gate picks Pallas the
    backward runs the fused dgrad+wgrad kernel (one streaming pass
    over activations and weight, cotangent VMEM-stationary) instead of
    XLA's two independent GEMMs — the dense1 half of perf.md §6.4.
    """

    features: int
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        dtype = self.dtype or x.dtype
        k = self.param("kernel", nn.initializers.lecun_normal(),
                       (x.shape[-1], self.features), self.param_dtype)
        b = self.param("bias", nn.initializers.zeros,
                       (self.features,), self.param_dtype)
        x, k = x.astype(dtype), k.astype(dtype)
        if pallas_gemm.choose("dense_bwd", (x.shape, k.shape),
                              dtype) == "pallas":
            out = pallas_gemm.dense_matmul(x, k)
        else:
            out = x @ k
        return out + b.astype(dtype)


class SmallCNN(nn.Module):
    """conv(k×k,c1) → pool → conv(k×k,c2) → pool → dense(hidden) → logits."""

    channels: tuple[int, int] = (32, 64)
    kernel: int = 5
    hidden: int = 2048
    num_classes: int = 62
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        if x.ndim == 3:
            x = x[..., None]  # HW → HWC
        x = x.astype(self.dtype)
        k = (self.kernel, self.kernel)
        for i, c in enumerate(self.channels):
            # explicit name= keeps the param tree keyed Conv_N exactly
            # as nn.Conv auto-naming did, so pre-PatchConv checkpoints
            # still resume (the two modules share param shapes)
            contraction = x.shape[-1] * self.kernel ** 2
            if contraction <= PATCH_CONV_MAX_CONTRACTION:
                x = PatchConv(c, k, dtype=self.dtype,
                              param_dtype=self.param_dtype,
                              name=f"Conv_{i}")(x)
            else:
                x = nn.Conv(c, k, padding="SAME", dtype=self.dtype,
                            param_dtype=self.param_dtype,
                            name=f"Conv_{i}")(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        # explicit name= keeps the tree keyed Dense_0/Dense_1 as the
        # nn.Dense auto-naming did (same rationale as Conv_N above);
        # dense1's backward is the gated Pallas hot path
        x = GatedDense(self.hidden, dtype=self.dtype,
                       param_dtype=self.param_dtype, name="Dense_0")(x)
        x = nn.relu(x)
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=self.param_dtype, name="Dense_1")(x)
        return x.astype(jnp.float32)


@register_model("mnist-cnn", "cnn", "mnistmodelcnn")
def MNISTModelCNN(num_classes: int = 10, hidden: int = 512, **kw) -> SmallCNN:
    return SmallCNN(channels=(32, 64), kernel=3, hidden=hidden,
                    num_classes=num_classes, **kw)


@register_model("femnist-cnn", "femnistmodelcnn")
def FEMNISTModelCNN(num_classes: int = 62, hidden: int = 2048, **kw) -> SmallCNN:
    """The LEAF FEMNIST CNN shape — the north-star workload
    (BASELINE.json: 64-node FEMNIST-CNN federation)."""
    return SmallCNN(channels=(32, 64), kernel=5, hidden=hidden,
                    num_classes=num_classes, **kw)
