"""Small convnets for MNIST / FEMNIST.

Capability parity with the reference's MNISTModelCNN
(fedstellar/learning/pytorch/mnist/models/cnn.py) and FEMNISTModelCNN
(femnist/models/cnn.py — the LEAF CNN: two 5×5 conv blocks + 2048-wide
dense, 62 classes). NHWC layout (XLA's native conv layout on TPU),
bfloat16 compute.

Which lowering each conv takes (``lowerings()`` records it at trace
time). The federation vmaps per-node conv weights, which XLA lowers to
grouped convolutions; a small per-group contraction (C_in * k * k <=
``PATCH_CONV_MAX_CONTRACTION``) takes another form:

- ``SmallCNN``'s first block (femnist-cnn 5x5, mnist-cnn 3x3; C_in 1):
  ``BandedConvPool``, conv + bias + ReLU + pool as lane-dense GEMMs.
- ResNet's RGB stem (``models/resnet.py``, contraction 27, GroupNorm
  behind it and no pool): ``PatchConv``, im2col patches + one GEMM.
- every other conv (conv2: contraction 800): ``nn.Conv``.

Measured on a TPU v5e, 64 nodes x batch 336, the north-star cell
``femnist-cnn.dfl64-full`` (PERF.md, PR 33): with conv1 as ``PatchConv``
89 ms of every 206 ms device round were copies and reshapes of its
``[.., 25]`` patches and ``[.., 32]`` output (nothing computed in them:
a quarter-full 128-lane tile made XLA:TPU put the batch or the node axis
on the lanes, on each side of the GEMM, of the pool and of the grouped
conv2), and 79 ms of each 254 ms evaluation. As ``BandedConvPool`` a
round takes 0.12 s for 0.21 and an evaluation 0.14 s for 0.25.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from p2pfl_tpu.models.base import register_model
from p2pfl_tpu.ops import pallas_gemm

#: contraction size (C_in * k * k) at or below which a conv leaves
#: lax.conv for one of the GEMM forms above. Both inflate memory by a
#: factor that grows with the contraction (im2col: k * k times the
#: input; the band: k times the input and w_in / k times the MACs), so
#: only small contractions qualify: conv2's 800-wide patches sank
#: whole-model im2col (docs/perf.md §4) and, as a Pallas stream,
#: asked a 16 GB v5e for a 34.5 GB patches array at the 64-node north
#: star (PERF.md, PR 21); banded, conv2 would need 72 MFLOP a sample
#: for 20.
PATCH_CONV_MAX_CONTRACTION = 64


_LANES = 128  # a TPU vector register's minor dimension

_lowerings: dict[str, dict] = {}


def lowerings() -> dict[str, dict]:
    """Which lowering each conv of this module's models took the last
    time it was traced: layer path -> ``{"form": "banded" | "patches" |
    "lax.conv", "input": shape, "kernel": shape}``. Recorded at trace
    time, as ``pallas_gemm.decisions()`` is."""
    return {k: dict(v) for k, v in _lowerings.items()}


def _record_lowering(module, form, x, kernel_shape):
    _lowerings["/".join(module.path)] = {
        "form": form, "input": tuple(x.shape), "kernel": tuple(kernel_shape)}


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
        _record_lowering(self, "patches", x, w.shape)
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


def _band(kernel, w_in, w_half, lanes):
    """``kernel`` [kh, kw, cin, f] as the right operands of GEMMs over
    whole padded image rows: ``[2, kh * w_in * cin, lanes]`` with
    ``band[q, (i, w', c), (v, f)] = kernel[i, w' - (2v + q), c, f]``
    where that tap exists, else 0: one operand for the even output
    columns (q = 0) and one for the odd, each a whole row of them,
    ``w_half * f`` wide, zero-padded to ``lanes``. A constant gather and
    a mask, so the kernel's gradient is the transpose of both."""
    kh, kw, cin, f = kernel.shape
    w = 2 * np.arange(w_half)[None, None, :] + np.arange(2)[:, None, None]
    j = np.arange(w_in)[None, :, None] - w  # [2, w_in, w_half]
    tap = (j >= 0) & (j < kw)
    band = jnp.where(tap[None, ..., None, None],
                     kernel[:, np.clip(j, 0, kw - 1)], 0)
    # [kh, 2, w_in, w_half, cin, f] -> [2, (kh, w_in, cin), (w_half, f)]
    band = band.transpose(1, 0, 2, 4, 3, 5).reshape(
        2, kh * w_in * cin, w_half * f)
    return jnp.pad(band, ((0, 0), (0, 0), (0, lanes - w_half * f)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _banded_matmul(rows, kernel, geom):
    """``rows [..., K] @ band(kernel)[q]`` for both column parities q:
    float32 accumulation, stored in ``rows.dtype``. ``kernel`` comes in
    float32. The custom backward exists for one reason: the kernel's
    gradient is the band's gradient summed over the output columns, and
    autodiff would round the band's gradient to the compute dtype before
    that sum. Here both stay in float32, so that the caller's cast rounds
    the kernel's gradient once, as the im2col GEMM's was."""
    band = _band(kernel, *geom).astype(rows.dtype)
    return tuple(
        jnp.einsum("...k,kn->...n", rows, band_q,
                   preferred_element_type=jnp.float32).astype(rows.dtype)
        for band_q in band)


def _banded_matmul_fwd(rows, kernel, geom):
    return _banded_matmul(rows, kernel, geom), (rows, kernel)


def _banded_matmul_bwd(geom, res, gs):
    rows, kernel = res
    band, band_t = jax.vjp(lambda k: _band(k, *geom), kernel)
    d_rows = sum(
        jnp.einsum("...n,kn->...k", g, band_q.astype(g.dtype),
                   preferred_element_type=jnp.float32)
        for g, band_q in zip(gs, band, strict=True))
    d_band = jnp.stack([
        jnp.einsum("...k,...n->kn", rows, g,
                   preferred_element_type=jnp.float32) for g in gs])
    (d_kernel,) = band_t(d_band)
    return d_rows.astype(rows.dtype), d_kernel


_banded_matmul.defvjp(_banded_matmul_fwd, _banded_matmul_bwd)


_WINDOW = 4  # members of a 2x2 pool window


@jax.custom_vjp
def _pool_bias_relu(window, bias_row):
    """``relu(max(window) + bias_row)`` over the 2x2 window's four members
    (same-shaped arrays, in the order a max-pool's backward prefers them
    on a tie): bias and ReLU commute with the max, so they run on a
    quarter of the elements. The backward is one select on a one-byte
    record of which member won (the first on a tie, as ``nn.max_pool``'s
    does; none where the ReLU is off): autodiff of ``maximum`` keeps the
    members and a mask for each, and splits ties with a divide."""
    return _pool_bias_relu_fwd(window, bias_row)[0]


def _pool_bias_relu_fwd(window, bias_row):
    best, winner = window[0], jnp.int8(0)
    for i, member in enumerate(window[1:], 1):
        winner = jnp.where(member > best, jnp.int8(i), winner)
        best = jnp.maximum(member, best)
    act = best.astype(jnp.float32) + bias_row
    winner = jnp.where(act > 0, winner, jnp.int8(_WINDOW))
    return nn.relu(act).astype(best.dtype), winner


def _pool_bias_relu_bwd(winner, g):
    live = jnp.where(winner < _WINDOW, g.astype(jnp.float32), 0)
    d_bias_row = live.sum(axis=tuple(range(live.ndim - 1)))
    d_window = tuple(jnp.where(winner == i, g, 0) for i in range(_WINDOW))
    return d_window, d_bias_row


_pool_bias_relu.defvjp(_pool_bias_relu_fwd, _pool_bias_relu_bwd)


class BandedConvPool(nn.Module):
    """'SAME' conv + bias + ReLU + 2x2 max-pool of a small-contraction
    conv, lowered lane-dense for the TPU.

    Same parameter tree as ``nn.Conv`` (``kernel`` [kh, kw, cin, f] +
    ``bias`` [f]) and the same values as ``nn.Conv`` -> ``nn.relu`` ->
    ``nn.max_pool(2, 2)`` (which floors odd sizes). The conv is GEMMs
    whose rows are whole padded image rows (the kh row-shifted views
    side by side: kh x the input, not kh*kw x) and whose right operands
    are the kernel banded along W (``_band``): each result holds half
    an output row, ``w_half * f`` wide, on the lanes, where the im2col
    form's 25- and 32-wide minors made XLA:TPU relay every conv1-sized
    array with the batch or the node axis on the lanes. The band
    multiplies zeros (kw / w_in of the MACs are useful); at
    K = kh * w_in * cin that is cheap on the MXU. One GEMM for each
    member of the pool's window (output rows and columns by parity), so
    the pool is an elementwise ``max`` of four same-shaped arrays and
    its backward a select (``_pool_bias_relu``).
    """

    features: int
    kernel_size: tuple[int, int]
    dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        kh, kw = self.kernel_size
        b, h, w, cin = x.shape
        f = self.features
        dtype = self.dtype or x.dtype
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (kh, kw, cin, f), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (f,),
                          self.param_dtype)
        _record_lowering(self, "banded", x, kernel.shape)
        h_half, w_half = h // 2, w // 2  # the pool floors
        w_in = w + kw - 1
        lanes = -(-w_half * f // _LANES) * _LANES
        with jax.named_scope("banded_conv_pool"):
            xp = jnp.pad(x.astype(dtype), (
                (0, 0), ((kh - 1) // 2, kh // 2), ((kw - 1) // 2, kw // 2),
                (0, 0))).reshape(b, h + kh - 1, w_in * cin)
            # every second padded row from each of the kh + 1 offsets;
            # output row 2v + p needs offsets p .. p + kh - 1, side by side
            shifted = [xp[:, o:o + 2 * h_half - 1:2] for o in range(kh + 1)]
            # the parameters' values in the compute dtype, their gradients
            # summed in float32 and rounded once by these casts
            kernel, bias = (a.astype(dtype).astype(jnp.float32)
                            for a in (kernel, bias))
            window = tuple(out for p in (0, 1) for out in _banded_matmul(
                jnp.concatenate(shifted[p:p + kh], axis=-1), kernel,
                (w_in, w_half, lanes)))
            bias_row = jnp.pad(jnp.tile(bias, w_half),
                               (0, lanes - w_half * f))
            out = _pool_bias_relu(window, bias_row)
            return out[..., :w_half * f].reshape(b, h_half, w_half, f)


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
            # as nn.Conv auto-naming did, so checkpoints from before
            # either lowering still resume (the modules share param
            # shapes)
            contraction = x.shape[-1] * self.kernel ** 2
            if contraction <= PATCH_CONV_MAX_CONTRACTION:
                x = BandedConvPool(c, k, dtype=self.dtype,
                                   param_dtype=self.param_dtype,
                                   name=f"Conv_{i}")(x)
            else:
                conv = nn.Conv(c, k, padding="SAME", dtype=self.dtype,
                               param_dtype=self.param_dtype,
                               name=f"Conv_{i}")
                _record_lowering(conv, "lax.conv", x, k + (x.shape[-1], c))
                x = nn.max_pool(nn.relu(conv(x)), (2, 2), strides=(2, 2))
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
