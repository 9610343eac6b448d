"""BandedConvPool (models/cnn.py): the lane-dense lowering of SmallCNN's
first block (conv + bias + ReLU + 2x2 max-pool) must be a drop-in for
nn.Conv('SAME') -> nn.relu -> nn.max_pool: same parameter tree, same
values, same gradients, odd sizes floored as the pool floors them, and
a kernel gradient that is rounded once (not a chain of bf16 sums)."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from p2pfl_tpu.models import cnn as cnn_mod
from p2pfl_tpu.models import get_model
from p2pfl_tpu.models.cnn import BandedConvPool, PatchConv

CASES = [(5, 28, 28, 32), (3, 28, 28, 32), (5, 12, 12, 8), (3, 9, 11, 4)]


def _pooled(conv, params, x):
    return nn.max_pool(nn.relu(conv.apply(params, x)), (2, 2), strides=(2, 2))


def _setup(k, h, w, f, dtype=jnp.float32, batch=3):
    kx, kp, kb, kc = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(kx, (batch, h, w, 1), jnp.float32)
    ref = nn.Conv(f, (k, k), padding="SAME", dtype=jnp.float32)
    params = ref.init(kp, x)
    # a bias that is not zero, so that it is seen
    params = {"params": {**params["params"], "bias":
                         0.3 * jax.random.normal(kb, (f,), jnp.float32)}}
    alt = BandedConvPool(f, (k, k), dtype=dtype)
    ct = jax.random.normal(kc, (batch, h // 2, w // 2, f), jnp.float32)
    return x, ref, alt, params, ct


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))


@pytest.mark.parametrize("k,h,w,f", CASES)
def test_banded_block_forward_matches_conv_relu_pool(k, h, w, f):
    x, ref, alt, params, _ = _setup(k, h, w, f)
    assert (jax.tree.structure(params)
            == jax.tree.structure(alt.init(jax.random.PRNGKey(0), x)))
    want = jax.jit(lambda p, x: _pooled(ref, p, x))(params, x)
    got = jax.jit(alt.apply)(params, x)
    assert got.shape == want.shape == (3, h // 2, w // 2, f)
    assert jnp.max(jnp.abs(got - want)) < 1e-5


@pytest.mark.parametrize("k,h,w,f", CASES)
def test_banded_block_gradients_match(k, h, w, f):
    x, ref, alt, params, ct = _setup(k, h, w, f)
    want = jax.jit(jax.grad(lambda p, x: jnp.sum(_pooled(ref, p, x) * ct),
                            argnums=(0, 1)))(params, x)
    got = jax.jit(jax.grad(lambda p, x: jnp.sum(alt.apply(p, x) * ct),
                           argnums=(0, 1)))(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert a.shape == b.shape
        assert _rel(a, b) < 1e-5


@pytest.mark.parametrize("k,h,w,f", CASES)
def test_banded_block_under_vmap_with_per_node_kernels(k, h, w, f):
    """The federation's shape: every node its own kernel and batch."""
    n = 4
    x, ref, alt, params, ct = _setup(k, h, w, f)
    keys = jax.random.split(jax.random.PRNGKey(7), n)
    stacked = jax.vmap(lambda key: jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(key, a.shape), params))(keys)
    xs = jax.vmap(lambda key: x + jax.random.normal(key, x.shape))(keys)

    def value_and_grads(mod_fn):
        return jax.jit(jax.vmap(jax.value_and_grad(
            lambda p, x: jnp.sum(mod_fn(p, x) * ct), argnums=(0, 1))))(
                stacked, xs)

    want = value_and_grads(lambda p, x: _pooled(ref, p, x))
    got = value_and_grads(alt.apply)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert a.shape == b.shape
        assert _rel(a, b) < 1e-5


def test_banded_kernel_gradient_is_rounded_once_in_bfloat16():
    """In bfloat16 the kernel's gradient may not be further from the
    float32 one than the im2col form's, whose weight gradient is one
    GEMM accumulated in float32 and rounded once: the band's gradient
    summed over the output columns in bfloat16 would be."""
    k, h, w, f = 5, 28, 28, 32
    x, ref, alt, params, ct = _setup(k, h, w, f, dtype=jnp.bfloat16, batch=32)
    patches = PatchConv(f, (k, k), dtype=jnp.bfloat16)

    def kernel_grad(fn):
        g = jax.jit(jax.grad(
            lambda p: jnp.sum(fn(p, x).astype(jnp.float32) * ct)))(params)
        return g["params"]["kernel"], g["params"]["bias"]

    want_k, want_b = kernel_grad(lambda p, x: _pooled(ref, p, x))
    old_k, old_b = kernel_grad(lambda p, x: _pooled(patches, p, x))
    new_k, new_b = kernel_grad(alt.apply)
    assert _rel(new_k, want_k) <= 1.5 * _rel(old_k, want_k)
    assert _rel(new_b, want_b) <= 1.5 * _rel(old_b, want_b)


def test_banded_matmul_kernel_gradient_sums_in_float32():
    """The sharper witness of the same: on bfloat16 operands the kernel's
    gradient is the float32 one, to be rounded once by the caller's cast.
    Autodiff through the band (its gradient stored in bfloat16, then
    summed over the 14 output columns of a parity) read 7e-3 here."""
    geom = (32, 14, 512)  # 28 + 5 - 1 input columns, 14 x 32 padded to 512
    kr, kk, kg = jax.random.split(jax.random.PRNGKey(3), 3)
    rows = jax.random.normal(kr, (32, 14, 160)).astype(jnp.bfloat16)
    kernel = (0.2 * jax.random.normal(kk, (5, 5, 1, 32))).astype(
        jnp.bfloat16).astype(jnp.float32)
    gs = tuple(jax.random.normal(kg, (2, 32, 14, 512)).astype(jnp.bfloat16))

    def exact(k):
        return tuple(jnp.einsum(
            "...k,kn->...n", rows.astype(jnp.float32), band_q,
            precision="highest") for band_q in cnn_mod._band(k, *geom))

    (want,) = jax.vjp(exact, kernel)[1](
        tuple(g.astype(jnp.float32) for g in gs))
    (got,) = jax.vjp(lambda k: cnn_mod._banded_matmul(rows, k, geom),
                     kernel)[1](gs)
    assert got.dtype == want.dtype == jnp.float32
    assert _rel(got, want) < 1e-5


def test_lowering_record_names_each_conv_form():
    """The program's own record of which lowering each conv took, read
    by the benchmark's ``fit.conv1_lane_dense``."""
    model = get_model("femnist-cnn")
    jax.eval_shape(model.init, jax.random.PRNGKey(0),
                   jnp.zeros((2, 28, 28, 1)))
    rec = cnn_mod.lowerings()
    assert rec["Conv_0"] == {"form": "banded", "input": (2, 28, 28, 1),
                             "kernel": (5, 5, 1, 32)}
    assert rec["Conv_1"] == {"form": "lax.conv", "input": (2, 14, 14, 32),
                             "kernel": (5, 5, 32, 64)}
    resnet = get_model("resnet9")
    jax.eval_shape(resnet.init, jax.random.PRNGKey(0),
                   jnp.zeros((2, 32, 32, 3)))
    stem = cnn_mod.lowerings()["ConvBlock_0/Conv_0"]
    assert stem["form"] == "patches" and stem["kernel"] == (3, 3, 3, 64)
    # a copy: a caller cannot edit the record
    cnn_mod.lowerings().clear()
    assert "Conv_0" in cnn_mod.lowerings()
