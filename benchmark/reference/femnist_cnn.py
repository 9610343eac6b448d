"""Plain reference of the LEAF FEMNIST CNN (arXiv:1812.01097,
``models/femnist/cnn.py``): conv 5x5x32 'same' -> relu -> maxpool 2 ->
conv 5x5x64 'same' -> relu -> maxpool 2 -> dense 2048 -> relu ->
dense 62. float32, ``highest`` matmul precision, no kernels.

Imports nothing of the program. ``q`` is applied to every matmul/conv
operand: the identity for the reference, a lower precision for the
control (``federation.quantizer``).
"""

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST

SHAPES = {
    "conv1_w": (5, 5, 1, 32), "conv1_b": (32,),
    "conv2_w": (5, 5, 32, 64), "conv2_b": (64,),
    "fc1_w": (3136, 2048), "fc1_b": (2048,),
    "fc2_w": (2048, 62), "fc2_b": (62,),
}


def init(key, sizes=None):
    """Weights from a key: kernels normal with variance 1/fan_in,
    biases zero (the usual LeCun scaling; LEAF's TF code leaves it to
    the framework default)."""
    out = {}
    for i, (name, shape) in enumerate(sorted(SHAPES.items())):
        if name.endswith("_b"):
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            fan_in = math.prod(shape[:-1])
            out[name] = jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32
            ) / math.sqrt(fan_in)
    return out


def _conv(x, w, q):
    return jax.lax.conv_general_dilated(
        q(x), q(w), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def _pool(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def forward(p, x, q=lambda a: a):
    """x: [B, 28, 28, 1] float32 -> logits [B, 62] float32."""
    if x.ndim == 3:
        x = x[..., None]
    x = _pool(jax.nn.relu(_conv(x, p["conv1_w"], q) + p["conv1_b"]))
    x = _pool(jax.nn.relu(_conv(x, p["conv2_w"], q) + p["conv2_b"]))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(q(x), q(p["fc1_w"]), precision=HI) + p["fc1_b"])
    return jnp.dot(q(x), q(p["fc2_w"]), precision=HI) + p["fc2_b"]
