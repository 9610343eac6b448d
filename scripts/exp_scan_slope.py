"""Scan-slope timing: wrap the op in lax.scan inside ONE jit call and
time two trip counts; the slope is the true per-iteration device time,
free of per-call dispatch overhead (which contaminates even pipelined
dispatches; its size on the current machine: not measured).
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import optax


def timed(fn, *args, reps=3):
    import numpy as np

    out = fn(*args)
    jax.block_until_ready(out)
    float(jnp.sum(jax.tree.leaves(out)[0].astype(jnp.float32)))
    ts = []
    for _ in range(reps):
        t0 = time.monotonic()
        out = fn(*args)
        float(jnp.sum(jax.tree.leaves(out)[0].astype(jnp.float32)))
        ts.append(time.monotonic() - t0)
    return float(np.median(ts))


def slope(make_scan, l1=4, l2=16):
    f1, a1 = make_scan(l1)
    f2, a2 = make_scan(l2)
    t1 = timed(f1, *a1)
    t2 = timed(f2, *a2)
    return (t2 - t1) / (l2 - l1)


def main() -> None:
    from p2pfl_tpu.learning.learner import make_step_fns
    from p2pfl_tpu.learning.objectives import get_objective
    from p2pfl_tpu.models import get_model

    n, bsz = 64, 64
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n, bsz, 28, 28, 1), jnp.float32)
    y = jnp.zeros((n, bsz), jnp.int32)
    mask = jnp.ones((n, bsz), bool)
    loss_fn = get_objective("classification")
    tx = optax.sgd(0.05, momentum=0.9)

    def make_states(model):
        fns = make_step_fns(model, learning_rate=0.05, batch_size=bsz)
        rngs = jnp.stack([jax.random.PRNGKey(0)] * n)
        return jax.jit(jax.vmap(fns.init, in_axes=(0, None)))(rngs, x[0, :1])

    def step_slope(model, tag):
        states = make_states(model)

        def per_node(st, xb, yb, mb):
            def batch_loss(p):
                return loss_fn(model.apply(p, xb), yb, mb)
            loss, grads = jax.value_and_grad(batch_loss)(st.params)
            updates, opt_state = tx.update(grads, st.opt_state, st.params)
            params = optax.apply_updates(st.params, updates)
            return st.replace(params=params, opt_state=opt_state), loss

        def make_scan(length):
            def body(carry, _):
                st, l = jax.vmap(per_node)(carry, x, y, mask)
                return st, jnp.sum(l)
            def run(states):
                st, ls = jax.lax.scan(body, states, None, length=length)
                return ls
            return jax.jit(run), (states,)

        s = slope(make_scan)
        print(f"{tag:28s} {s*1000:8.2f} ms/step")
        return s

    step_slope(get_model("femnist-cnn"), "nn.Conv step")

    import flax.linen as nn

    class Im2ColConv(nn.Module):
        features: int
        kernel: int = 5
        dtype: jnp.dtype = jnp.bfloat16
        param_dtype: jnp.dtype = jnp.float32

        @nn.compact
        def __call__(self, x):
            k = self.kernel
            cin = x.shape[-1]
            w = self.param("kernel", nn.initializers.lecun_normal(),
                           (k * k * cin, self.features), self.param_dtype)
            b = self.param("bias", nn.initializers.zeros,
                           (self.features,), self.param_dtype)
            patches = jax.lax.conv_general_dilated_patches(
                x.astype(self.dtype), (k, k), (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            return patches @ w.astype(self.dtype) + b.astype(self.dtype)

    class CNN2(nn.Module):
        @nn.compact
        def __call__(self, x):
            if x.ndim == 3:
                x = x[..., None]
            x = x.astype(jnp.bfloat16)
            for c in (32, 64):
                x = Im2ColConv(features=c, kernel=5)(x)
                x = nn.relu(x)
                x = nn.max_pool(x, (2, 2), strides=(2, 2))
            x = x.reshape((x.shape[0], -1))
            x = nn.Dense(2048, dtype=jnp.bfloat16)(x)
            x = nn.relu(x)
            x = nn.Dense(62, dtype=jnp.bfloat16)(x)
            return x.astype(jnp.float32)

    step_slope(CNN2(), "im2col step")

    # ---- fwd-only slopes (eval cost model) ----------------------------
    def fwd_slope(model, tag):
        states = make_states(model)

        def make_scan(length):
            def body(carry, _):
                out = jax.vmap(lambda p, xb: model.apply(p, xb))(
                    carry.params, x)
                return carry, jnp.sum(out)
            def run(states):
                _, ls = jax.lax.scan(body, states, None, length=length)
                return ls
            return jax.jit(run), (states,)

        s = slope(make_scan)
        print(f"{tag:28s} {s*1000:8.2f} ms/fwd")

    fwd_slope(get_model("femnist-cnn"), "nn.Conv fwd")
    fwd_slope(CNN2(), "im2col fwd")

    # ---- mixing einsum f32 vs bf16 ------------------------------------
    model = get_model("femnist-cnn")
    states = make_states(model)
    wn = jnp.ones((n, n), jnp.float32) / n

    def mix_slope(cast, tag):
        def make_scan(length):
            def body(params, _):
                def leaf(p):
                    flat = p.reshape(p.shape[0], -1)
                    if cast:
                        out = jax.lax.dot(
                            wn.astype(jnp.bfloat16), flat.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
                    else:
                        out = wn @ flat.astype(jnp.float32)
                    return out.reshape(p.shape).astype(p.dtype)
                return jax.tree.map(leaf, params), None
            def run(params):
                out, _ = jax.lax.scan(body, params, None, length=length)
                return jax.tree.leaves(out)[0]
            return jax.jit(run), (states.params,)

        s = slope(make_scan)
        print(f"{tag:28s} {s*1000:8.2f} ms/mix")

    mix_slope(False, "mix einsum f32")
    mix_slope(True, "mix einsum bf16")

    # ---- permutation: row gather vs one-hot matmul --------------------
    xs = jax.random.normal(key, (n, 750, 28, 28, 1), jnp.float32)

    def perm_slope(onehot, tag):
        def make_scan(length):
            def body(carry, r):
                def one(xn, rr):
                    perm = jax.random.permutation(rr, xn.shape[0])
                    if onehot:
                        oh = jax.nn.one_hot(perm, xn.shape[0],
                                            dtype=jnp.bfloat16)
                        flat = xn.reshape(xn.shape[0], -1).astype(jnp.bfloat16)
                        return (oh @ flat).reshape(xn.shape).astype(xn.dtype)
                    return xn[perm]
                rngs = jax.random.split(r, carry.shape[0])
                out = jax.vmap(one)(carry, rngs)
                return out, None
            def run(xx):
                keys = jax.random.split(key, length)
                def body2(c, kk):
                    return body(c, kk)
                out, _ = jax.lax.scan(body2, xx, keys)
                return out
            return jax.jit(run), (xs,)

        s = slope(make_scan)
        print(f"{tag:28s} {s*1000:8.2f} ms/perm")

    perm_slope(False, "perm row-gather")
    perm_slope(True, "perm one-hot mm")


if __name__ == "__main__":
    main()
