"""Sweep: per-step time vs (batch, momentum dtype).

fori_loop with a RUNTIME trip count -> one compile per config; slope
between two trip counts gives per-step device time free of per-call
dispatch overhead.
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
    float(jnp.sum(jax.tree.leaves(out)[0].astype(jnp.float32)))
    ts = []
    for _ in range(reps):
        t0 = time.monotonic()
        out = fn(*args)
        float(jnp.sum(jax.tree.leaves(out)[0].astype(jnp.float32)))
        ts.append(time.monotonic() - t0)
    return float(np.median(ts))


def main() -> None:
    from p2pfl_tpu.learning.objectives import get_objective
    from p2pfl_tpu.models import get_model

    n = 64
    key = jax.random.PRNGKey(0)
    loss_fn = get_objective("classification")
    model = get_model("femnist-cnn")

    def sweep(bsz, tx, tag):
        x = jax.random.normal(key, (n, bsz, 28, 28, 1), jnp.float32)
        y = jnp.zeros((n, bsz), jnp.int32)
        mask = jnp.ones((n, bsz), bool)
        x1 = jnp.zeros((1, 28, 28, 1), jnp.float32)

        def init(rng):
            params = model.init(rng, x1)
            return params, tx.init(params)

        rngs = jnp.stack([jax.random.PRNGKey(0)] * n)
        params, opt_state = jax.jit(jax.vmap(init))(rngs)

        def per_node(p, o, xb, yb, mb):
            def batch_loss(pp):
                return loss_fn(model.apply(pp, xb), yb, mb)
            loss, grads = jax.value_and_grad(batch_loss)(p)
            updates, o2 = tx.update(grads, o, p)
            return optax.apply_updates(p, updates), o2, loss

        @jax.jit
        def run(p, o, length):
            def body(_, carry):
                p, o, acc = carry
                p, o, l = jax.vmap(per_node)(p, o, x, y, mask)
                return (p, o, acc + jnp.sum(l))
            _, _, acc = jax.lax.fori_loop(0, length, body, (p, o, 0.0))
            return acc

        t1 = timed(run, params, opt_state, 8)
        t2 = timed(run, params, opt_state, 40)
        s = (t2 - t1) / 32
        steps = 750 // bsz
        print(f"{tag:34s} {s*1000:7.2f} ms/step  x{steps:2d} = "
              f"{s*steps*1000:7.1f} ms/epoch", flush=True)

    import os
    which = os.environ.get("SWEEP", "all")
    cfgs = {
        "m64": (64, lambda: optax.sgd(0.05, momentum=0.9), "b64 sgd+mom f32"),
        "m128": (128, lambda: optax.sgd(0.05, momentum=0.9), "b128 sgd+mom f32"),
        "m256": (256, lambda: optax.sgd(0.05, momentum=0.9), "b256 sgd+mom f32"),
        "mbf": (64, lambda: optax.sgd(0.05, momentum=0.9,
                                      accumulator_dtype=jnp.bfloat16),
                "b64 sgd+mom bf16acc"),
        "p64": (64, lambda: optax.sgd(0.12), "b64 sgd plain"),
        "p128": (128, lambda: optax.sgd(0.12), "b128 sgd plain"),
        "p256": (256, lambda: optax.sgd(0.12), "b256 sgd plain"),
    }
    for k, (bsz, mk, tag) in cfgs.items():
        if which == "all" or k in which.split(","):
            sweep(bsz, mk(), tag)


if __name__ == "__main__":
    main()
