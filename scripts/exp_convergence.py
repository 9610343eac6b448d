"""Convergence sweep: rounds-to-80% vs (batch, lr) for the optimized
round program. One jitted fori_loop runs the whole 30-round trajectory
with an in-round 512-sample eval, so host dispatch is paid once."""

from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np


def sweep(batch_size, lr, rounds=30):
    from p2pfl_tpu.config.schema import DataConfig
    from p2pfl_tpu.datasets import FederatedDataset
    from p2pfl_tpu.learning.learner import make_step_fns
    from p2pfl_tpu.models import get_model
    from p2pfl_tpu.parallel.federated import (
        build_round_fn,
        init_federation,
        make_round_plan,
    )
    from p2pfl_tpu.parallel.transport import MeshTransport
    from p2pfl_tpu.topology.topology import generate_topology

    n = 64
    ds = FederatedDataset.make(
        DataConfig(dataset="femnist", samples_per_node=750,
                   batch_size=batch_size), n)
    x, y, smask, nsamp = ds.stacked()
    fns = make_step_fns(get_model("femnist-cnn"), learning_rate=lr,
                        batch_size=batch_size)
    topo = generate_topology("ring", n)
    plan = make_round_plan(topo, ["aggregator"] * n, "DFL")
    tr = MeshTransport(n)
    fed = tr.put_stacked(init_federation(fns, jnp.asarray(x[0, :1]), n))
    fargs = tuple(
        tr.put_stacked(jnp.asarray(a))
        for a in (x, y, smask, nsamp, plan.mix, plan.adopt, plan.trains)
    )
    xt = tr.put_replicated(jnp.asarray(ds.x_test[:512]))
    yt = tr.put_replicated(jnp.asarray(ds.y_test[:512]))
    round_fn = build_round_fn(fns, epochs=1, exchange_dtype=jnp.bfloat16)

    @jax.jit
    def trajectory(fed, xt, yt, *fargs):
        tmask = jnp.ones((xt.shape[0],), bool)

        def body(r, carry):
            fed, accs = carry
            fed, _ = round_fn(fed, *fargs)
            ev = jax.vmap(fns.evaluate, in_axes=(0, None, None, None))(
                fed.states.params, xt, yt, tmask)
            return fed, accs.at[r].set(jnp.mean(ev["accuracy"]))

        accs = jnp.zeros((rounds,), jnp.float32)
        fed, accs = jax.lax.fori_loop(0, rounds, body, (fed, accs))
        return fed, accs

    t0 = time.monotonic()
    fed, accs = trajectory(fed, xt, yt, *fargs)
    accs = np.asarray(accs)
    wall = time.monotonic() - t0  # includes compile
    r80 = int(np.argmax(accs >= 0.80)) + 1 if (accs >= 0.80).any() else None
    print(f"b{batch_size} lr{lr}: r80={r80} acc10={accs[9]:.3f} "
          f"acc30={accs[-1]:.3f} wall={wall:.1f}s", flush=True)


if __name__ == "__main__":
    import os
    cfgs = [(64, 0.05), (128, 0.08), (150, 0.08), (150, 0.12), (250, 0.15)]
    pick = os.environ.get("CFG")
    if pick:
        i = int(pick)
        cfgs = cfgs[i:i + 1]
    for b, lr in cfgs:
        sweep(b, lr)
