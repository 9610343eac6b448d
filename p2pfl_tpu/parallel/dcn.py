"""Multi-host (DCN) federation: ``jax.distributed`` + one global mesh.

SURVEY.md §7 phase 6. The reference cannot span hosts without its TCP
socket mesh and hand-rolled wire grammar; here a multi-host federation
is the SAME SPMD round program, compiled over a global device mesh
that spans every process in a ``jax.distributed`` job — weight
exchange rides ICI within a host/slice and DCN across hosts, scheduled
by XLA's collectives, with no bespoke message layer on the data path.

Topology of a job: each host runs one process with its local devices;
``jax.distributed.initialize`` wires them into one runtime
(coordinator at process 0). Federated node *i* lives on global device
*i* — data for node *i* is materialized ONLY on the process that owns
that device (``jax.make_array_from_callback`` slices the host copy).

Two entry points:

- ``run_scenario(config_path)`` — the real mode: the FULL ``Scenario``
  surface (any topology/federation/aggregator, train-set votes, fault
  injection, checkpoint/resume, metrics + monitoring) over the global
  mesh. ``MeshTransport`` detects the multi-process runtime and places
  every array with ``make_array_from_callback``; per-node host reads
  ride ``process_allgather``; process 0 owns logs and checkpoints.
- ``run_federation(...)`` — the minimal hardcoded demo kept as a
  smoke target (fully-connected DFL FedAvg, one jit, no scenario
  machinery).

Simulation recipe (no cluster needed — the 2-process tests in
tests/test_dcn.py): run N processes on localhost, each with
``--xla_force_host_platform_device_count=K`` virtual CPU devices, all
pointing at the same coordinator:

    python -m p2pfl_tpu.parallel.dcn --coordinator 127.0.0.1:9911 \
        --num-processes 2 --process-id {0,1} --platform cpu \
        [--config scenario.json | --rounds 1]
"""

from __future__ import annotations

import argparse
import json
import sys


def initialize(coordinator_address: str, num_processes: int,
               process_id: int) -> None:
    """Join this process into the distributed runtime (idempotent).

    Must run before anything touches the XLA backend — so no
    ``jax.devices()``/``device_put`` before this.
    """
    import jax

    if jax.distributed.is_initialized() or num_processes == 1:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def make_global(x, sharding):
    """Materialize a host array as a global sharded array: each process
    fills only the shards it owns (the DCN-safe device_put).

    ``dtype`` is passed explicitly: a process whose devices all fall
    OUTSIDE the federation mesh (e.g. 6 nodes on 4 hosts x 2 devices —
    the divisor rule uses 6 of 8 devices, host 3 owns none) fills no
    shards, and make_array_from_callback cannot infer the dtype from
    an empty shard list."""
    import jax
    import numpy as np

    x = np.asarray(x)
    return jax.make_array_from_callback(
        x.shape, sharding, lambda idx: x[idx], dtype=x.dtype
    )


def run_federation(rounds: int = 1, dataset: str = "mnist",
                   model_name: str = "mnist-mlp",
                   samples_per_node: int = 150,
                   learning_rate: float = 0.05, seed: int = 0,
                   exchange_dtype: str | None = None) -> dict:
    """One federation spanning every device of every process: node i on
    global device i, fully-connected DFL FedAvg. Every process executes
    this same function (SPMD); returns globally-agreed metrics.

    ``exchange_dtype`` ("bf16") down-casts the mix contraction's
    inputs — the same wire-precision knob the single-host builders
    take, here shrinking the DCN (cross-host) exchange bytes.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec as P

    from p2pfl_tpu.config.schema import DataConfig
    from p2pfl_tpu.datasets import FederatedDataset
    from p2pfl_tpu.learning.learner import make_step_fns
    from p2pfl_tpu.models import get_model
    from p2pfl_tpu.parallel.federated import (
        build_eval_fn,
        build_round_fn,
        init_federation,
        make_round_plan,
    )
    from p2pfl_tpu.parallel.mesh import NODES_AXIS, federation_mesh
    from p2pfl_tpu.topology.topology import generate_topology

    n = len(jax.devices())  # ALL global devices — one federated node each
    mesh = federation_mesh()
    stacked = NamedSharding(mesh, P(NODES_AXIS))
    replicated = NamedSharding(mesh, P())

    # identical on every process (deterministic seeds) — each process
    # materializes only its own devices' node shards
    ds = FederatedDataset.make(
        DataConfig(dataset=dataset, samples_per_node=samples_per_node), n
    )
    x, y, smask, nsamp = ds.stacked()
    fns = make_step_fns(get_model(model_name), learning_rate=learning_rate,
                        batch_size=32)
    topo = generate_topology("fully", n)
    plan = make_round_plan(topo, ["aggregator"] * n, "DFL")

    def g(a):
        return make_global(a, stacked)

    fed_host = jax.tree.map(np.asarray, init_federation(
        fns, jnp.asarray(np.asarray(x)[0, :1]), n, seed=seed))
    fed = jax.tree.map(
        lambda a: g(a) if a.ndim >= 1 and a.shape[0] == n
        else make_global(a, replicated),
        fed_host,
    )
    args = [g(a) for a in (x, y, smask, nsamp, plan.mix, plan.adopt,
                           plan.trains)]
    ex_dt = jnp.bfloat16 if exchange_dtype in ("bf16", "int8") else None
    round_fn = jax.jit(build_round_fn(fns, epochs=1, exchange_dtype=ex_dt),
                       donate_argnums=(0,))
    eval_fn = jax.jit(build_eval_fn(fns))

    for _ in range(rounds):
        fed, metrics = round_fn(fed, *args)
    losses = multihost_utils.process_allgather(metrics["train_loss"], tiled=True)
    x_test = make_global(ds.x_test[:1000], replicated)
    y_test = make_global(ds.y_test[:1000], replicated)
    acc = multihost_utils.process_allgather(
        eval_fn(fed, x_test, y_test)["accuracy"], tiled=True
    )
    # fully-connected DFL FedAvg: params must agree ACROSS processes
    leaf = jax.tree.leaves(fed.states.params)[0]
    leaf_all = multihost_utils.process_allgather(leaf, tiled=True)
    spread = float(np.max(np.abs(
        leaf_all.reshape(n, -1) - leaf_all.reshape(n, -1)[0]
    )))
    return {
        "process": jax.process_index(),
        "n_processes": jax.process_count(),
        "n_nodes": n,
        "rounds": rounds,
        "mean_loss": float(np.mean(losses)),
        "mean_accuracy": float(np.mean(acc)),
        "cross_process_param_spread": spread,
    }


def run_scenario(config_path: str) -> dict:
    """The REAL DCN mode: drive a full ``Scenario`` — topology,
    federation scheme, robust aggregators, train-set votes, fault
    injection, checkpoint/resume, metrics/monitoring — over the global
    multi-process mesh. ``jax.distributed`` must be initialized first;
    every process calls this with the same scenario file and executes
    the same SPMD round program (MeshTransport places each node's
    shards only on the process that owns its device; process 0 owns
    the log artifacts)."""
    import jax
    import numpy as np

    from p2pfl_tpu.config.schema import ScenarioConfig
    from p2pfl_tpu.federation.scenario import Scenario

    cfg = ScenarioConfig.load(config_path)
    scenario = Scenario(cfg)
    result = scenario.run()
    scenario.close()
    return {
        "process": jax.process_index(),
        "n_processes": jax.process_count(),
        "n_nodes": cfg.n_nodes,
        "federation": cfg.federation,
        "topology": cfg.topology,
        "aggregator": cfg.aggregator,
        "sparse_transport": scenario.sparse_transport,
        "rounds": result.rounds_run,
        "final_accuracy": round(float(result.final_accuracy), 4),
        "min_accuracy": round(float(result.min_accuracy), 4),
        "mean_round_s": round(
            float(np.mean(result.round_times_s)), 4
        ) if result.round_times_s else None,
        "leader": scenario.leader,
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="p2pfl_tpu.parallel.dcn")
    ap.add_argument("--coordinator", default="127.0.0.1:9911")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--platform", default=None,
                    help="force a JAX platform (cpu for the simulation recipe)")
    ap.add_argument("--rounds", type=int, default=1)
    ap.add_argument("--dataset", default="mnist")
    ap.add_argument("--model", default="mnist-mlp")
    ap.add_argument("--exchange-dtype", default=None,
                    choices=("f32", "bf16"),
                    help="wire precision for the demo federation's "
                         "exchange (the config knob is wire_dtype)")
    ap.add_argument("--config", default=None,
                    help="ScenarioConfig JSON: run the FULL scenario "
                         "surface over the global mesh instead of the "
                         "minimal demo federation")
    args = ap.parse_args(argv)
    from p2pfl_tpu.utils import compile_cache

    compile_cache.enable()
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    initialize(args.coordinator, args.num_processes, args.process_id)
    if args.config:
        result = run_scenario(args.config)
    else:
        result = run_federation(rounds=args.rounds, dataset=args.dataset,
                                model_name=args.model,
                                exchange_dtype=args.exchange_dtype)
    print("P2PFL_DCN_RESULT " + json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
