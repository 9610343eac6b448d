"""Does the system still start on the chip?

    python chip_smoke.py                 # the check; needs a TPU
    python chip_smoke.py --rehearse-cpu  # same stages, toy sizes, any backend

One process, the entry points a user calls, the north-star federation
at full width (the configuration ``BASELINE.md`` states, written as a
``ScenarioConfig``):

A. ``ScenarioConfig`` -> ``Scenario`` -> ``Scenario.run()`` (what
   ``python -m p2pfl_tpu.run scenario.json`` calls): 64 nodes, DFL,
   ring, FedAvg, ``femnist-cnn`` with bf16 parameters on the seeded
   hard synthetic surrogate, three rounds and one evaluation.
B. the socket plane in the same process (``p2p.launch.run_simulation``,
   4 nodes, ``mnist-mlp``, 2 rounds): ``JaxLearner``/``SharedTrainer``
   is separate device code.
C. on four or more devices: a 4-node ring, which must pick the sparse
   ``ppermute`` schedule, and one ``cross_device`` round with
   ``cohort_shards=4`` (the other ``shard_map``).

No stage is wrapped in a ``try``: whatever raises ends the run with a
traceback and a non-zero exit. The script never selects a platform and
never continues on a CPU unasked. Its last line of standard output is
one JSON object, ``{"ok": true, "device": {"platform", "kind",
"count"}}`` as JAX reported the device, and nothing else; the line
before it (``facts: {...}``) carries the seconds and decisions, which
are facts about this run for ``PERF.md``, not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time


class SmokeFailure(Exception):
    """A stage ran but what came out is wrong."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def north_star_config(n_nodes: int, rounds: int):
    """The north-star federation as the scenario a user would write
    (``benchmark/configs/femnist-cnn.json`` holds the same values)."""
    from p2pfl_tpu.config.schema import (
        DataConfig,
        ModelConfig,
        ProtocolConfig,
        ScenarioConfig,
        TrainingConfig,
    )

    spn = 750
    return ScenarioConfig(
        name="north-star",
        federation="DFL",
        topology="ring",
        topology_kwargs={"seed": 0},
        n_nodes=n_nodes,
        data=DataConfig(
            dataset="femnist", samples_per_node=spn, batch_size=336,
            # sized so samples_per_node is actually delivered after the
            # 10% validation split
            synthetic_train=int(n_nodes * spn / 0.9) + n_nodes,
            surrogate_profile="hard",
        ),
        model=ModelConfig(model="femnist-cnn", param_dtype="bfloat16"),
        training=TrainingConfig(
            rounds=rounds, epochs_per_round=1, learning_rate=0.05,
            momentum_dtype="bf16", eval_every=0,
        ),
        # every node trains every round
        protocol=ProtocolConfig(train_set_size=0),
        aggregator="fedavg",
        wire_dtype="bf16",
        transport="auto",
    )


def toy_config(name: str, n_nodes: int, rounds: int, **kw):
    from p2pfl_tpu.config.schema import (
        DataConfig,
        ModelConfig,
        ScenarioConfig,
        TrainingConfig,
    )

    return ScenarioConfig(
        name=name, n_nodes=n_nodes,
        data=DataConfig(dataset="mnist", samples_per_node=120,
                        batch_size=32),
        model=ModelConfig(model="mnist-mlp"),
        training=TrainingConfig(rounds=rounds, epochs_per_round=1,
                                learning_rate=0.1, eval_every=0),
        **kw,
    )


def run_spmd(cfg, scenario_cls=None) -> dict:
    """Build and run one SPMD scenario; check what any of them must
    satisfy; return the facts worth printing."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding

    from p2pfl_tpu.federation import Scenario
    from p2pfl_tpu.federation.events import Events
    from p2pfl_tpu.obs import trace as obs_trace

    t0 = time.monotonic()
    sc = (scenario_cls or Scenario)(cfg)
    jax.block_until_ready(sc.fed)
    build_s = time.monotonic() - t0

    # compiles are expected up to the end of the first round, and again
    # in the evaluation after the last: count the rounds in between
    last = cfg.training.rounds - 1
    recompiles = {}

    def on_event(event, payload):
        if event is Events.ROUND_FINISHED:
            if payload["round"] == 0:
                obs_trace.reset_xla_counters()
            if payload["round"] == last:
                recompiles["steady"] = obs_trace.xla_recompiles()

    sc.add_observer(on_event)
    t0 = time.monotonic()
    result = sc.run()
    run_s = time.monotonic() - t0
    sc.close()

    rows = [r for r in result.history
            if "Train/loss" in r and r.get("node") is not None]
    if rows:  # per-node rows (Scenario); cross-device logs one mean
        loss = np.full((cfg.training.rounds, cfg.n_nodes), np.nan)
        for r in rows:
            loss[r["round"], r["node"]] = r["Train/loss"]
    else:
        loss = np.array([[r["Train/loss"]] for r in result.history
                         if "Train/loss" in r])
    check(bool(np.isfinite(loss).all()),
          f"train loss finite for every node in all {len(loss)} rounds")
    if len(loss) > 1:
        check(bool((loss[-1] < loss[0]).all()),
              f"every node's train loss fell: round 0 mean "
              f"{loss[0].mean():.4f} -> round {len(loss) - 1} mean "
              f"{loss[-1].mean():.4f}")
        check(recompiles["steady"] == 0,
              "0 XLA recompiles over the rounds after the first")
    check(math.isfinite(result.final_accuracy)
          and 0.0 <= result.final_accuracy <= 1.0,
          f"evaluation returned a finite accuracy "
          f"({result.final_accuracy:.4f})")
    mesh = sc.transport.mesh
    if scenario_cls is None:
        # each device must hold its own n/D nodes of every leaf. (On
        # one device jit hands back the spec as P(): sharded and
        # replicated are the same layout there — so count the rows.)
        per_dev = cfg.n_nodes // mesh.size
        for leaf in jax.tree.leaves(sc.fed.states.params):
            sh = leaf.sharding
            shards = leaf.addressable_shards
            if not (isinstance(sh, NamedSharding)
                    and sh.mesh.axis_names == ("nodes",)
                    and len(shards) == mesh.size
                    and all(s.data.shape[0] == per_dev for s in shards)):
                raise SmokeFailure(f"params leaf not node-sharded over "
                                   f"the {mesh.size}-device mesh: {sh}")
        print(f"  ok: every params leaf is a NamedSharding over the "
              f"'nodes' axis, {per_dev} node(s) on each of "
              f"{mesh.size} mesh device(s)", flush=True)
    times = result.round_times_s
    return {
        "n_nodes": cfg.n_nodes,
        "mesh_devices": int(mesh.size),
        "build_s": round(build_s, 2),
        # trace + kernel-gate measurements + compile + one round
        "first_round_s": round(times[0], 2),
        "steady_round_s": [round(t, 4) for t in times[1:]],
        "eval_and_rest_s": round(run_s - sum(times), 2),
        "loss_by_round": [round(float(m), 4) for m in loss.mean(axis=1)],
        "accuracy": round(result.final_accuracy, 4),
        "sparse_transport": getattr(sc, "sparse_transport", None),
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="REHEARSAL: the same stages at toy sizes on whatever "
             "backend JAX finds — checks the script, says nothing "
             "about the chip")
    args = ap.parse_args(argv)
    rehearsal = args.rehearse_cpu

    from p2pfl_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()

    import jax
    import jaxlib
    from jax import monitoring

    backend = jax.default_backend()
    if backend != "tpu" and not rehearsal:
        print(f"chip_smoke: JAX's default backend is {backend!r}, not "
              f"'tpu' — this check runs on the chip only (a toy-size "
              f"rehearsal of the script itself: --rehearse-cpu)",
              file=sys.stderr)
        return 1
    tag = "REHEARSAL (toy sizes, not a chip result) " if rehearsal else ""

    cache = {"hits": 0, "misses": 0}

    def on_cache_event(event: str, **_kw) -> None:
        # jax.monitoring's persistent-cache events (a miss = an entry
        # written: programs under the size/time thresholds count as
        # neither)
        if event.endswith("/cache_hits"):
            cache["hits"] += 1
        elif event.endswith("/cache_misses"):
            cache["misses"] += 1

    monitoring.register_event_listener(on_cache_event)

    from importlib import metadata

    from p2pfl_tpu.obs import cost_model
    from p2pfl_tpu.ops import pallas_gemm

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    print(f"chip_smoke {tag}device={device} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu} "
          f"compile_cache={cache_dir}", flush=True)
    # a TPU outside the peak table raises here, before any MFU is read
    print(f"  peak bf16 FLOP/s per chip: "
          f"{cost_model.peak_flops(devices[0])}", flush=True)

    facts: dict = {}

    print(f"[A] {tag}SPMD north star: Scenario.run()", flush=True)
    cfg = (toy_config("rehearsal-ring", 4, 3, topology="ring")
           if rehearsal else north_star_config(64, 3))
    facts["spmd"] = run_spmd(cfg)
    print(f"  {facts['spmd']}", flush=True)

    print(f"[B] {tag}socket plane: p2p.launch.run_simulation", flush=True)
    from p2pfl_tpu.p2p.launch import run_simulation

    t0 = time.monotonic()
    sim = run_simulation(toy_config("smoke-socket", 4, 2,
                                    topology="fully"), timeout=300)
    check(sim["rounds"] == 2, "all 4 socket nodes finished 2 rounds")
    check(sim["mean_accuracy"] is not None
          and math.isfinite(sim["mean_accuracy"]),
          f"socket federation evaluated to a finite accuracy "
          f"({sim['mean_accuracy']})")
    facts["socket"] = {
        "wall_s": round(time.monotonic() - t0, 2),
        "round_s": sim["round_s"],
        "accuracy": sim["mean_accuracy"],
        "xla_recompiles_after_warmup": sim["xla_recompiles"],
    }
    print(f"  {facts['socket']}", flush=True)

    if len(devices) >= 4:
        print(f"[C] {tag}four devices: sparse ppermute ring + sharded "
              f"cohort scan", flush=True)
        from p2pfl_tpu.config.schema import CrossDeviceConfig
        from p2pfl_tpu.federation import CrossDeviceScenario

        ring = run_spmd(toy_config("smoke-ring4", 4, 2, topology="ring"))
        check(ring["sparse_transport"] is True and ring["mesh_devices"] == 4,
              "4-node ring on 4 devices took the sparse ppermute schedule")
        facts["ring4"] = ring
        print(f"  {ring}", flush=True)
        facts["cohorts4"] = run_spmd(
            toy_config("smoke-cohorts4", 4, 1,
                       cross_device=CrossDeviceConfig(
                           n_clients=64, clients_per_round=32,
                           cohort_size=8, cohort_shards=4)),
            scenario_cls=CrossDeviceScenario)
        print(f"  {facts['cohorts4']}", flush=True)
    else:
        print(f"[C] skipped: {len(devices)} device(s), needs 4",
              flush=True)

    decisions = pallas_gemm.decisions()
    print("pallas_gemm decisions (every stage):", flush=True)
    for key, rec in decisions.items():
        print(f"  {key}: {rec}", flush=True)
    broken = [k for k, rec in decisions.items() if "error" in rec]
    check(not broken, f"no gate decision carries an error {broken}")
    # the summary keeps the measured ones; forced ones are printed above
    facts["pallas_gemm_measured"] = {
        k: rec for k, rec in decisions.items() if not rec["forced"]}

    stats = devices[0].memory_stats() or {}
    facts["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    facts["compile_cache"] = {"dir": cache_dir, **cache}
    print(f"  device 0 peak_bytes_in_use={facts['peak_bytes_in_use']} "
          f"compile cache hits={cache['hits']} misses={cache['misses']}",
          flush=True)

    # the facts for PERF.md on their own line; the LAST line is the
    # result, with exactly the keys "ok" and "device" and nothing else
    print("facts: " + json.dumps({"rehearsal": rehearsal, **facts,
                                  "claim": None}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
