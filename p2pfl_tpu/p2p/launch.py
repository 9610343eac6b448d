"""Multi-process federation launcher — the "real mode" controller.

Parity with the reference's deployment path (controller.py:456-485
start_nodes_cmd: one OS process per participant reading its stamped
JSON; node_start.py:28-120 per-process entry), minus the fixed 30 s +
5 s/neighbor sleeps: nodes retry-connect until their neighbors' ports
listen.

Usage (also what ``python -m p2pfl_tpu.p2p.launch scenario.json``
does): the parent stamps per-node JSON configs with assigned ports,
spawns N ``node_main`` processes, waits, and aggregates their result
lines. Each process trains with the same JaxLearner; on a multi-host
deployment you run ``node_main`` yourself on each host with the same
scenario file and per-host node indices.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import json
import math
import os
import pathlib
import socket
import subprocess
import sys
import time

from p2pfl_tpu.config.schema import ScenarioConfig
from p2pfl_tpu.core.aggregators import get_aggregator
from p2pfl_tpu.p2p.aggd import SidecarClient
from p2pfl_tpu.datasets import FederatedDataset
from p2pfl_tpu.learning import JaxLearner
from p2pfl_tpu.models.base import build_model
from p2pfl_tpu.obs import flight
from p2pfl_tpu.obs import trace as obs_trace
from p2pfl_tpu.p2p.node import P2PNode
from p2pfl_tpu.topology.topology import generate_topology
from p2pfl_tpu.utils import compile_cache


def _trace_setup(cfg: ScenarioConfig) -> obs_trace.Tracer:
    """Per-process obs wiring: the recompile listener plus the tracer,
    enabled by P2PFL_TRACE and exporting into ``<log_dir>/<name>/trace``
    — the same directory convention as the status dir, so traceview
    finds every process of a federation under one root."""
    obs_trace.install_xla_listener()
    if cfg.log_dir:
        # flight postmortems land next to the status/trace dirs; the
        # recorder itself is always on (P2PFL_FLIGHT=0 to disable)
        flight.configure(
            dump_dir=pathlib.Path(cfg.log_dir) / cfg.name / "flight"
        )
    return obs_trace.configure_from_env(
        default_dir=(pathlib.Path(cfg.log_dir) / cfg.name / "trace")
        if cfg.log_dir else None,
    )


def _adversary_setup(cfg: ScenarioConfig):
    """(malicious mask, AttackSpec | None, reputation on?) — derived
    from config alone so every process of a multi-process federation
    (and the SPMD Scenario) computes the SAME cohort and transforms."""
    adv = cfg.adversary
    if not (adv.active or adv.reputation):
        return None, None, False
    import numpy as np

    from p2pfl_tpu.adversary import AttackSpec, malicious_indices

    mask = (
        malicious_indices(cfg.n_nodes, adv.fraction, adv.seed,
                          tuple(adv.nodes))
        if adv.active else np.zeros(cfg.n_nodes, bool)
    )
    spec = (
        AttackSpec(kind=adv.kind, scale=adv.scale, seed=adv.seed)
        if adv.active else None
    )
    return mask, spec, adv.reputation


def _poison_shard(data: FederatedDataset, idx: int) -> None:
    """Label-flip data poisoning for one node's TRAIN shard (the
    stacked SPMD path flips the same rows — Scenario.__init__)."""
    from p2pfl_tpu.adversary import flip_labels

    nd = data.nodes[idx]
    data.nodes[idx] = dataclasses.replace(
        nd, y=flip_labels(nd.y, data.num_classes)
    )


def _node_adversary_kwargs(cfg: ScenarioConfig, idx: int, data, setup):
    """Per-node P2PNode attack/reputation kwargs (+ shard poisoning as
    a side effect on ``data``) from one _adversary_setup tuple."""
    mask, spec, want_rep = setup
    if mask is None:
        return {}
    if spec is not None and spec.kind == "labelflip" and mask[idx]:
        _poison_shard(data, idx)
    out = {"attack": spec if (spec is not None and mask[idx]) else None}
    if want_rep:
        from p2pfl_tpu.adversary import ReputationMonitor

        # one monitor PER NODE: trust is each node's local view in a
        # decentralized deployment — no shared state between processes
        out["reputation"] = ReputationMonitor(
            cfg.n_nodes, alpha=cfg.adversary.reputation_alpha,
            cutoff=cfg.adversary.reputation_cutoff,
        )
    return out


def _node_privacy_kwargs(cfg: ScenarioConfig, idx: int,
                         tls_dir: str | None = None) -> dict:
    """Per-node P2PNode dp/masker kwargs — derived from config alone
    (like _node_adversary_kwargs) so every process of a multi-process
    federation privatizes with the SAME noise streams and derives the
    SAME pair secrets. With a TLS dir (and the optional ``cryptography``
    package) secagg pair secrets come from P-256 ECDH over the scenario
    certs; otherwise the seeded fallback (see privacy.secagg's threat
    model)."""
    priv = cfg.privacy
    out: dict = {}
    if priv.dp:
        from p2pfl_tpu.privacy.dp import DPSpec

        out["dp"] = DPSpec(clip_norm=priv.clip_norm,
                           noise_multiplier=priv.noise_multiplier,
                           seed=cfg.seed)
    if priv.secagg:
        from p2pfl_tpu.privacy.secagg import PairwiseMasker

        out["masker"] = PairwiseMasker(
            idx, root_seed=cfg.seed, bits=priv.secagg_bits,
            pair_secrets=_tls_pair_secrets(tls_dir, idx, cfg.n_nodes),
        )
    return out


def _tls_pair_secrets(tls_dir: str | None, idx: int,
                      n: int) -> dict[int, bytes] | None:
    """ECDH pair secrets off the scenario TLS identity layer, or None
    (→ seeded fallback) when there is no TLS dir or no ``cryptography``
    package in this interpreter."""
    if not tls_dir:
        return None
    try:
        from cryptography import x509
        from cryptography.hazmat.primitives import serialization
    except ImportError:
        return None
    from p2pfl_tpu.privacy.secagg import pair_secrets_from_tls

    d = pathlib.Path(tls_dir)
    key_path = d / f"node{idx}.key"
    if not key_path.exists():
        return None
    private_key = serialization.load_pem_private_key(
        key_path.read_bytes(), password=None
    )
    peer_certs = {}
    for j in range(n):
        cert_path = d / f"node{j}.crt"
        if j != idx and cert_path.exists():
            peer_certs[j] = x509.load_pem_x509_certificate(
                cert_path.read_bytes()
            )
    return pair_secrets_from_tls(idx, private_key, peer_certs)


def _privacy_status(cfg: ScenarioConfig, round_num: int) -> dict:
    """DP spend gauges for a status record: the accountant's ε is a
    pure function of (config, rounds completed), so every process —
    and the monitor/health plane reading the records — sees the same
    number with no cross-process state."""
    priv = cfg.privacy
    if not priv.dp:
        return {}
    from p2pfl_tpu.privacy.dp import epsilon_at

    eps = epsilon_at(priv.noise_multiplier, int(round_num), priv.delta)
    return {
        "dp_epsilon": round(eps, 4) if math.isfinite(eps) else eps,
        "dp_epsilon_budget": priv.epsilon_budget,
    }


def _declares_full_mesh(cfg) -> bool:
    """True when the launcher can PROMISE every pair of nodes a healthy
    direct link: fully-connected topology with no link shaping at all.
    Any shaping (loss, delay, jitter, or a rate cap that can convoy
    beats behind multi-MB PARAMS frames) disqualifies — relay damping
    must not remove the repair path on links the shaper degrades.
    A scheduled partition plan disqualifies for the same reason: while
    a cut is open the "full mesh" promise is false by design."""
    net = cfg.network
    return cfg.topology == "fully" and not (
        net.loss_pct or net.delay_ms or net.jitter_ms or net.rate_mbps
        or getattr(net, "partitions", None)
    )


def _aggd_status(client: SidecarClient | None) -> dict:
    """Sidecar gauges for a status record: descriptor-queue depth vs
    slot releases is the pair the sidecar-stalled health rule compares,
    bytes_ingested is the live zero-copy-ingest odometer."""
    if client is None:
        return {}
    return {
        "aggd_desc_q_depth": client.queue_depth(),
        "aggd_slot_releases": client.slot_releases,
        "aggd_bytes_ingested": client.bytes_ingested,
    }


def _critpath_status(node) -> dict:
    """Flatten the node's last per-round critical-path snapshot into
    ``critpath_*`` status gauges — the monitor's WAIT% column and the
    webapp's breakdown pane read these. Empty before round 1 closes."""
    cp = node.critpath_last
    if not cp:
        return {}
    return {
        "critpath_round": cp["round"],
        "critpath_round_s": cp["round_s"],
        "critpath_fit_s": cp["fit_s"],
        "critpath_wire_s": cp["wire_s"],
        "critpath_wait_s": cp["wait_s"],
        "critpath_agg_s": cp["agg_s"],
        "critpath_other_s": cp["other_s"],
    }


def _crossdev_status(obj) -> dict:
    """Cross-device throughput gauges (round 20) for a status record:
    ``crossdev_clients_per_s`` plus, on streamed rounds, the prefetch
    bytes/stall pair. Reads the driver's ``crossdev_last`` dict
    (CrossDeviceScenario refreshes it per round); empty — and therefore
    rendered as "-" by the monitor — for anything that is not a
    cross-device driver."""
    last = getattr(obj, "crossdev_last", None)
    if not last:
        return {}
    return dict(last)


def _devprof_status(obj) -> dict:
    """Device-profiling gauges (MFU / achieved-TFLOPs / HBM+RSS
    watermarks) for a status record. Reads ``devprof_last`` off the
    learner (socket plane: the JaxLearner refreshes it per fit when
    ``P2PFL_DEVPROF`` is on) — accepts either the learner itself or a
    Node wrapping one. Empty — rendered "-" — when devprof is off."""
    last = getattr(obj, "devprof_last", None)
    if last is None:
        last = getattr(getattr(obj, "learner", None), "devprof_last", None)
    if not last:
        return {}
    return dict(last)


def _free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


async def _run_node(cfg: ScenarioConfig, idx: int, ports: list[int],
                    tls_dir: str | None = None,
                    hosts: list[str] | None = None,
                    bind: str = "127.0.0.1",
                    resume: bool = False,
                    sidecar: SidecarClient | None = None) -> dict:
    """One node's full lifecycle (node_start.py main analog).

    ``hosts`` gives each node's reachable address (container service
    names in a compose deployment; defaults to loopback for localhost
    federations); ``bind`` is this node's listen address ("0.0.0.0"
    inside containers so peers can reach it). ``resume=True`` is the
    supervisor's restart path: the node adopts its own periodic
    checkpoint and re-enters through the live-join handshake.
    """
    n = cfg.n_nodes
    hosts = hosts or ["127.0.0.1"] * n
    tls = None
    if tls_dir:
        from p2pfl_tpu.p2p.tls import load_node_credentials

        tls = load_node_credentials(tls_dir, idx)
    data = FederatedDataset.make(cfg.data, n)  # deterministic: same shards
    adv_kwargs = _node_adversary_kwargs(cfg, idx, data,
                                        _adversary_setup(cfg))
    priv_kwargs = _node_privacy_kwargs(cfg, idx, tls_dir=tls_dir)
    from p2pfl_tpu.learning.lora import maybe_wrap_lora

    learner = JaxLearner(
        model=maybe_wrap_lora(build_model(cfg.model), cfg,
                              data.nodes[idx].x[:1]),
        data=data.nodes[idx],
        objective=cfg.model.objective,
        optimizer=cfg.training.optimizer,
        learning_rate=cfg.training.learning_rate,
        momentum=cfg.training.momentum,
        weight_decay=cfg.training.weight_decay,
        momentum_dtype=cfg.training.momentum_dtype,
        batch_size=cfg.data.batch_size,
        seed=cfg.seed,
    )
    node = P2PNode(
        idx,
        learner,
        host=bind,
        port=ports[idx],
        role=cfg.nodes[idx].role,
        n_nodes=n,
        aggregator=get_aggregator(cfg.aggregator, **cfg.aggregator_kwargs),
        protocol=cfg.protocol,
        federation=cfg.federation,
        seed=cfg.seed,
        tls=tls,
        netem=cfg.network,
        full_mesh=_declares_full_mesh(cfg),
        wire_dtype=cfg.wire_dtype,
        elastic=cfg.elastic,
        fit_slowdown=cfg.nodes[idx].fit_slowdown,
        local_epochs=cfg.nodes[idx].epochs,
        checkpoint_dir=cfg.checkpoint_dir,
        checkpoint_every=cfg.checkpoint_every,
        resume=resume,
        joiner=resume,
        sidecar=sidecar,
        **adv_kwargs,
        **priv_kwargs,
    )
    await node.start()
    topo = generate_topology(cfg.topology, n, **cfg.topology_kwargs)
    # connect to higher-index neighbors; lower-index ones dial us.
    # retry until the peer's listener is up (replaces node_start.py:106's
    # fixed 30 s grace sleep)
    for j in topo.neighbors(idx):
        if j < idx:
            continue
        deadline = time.monotonic() + 60
        while True:
            try:
                await node.connect_to(hosts[j], ports[j])
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise
                await asyncio.sleep(0.1)
    # wait until every neighbor connection exists (either direction)
    want = set(topo.neighbors(idx))
    deadline = time.monotonic() + 60
    while not want <= set(node.peers) and time.monotonic() < deadline:
        await asyncio.sleep(0.1)
    status_task = None
    if cfg.log_dir:
        from p2pfl_tpu.utils.monitor import publish_status

        status_dir = pathlib.Path(cfg.log_dir) / cfg.name / "status"

        async def _publish_loop():
            # the reference's REPORT_STATUS_TO_CONTROLLER heartbeat
            # cycle (node.py:916-937, heartbeater.py:75-78)
            while True:
                publish_status(
                    status_dir, idx,
                    {"role": node.role, "round": node.round,
                     "peers": len(node.peers),
                     "leader": node.leader,
                     "round_p95_s": node.round_p95_s(),
                     "bytes_in": node.bytes_in,
                     "bytes_out": node.bytes_out,
                     # per-LINK wire totals: the partition-suspected
                     # health rule keys on cross-cohort counters going
                     # one-sided (json turns the int keys into strings)
                     "peer_bytes_in": dict(node.peer_bytes_in),
                     "peer_bytes_out": dict(node.peer_bytes_out),
                     "recompiles": obs_trace.xla_recompiles(),
                     **_privacy_status(cfg, node.round),
                     **_critpath_status(node),
                     **_crossdev_status(learner),
                     **_devprof_status(learner),
                     **_aggd_status(sidecar)},
                )
                await asyncio.sleep(cfg.protocol.heartbeat_period_s)

        status_task = asyncio.get_event_loop().create_task(_publish_loop())
    # warm the compiled programs off-loop BEFORE the round clock can
    # start (run_simulation warms every node the same way): the first
    # fit would otherwise bill its XLA compile to round 1 and skew
    # learn_wall_s, the number the multi-process bench reports
    await asyncio.get_running_loop().run_in_executor(None, learner.warm_up)
    if cfg.nodes[idx].start and not resume:
        # a resumed relaunch never re-starts the federation: it joins
        # the running one through the "jr" hello → STATE_SYNC handshake
        learner.init()
        node.set_start_learning(cfg.training.rounds,
                                cfg.training.epochs_per_round)
    await asyncio.wait_for(node.finished.wait(), timeout=600)
    # the learning loop already evaluated and recorded its own metrics
    # (the METRICS flood) — don't evaluate twice
    own = node.peer_metrics.get(idx)
    metrics = (
        {k: v for k, v in own.items() if k != "round"}
        if own is not None else learner.evaluate()
    )
    if status_task is not None:
        status_task.cancel()
        publish_status(
            status_dir, idx,
            {"role": node.role, "round": node.round,
             "peers": len(node.peers), "leader": node.leader,
             "round_p95_s": node.round_p95_s(),
             "bytes_in": node.bytes_in,
             "bytes_out": node.bytes_out, **metrics},
        )
    await node.stop()
    result = {"node": idx, "round": node.round,
              "round_p95_s": node.round_p95_s(),
              "bytes_in": node.bytes_in, "bytes_out": node.bytes_out,
              "params_bytes_out": node.params_bytes_out,
              **metrics}
    # round-loop wall clock (post-warm-up, excludes startup/diffusion):
    # what socket_round_s_24node_multiproc is computed from
    if node.learn_t0 is not None and node.learn_t1 is not None:
        result["learn_wall_s"] = round(node.learn_t1 - node.learn_t0, 3)
    return result


def node_main(config_path: str, idx: int | list[int], ports: list[int],
              tls_dir: str | None = None,
              hosts: list[str] | None = None,
              bind: str = "127.0.0.1",
              resume: bool = False) -> None:
    """Child-process entry. ``idx`` may be a LIST of node indices: all
    of them share this process's event loop (the k-nodes-per-process
    layouts the multi-process bench measures, e.g. 6 processes × 4
    nodes) — in-between the two extremes of run_simulation (n×1-loop)
    and one-process-per-node."""
    idxs = [idx] if isinstance(idx, int) else list(idx)
    cfg = ScenarioConfig.load(config_path)
    tracer = _trace_setup(cfg)
    if cfg.log_dir:
        # per-participant log trail + environment banner
        # (base_node.py:133-158, utils/env.py parity)
        from p2pfl_tpu.utils.env import log_environment
        from p2pfl_tpu.utils.nodelog import setup_node_logging

        setup_node_logging(cfg.log_dir, cfg.name, idxs[0])
        log_environment()

    # one sidecar per OS process: every node sharing this event loop
    # lands payloads into the same shared-memory arena (the per-HOST
    # deployment shape — each host runs its own aggd). Sizing: each of
    # this process's sessions holds up to n_nodes payload slots for the
    # whole round (full mesh, entries pinned until the fuse) plus a
    # result slot; +8 margin for in-flight reads
    sidecar = None
    if cfg.aggregation_plane == "sidecar":
        sidecar = SidecarClient(
            n_slots=len(idxs) * (cfg.n_nodes + 2) + 8)

    async def _run_all() -> list[dict]:
        return list(
            await asyncio.gather(
                *(_run_node(cfg, i, ports, tls_dir=tls_dir,
                            hosts=hosts, bind=bind, resume=resume,
                            sidecar=sidecar)
                  for i in idxs)
            )
        )

    try:
        results = asyncio.run(_run_all())
    except Exception as e:
        # an unhandled child-process exception is exactly the moment
        # the control-event ring matters: dump before dying so the
        # parent finds a postmortem next to the (absent) result line
        flight.record("proc.exception", nodes=idxs, error=repr(e))
        flight.dump(f"proc{idxs[0]}.exception")
        raise
    finally:
        if sidecar is not None:
            sidecar.close()
    if tracer.enabled:
        # one file per OS process; nodes sharing this event loop are
        # separated by lane inside it (traceview merges across files)
        tracer.export(
            process_name="nodes " + ",".join(map(str, idxs))
        )
    for result in results:
        print("P2PFL_RESULT " + json.dumps(result), flush=True)


async def _simulate(cfg: ScenarioConfig, timeout: float = 600) -> dict:
    n = cfg.n_nodes
    tracer = _trace_setup(cfg)
    data = FederatedDataset.make(cfg.data, n)
    topo = generate_topology(cfg.topology, n, **cfg.topology_kwargs)
    from p2pfl_tpu.learning.learner import SharedTrainer
    from p2pfl_tpu.learning.lora import maybe_wrap_lora

    shared = SharedTrainer(
        maybe_wrap_lora(build_model(cfg.model), cfg, data.nodes[0].x[:1]),
        objective=cfg.model.objective,
        optimizer=cfg.training.optimizer,
        learning_rate=cfg.training.learning_rate,
        momentum=cfg.training.momentum,
        weight_decay=cfg.training.weight_decay,
        momentum_dtype=cfg.training.momentum_dtype,
        batch_size=cfg.data.batch_size,
    )
    adv_setup = _adversary_setup(cfg)
    # shard poisoning mutates data.nodes — run BEFORE learners capture
    adv_kwargs = [
        _node_adversary_kwargs(cfg, i, data, adv_setup) for i in range(n)
    ]
    # in-process simulation has no TLS layer: secagg maskers run in
    # seeded-fallback pair-secret mode (privacy.secagg threat model)
    priv_kwargs = [_node_privacy_kwargs(cfg, i) for i in range(n)]
    # one shared sidecar for the whole in-process federation (simulation
    # mode models ONE host). Sizing: every session can hold up to n
    # payload slots for the whole round (full mesh, entries pinned
    # until the fuse) plus its result slot; +8 margin for in-flight
    # reads — exhaustion degrades to blob entries, never to wrong math
    sidecar = None
    if cfg.aggregation_plane == "sidecar":
        sidecar = SidecarClient(n_slots=n * (n + 2) + 8)
    nodes = [
        P2PNode(
            i,
            JaxLearner(model=None, data=data.nodes[i],
                       batch_size=cfg.data.batch_size, seed=cfg.seed,
                       trainer=shared),
            role=cfg.nodes[i].role,
            n_nodes=n,
            aggregator=get_aggregator(cfg.aggregator, **cfg.aggregator_kwargs),
            protocol=cfg.protocol,
            federation=cfg.federation,
            seed=cfg.seed,
            netem=cfg.network,
            full_mesh=_declares_full_mesh(cfg),
            wire_dtype=cfg.wire_dtype,
            elastic=cfg.elastic,
            fit_slowdown=cfg.nodes[i].fit_slowdown,
            local_epochs=cfg.nodes[i].epochs,
            checkpoint_dir=cfg.checkpoint_dir,
            checkpoint_every=cfg.checkpoint_every,
            sidecar=sidecar,
            **adv_kwargs[i],
            **priv_kwargs[i],
        )
        for i in range(n)
    ]
    for node in nodes:
        await node.start()
    for i in range(n):
        for j in topo.neighbors(i):
            if j > i:
                await nodes[i].connect_to(nodes[j].host, nodes[j].port)
    starter = next(
        (i for i, nc in enumerate(cfg.nodes) if nc.start), 0
    )
    nodes[starter].learner.init()
    # warm EVERY node's compiled programs before the clock starts
    # (ragged dirichlet shards mean distinct shapes per node; the jit
    # cache dedups identical ones, so iid costs one compile): the
    # first fit/evaluate would otherwise bill their compiles to round
    # 1 and skew the steady-state round time being measured
    for node in nodes:
        node.learner.warm_up()
    # steady-state recompile accounting starts HERE: warm-up compiles
    # are expected; anything counted past this point is a mid-round
    # recompile (the round-7 storm this counter exists to surface)
    obs_trace.reset_xla_counters()

    # ---- scripted churn (round 11): on the socket plane FaultEvents
    # drive ACTUAL node death and live re-join — a "crash" is an
    # abrupt teardown peers must detect via heartbeat silence and the
    # probe machine, a "join"/"recover" builds a FRESH P2PNode that
    # re-enters through the live-join handshake ("jr" hello →
    # STATE_SYNC model fetch) instead of a scripted beating flag.
    el = cfg.elastic
    joined: list[int] = []
    restarted: list[int] = []

    async def _rejoin_node(i: int, resume: bool = False) -> None:
        ln = JaxLearner(model=None, data=data.nodes[i],
                        batch_size=cfg.data.batch_size, seed=cfg.seed,
                        trainer=shared)
        nd = P2PNode(
            i, ln, role=cfg.nodes[i].role, n_nodes=n,
            aggregator=get_aggregator(cfg.aggregator,
                                      **cfg.aggregator_kwargs),
            protocol=cfg.protocol, federation=cfg.federation,
            seed=cfg.seed, netem=cfg.network,
            full_mesh=_declares_full_mesh(cfg),
            wire_dtype=cfg.wire_dtype, elastic=el,
            fit_slowdown=cfg.nodes[i].fit_slowdown,
            local_epochs=cfg.nodes[i].epochs,
            joiner=True,
            checkpoint_dir=cfg.checkpoint_dir,
            checkpoint_every=cfg.checkpoint_every,
            resume=resume,
            sidecar=sidecar,
            **adv_kwargs[i],
            # fresh masker, same derived secrets: pair streams are a
            # pure function of (seed, pair, round), so a rejoiner
            # re-derives exactly what the fleet expects of it
            **_node_privacy_kwargs(cfg, i),
        )
        nodes[i] = nd
        await nd.start()
        ln.warm_up()  # shared trainer is already compiled — cheap
        for j in topo.neighbors(i):
            other = nodes[j]
            if other is nd or other.finished.is_set():
                continue
            try:
                await nd.connect_to(other.host, other.port)
            except OSError:
                continue
        (restarted if resume else joined).append(i)

    status_task = None
    publish_pass = None
    if cfg.log_dir:
        # simulation-mode status publishing (round 12): the same
        # records _run_node's per-process loop publishes, emitted for
        # every node from one task — so the monitor/healthcheck see an
        # in-process federation too.
        from p2pfl_tpu.utils.monitor import publish_status

        status_dir = pathlib.Path(cfg.log_dir) / cfg.name / "status"

        published_final: set[int] = set()

        def publish_pass() -> None:
            for nd in nodes:
                if nd.finished.is_set():
                    # a CRASHED node never publishes again — its record
                    # ages out like a killed process's, which is what
                    # the node-dead rule keys on. A node that finished
                    # the schedule gracefully gets ONE final record so
                    # the dashboards and the healthcheck see its true
                    # final round instead of a stale mid-run snapshot.
                    if nd._crashed or nd.idx in published_final:
                        continue
                    published_final.add(nd.idx)
                publish_status(
                    status_dir, nd.idx,
                    {"role": nd.role, "round": nd.round,
                     "peers": len(nd.peers), "leader": nd.leader,
                     "round_p95_s": nd.round_p95_s(),
                     "bytes_in": nd.bytes_in,
                     "bytes_out": nd.bytes_out,
                     "peer_bytes_in": dict(nd.peer_bytes_in),
                     "peer_bytes_out": dict(nd.peer_bytes_out),
                     "recompiles": obs_trace.xla_recompiles(),
                     **_privacy_status(cfg, nd.round),
                     **_critpath_status(nd),
                     **_crossdev_status(nd),
                     **_devprof_status(nd),
                     **_aggd_status(sidecar)},
                )

        async def _status_loop() -> None:
            while True:
                publish_pass()
                await asyncio.sleep(cfg.protocol.heartbeat_period_s)

        status_task = asyncio.create_task(_status_loop())

    fault_task = None
    watch_tasks: list[asyncio.Task] = []
    recovery: dict = {"partitions": 0, "heals": 0}
    if cfg.faults:
        events = sorted(cfg.faults, key=lambda f: (f.round, f.node))

        async def _recovery_watch(t_heal: float,
                                  rounds_at_heal: dict[int, int]) -> None:
            # chaos_recovery_s: heal observation → first POST-MERGE
            # round, i.e. every live node has completed a round that
            # started after the heal (its front moved past the snapshot)
            while True:
                live = [nd for nd in nodes if not nd.finished.is_set()]
                if not live:
                    break
                if all(nd.round > rounds_at_heal.get(nd.idx, -1)
                       for nd in live):
                    break
                await asyncio.sleep(0.05)
            recovery["recovery_s"] = round(time.monotonic() - t_heal, 3)
            flight.record("sim.recovered",
                          recovery_s=recovery["recovery_s"])

        async def _fault_driver() -> None:
            for f in events:
                while True:
                    fronts = [nd.round for nd in nodes
                              if not nd.finished.is_set()]
                    if not fronts:
                        return  # federation over; remaining faults moot
                    if max(fronts) >= f.round:
                        break
                    await asyncio.sleep(0.05)
                if f.kind == "crash":
                    await nodes[f.node].crash()
                elif f.kind == "partition":
                    # same cut on every live node → symmetric sever
                    recovery["partitions"] += 1
                    for nd in nodes:
                        if not nd.finished.is_set():
                            nd.apply_partition(f.groups)
                elif f.kind == "heal":
                    recovery["heals"] += 1
                    snap = {nd.idx: nd.round for nd in nodes
                            if not nd.finished.is_set()}
                    for nd in nodes:
                        if not nd.finished.is_set():
                            nd.heal_partition()
                    watch_tasks.append(asyncio.create_task(
                        _recovery_watch(time.monotonic(), snap)))
                elif f.kind == "restart":
                    # crash-consistent relaunch: the fresh node adopts
                    # the newer of (own checkpoint, peer STATE_SYNC)
                    await _rejoin_node(f.node, resume=True)
                else:  # recover / join: live re-entry via the handshake
                    await _rejoin_node(f.node)

        fault_task = asyncio.create_task(_fault_driver())

    async def _all_finished() -> None:
        # replacement-aware: a join swaps nodes[i] for a fresh object,
        # so a plain gather over the initial events would miss it
        while not all(nd.finished.is_set() for nd in nodes):
            await asyncio.sleep(0.1)

    t0 = time.monotonic()
    nodes[starter].set_start_learning(
        cfg.training.rounds, cfg.training.epochs_per_round
    )
    try:
        await asyncio.wait_for(_all_finished(), timeout=timeout)
    finally:
        wall = time.monotonic() - t0
        if fault_task is not None:
            fault_task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await fault_task
        for wt in watch_tasks:
            # give a still-pending recovery watch one tick to observe
            # the (now fully finished) federation, then reap it
            if not wt.done():
                with contextlib.suppress(asyncio.TimeoutError):
                    await asyncio.wait_for(wt, timeout=0.5)
            if not wt.done():
                wt.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await wt
        if status_task is not None:
            status_task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await status_task
        if publish_pass is not None:
            # one synchronous pass after the loop dies: the LAST node
            # to finish otherwise races the cancel and never gets its
            # graceful final record
            publish_pass()
        for node in nodes:
            await node.stop()
        if sidecar is not None:
            sidecar.close()
    accs = [
        m.get("accuracy") for m in
        (nd.peer_metrics.get(nd.idx) or {} for nd in nodes)
        if m.get("accuracy") is not None
    ]
    out = {
        "n_nodes": n,
        "rounds": min(nd.round for nd in nodes),
        "wall_s": round(wall, 3),
        "round_s": round(wall / max(cfg.training.rounds, 1), 3),
        "mean_accuracy": (
            round(sum(accs) / len(accs), 4) if accs else None
        ),
        # post-warm-up recompiles (0 on a healthy run — see the reset
        # above) and the federation's total wire traffic
        "xla_recompiles": obs_trace.xla_recompiles(),
        "bytes_in": sum(nd.bytes_in for nd in nodes),
        "bytes_out": sum(nd.bytes_out for nd in nodes),
        # encoded PARAMS blob bytes × targets — the wire-dtype A/B's
        # numerator, isolated from control-plane traffic
        "params_bytes_out": sum(nd.params_bytes_out for nd in nodes),
        # payload bytes the event loop itself decoded/materialized on
        # the round path — the aggregation-plane A/B's contrast metric
        # (sidecar arm pins this at 0; inline arm pays it in full)
        "loop_payload_touch_bytes": sum(
            nd.loop_payload_touch_bytes for nd in nodes),
    }
    if sidecar is not None:
        out["aggd_bytes_ingested"] = sidecar.bytes_ingested
        out["aggd_fused_rounds"] = sidecar.fused_rounds
        out["aggd_fallbacks"] = sidecar.fallbacks
    if cfg.faults or el.active:
        # elasticity accounting: who crashed/re-joined, which nodes ran
        # slow, and whether the async close rule was on — the churn
        # bench and the elasticity tests read these
        out["churn"] = {
            "async": el.async_aggregation,
            "crashes": sorted(f.node for f in cfg.faults
                              if f.kind == "crash"),
            "joined": sorted(joined),
            "restarted": sorted(restarted),
            "stragglers": [i for i in range(n)
                           if cfg.nodes[i].fit_slowdown > 1.0],
        }
        if recovery["partitions"] or recovery["heals"]:
            out["churn"]["partitions"] = recovery["partitions"]
            out["churn"]["heals"] = recovery["heals"]
            if "recovery_s" in recovery:
                out["churn"]["recovery_s"] = recovery["recovery_s"]
    if tracer.enabled:
        out["obs"] = tracer.summarize()
        tracer.export(process_name=f"sim[{cfg.name}]")
    if any(nd.reputation is not None for nd in nodes):
        # each node's LOCAL trust vector (decentralized: no shared
        # monitor) + who it would exclude — the robustness tests and
        # the monitor read these
        out["trust"] = [
            [round(float(t), 4) for t in nd.reputation.trust]
            if nd.reputation is not None else None
            for nd in nodes
        ]
        out["suspects"] = sorted(
            {s for nd in nodes if nd.reputation is not None
             for s in nd.reputation.suspects()}
        )
    return out


def run_simulation(cfg: ScenarioConfig, timeout: float = 600) -> dict:
    """ALL nodes of a socket federation in one process/event loop —
    the reference's simulation mode (``scenario_args.simulation``,
    SURVEY §4: same code path, loopback TCP, no cluster). One
    ``SharedTrainer`` serves every node, so the model compiles once
    instead of ``n_nodes`` times. Returns wall-clock and per-round
    timing plus the federation's mean final accuracy.

    Under ``P2PFL_SANITIZE=1`` the run executes with jax_debug_nans,
    asyncio debug mode, and leaked-resource/never-awaited warnings
    promoted to errors (utils/sanitize.py)."""
    from p2pfl_tpu.utils import sanitize

    with sanitize.scope():
        return asyncio.run(_simulate(cfg, timeout),
                           debug=sanitize.asyncio_debug())


class ChipContention(RuntimeError):
    """More node processes than TPU chips: refused before any child
    starts."""


_CHIP_PROBE = (
    "import jax; print('P2PFL_TPU_CHIPS', "
    "jax.device_count() if jax.default_backend() == 'tpu' else 0)"
)


def _tpu_chips() -> int:
    """How many TPU chips a child of this process would find; 0 when
    its default backend is not a TPU. Asked of a throwaway subprocess
    that has exited — and released the device — before any child
    starts, so the parent itself never initialises a backend: a chip
    belongs to one process at a time, and a parent holding it would
    starve every child."""
    res = subprocess.run([sys.executable, "-c", _CHIP_PROBE],
                         capture_output=True, text=True, timeout=300)
    for line in res.stdout.splitlines():
        if line.startswith("P2PFL_TPU_CHIPS "):
            return int(line.split()[1])
    raise RuntimeError(
        f"device probe failed (rc={res.returncode}): "
        f"{res.stderr.strip()[-400:]}")


def _child_envs(n_groups: int, n_nodes: int,
                platform: str | None) -> list[dict | None]:
    """One environment per child process (None = inherit): on a TPU
    host each child is pinned to its own chip, or the launch is refused
    — N processes left to race for one chip end with one winner and
    N-1 start-up failures or hangs."""
    if platform not in (None, "tpu"):
        return [None] * n_groups  # children are told their platform
    chips = _tpu_chips()
    if chips == 0:
        return [None] * n_groups
    if n_groups > chips:
        raise ChipContention(
            f"{n_groups} node processes would contend for {chips} TPU "
            f"chip(s), and a chip belongs to one process at a time. Run "
            f"the children on the CPU (--platform cpu), or pack the nodes "
            f"into at most {chips} process(es) (--nodes-per-proc "
            f"{math.ceil(n_nodes / chips)}).")
    return [
        dict(os.environ,
             TPU_VISIBLE_CHIPS=str(i),
             TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
             TPU_PROCESS_BOUNDS="1,1,1",
             # several single-chip runtimes on one host: libtpu's
             # one-load-per-host lock would refuse the second child
             ALLOW_MULTIPLE_LIBTPU_LOAD="true")
        for i in range(n_groups)
    ]


def launch(cfg: ScenarioConfig, config_path: str | pathlib.Path,
           platform: str | None = None,
           nodes_per_proc: int = 1,
           max_restarts: int = 0,
           restart_backoff_s: float = 1.0) -> list[dict]:
    """Spawn node processes; collect their results.

    ``max_restarts`` > 0 turns the parent into a supervisor: a child
    group that dies (non-zero exit) is relaunched with ``--resume`` —
    each node adopts the newer of its own periodic checkpoint
    (``cfg.checkpoint_dir``) and a peer's STATE_SYNC — under
    exponential backoff (``restart_backoff_s * 2^(attempt-1)``, capped
    at 30 s), up to ``max_restarts`` times per group.

    ``nodes_per_proc`` > 1 packs k nodes into each child's event loop
    (``--node "0,1,2,3"``), so a 24-node federation can run as 24×1,
    6×4, … — the layouts the multi-process bench compares against the
    all-in-one-loop simulation mode.

    ``platform="cpu"`` forces the children onto the CPU backend. Left
    to the default backend on a TPU host, the launcher decides from
    the chip count and the group count (``_child_envs``): each child is
    pinned to its own chip when there are enough chips, and otherwise
    :class:`ChipContention` is raised before any child starts. The
    parent never initialises a backend.

    With ``cfg.encrypt`` the parent mints a scenario CA + per-node
    certificates next to the config file and every connection runs
    mutual TLS (controller-stamps-credentials analog of the
    reference's encrypter wiring, base_node.py:246-256).
    """
    ports = _free_ports(cfg.n_nodes)
    tls_dir = None
    if cfg.encrypt:
        from p2pfl_tpu.p2p.tls import make_scenario_credentials

        tls_dir = str(pathlib.Path(config_path).resolve().parent / "tls")
        make_scenario_credentials(tls_dir, cfg.n_nodes, name=cfg.name)
    k = max(int(nodes_per_proc), 1)
    groups = [list(range(i, min(i + k, cfg.n_nodes)))
              for i in range(0, cfg.n_nodes, k)]
    envs = _child_envs(len(groups), cfg.n_nodes, platform)

    def _spawn(gi: int, cmd: list[str]) -> subprocess.Popen:
        return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True,
                                env=envs[gi])

    cmds, procs = [], []
    for group in groups:
        cmd = [sys.executable, "-m", "p2pfl_tpu.p2p.launch",
               str(config_path), "--node", ",".join(map(str, group)),
               "--ports", ",".join(map(str, ports))]
        if platform:
            cmd += ["--platform", platform]
        if tls_dir:
            cmd += ["--tls-dir", tls_dir]
        cmds.append(cmd)
        procs.append(_spawn(len(procs), cmd))

    def _supervise(gi: int) -> str:
        """Wait out one group, restarting it (with ``--resume``) on
        non-zero exit until the restart budget runs dry. Returns the
        concatenated stdout of every attempt — the parent scans it for
        P2PFL_RESULT lines, so a successful relaunch reports exactly
        like an uninterrupted child."""
        p, attempt, chunks = procs[gi], 0, []
        while True:
            out, _ = p.communicate(timeout=900)
            chunks.append(out)
            if p.returncode == 0 or attempt >= max_restarts:
                if p.returncode != 0:
                    # a dead child leaves no result line: say why
                    print(f"p2pfl_tpu.p2p.launch: child for nodes "
                          f"{groups[gi]} exited rc={p.returncode}:\n"
                          f"{out[-2000:]}", file=sys.stderr, flush=True)
                return "".join(chunks)
            attempt += 1
            delay = min(restart_backoff_s * (2.0 ** (attempt - 1)), 30.0)
            flight.record("launch.restart", group=groups[gi],
                          attempt=attempt, rc=p.returncode,
                          backoff_s=round(delay, 3))
            time.sleep(delay)
            p = _spawn(gi, cmds[gi] + ["--resume"])

    if max_restarts > 0:
        # supervise groups concurrently: a crashed group must respawn
        # while its peers are still mid-federation, not after they exit
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=len(groups)) as pool:
            outs = list(pool.map(_supervise, range(len(groups))))
    else:
        outs = [_supervise(gi) for gi in range(len(groups))]
    results = []
    for out in outs:
        for line in out.splitlines():
            if line.startswith("P2PFL_RESULT "):
                results.append(json.loads(line[len("P2PFL_RESULT "):]))
    return results


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="p2pfl_tpu.p2p.launch")
    ap.add_argument("config")
    ap.add_argument("--node", default=None,
                    help="node index, or comma-separated indices to run "
                         "on one event loop (child mode)")
    ap.add_argument("--nodes-per-proc", type=int, default=1,
                    help="parent mode: pack k nodes into each child "
                         "process (e.g. 24 nodes, k=4 -> 6 processes)")
    ap.add_argument("--ports", default=None,
                    help="comma-separated port per node (child mode)")
    ap.add_argument("--platform", default=None,
                    help="force a JAX platform (e.g. cpu) in children")
    ap.add_argument("--tls-dir", default=None,
                    help="directory with scenario TLS material (child mode)")
    ap.add_argument("--hosts", default=None,
                    help="comma-separated per-node hostnames (child mode; "
                         "compose service names in a container deployment)")
    ap.add_argument("--bind", default="127.0.0.1",
                    help="listen address (0.0.0.0 inside containers)")
    ap.add_argument("--resume", action="store_true",
                    help="child mode: adopt the node's periodic "
                         "checkpoint before joining (restart path)")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="parent mode: relaunch a dead child group with "
                         "--resume up to this many times")
    ap.add_argument("--restart-backoff-s", type=float, default=1.0,
                    help="base of the exponential restart backoff "
                         "(doubles per attempt, capped at 30 s)")
    args = ap.parse_args(argv)
    compile_cache.enable()  # parent and child: the child inherits it
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    if args.node is not None:
        node_main(args.config,
                  [int(i) for i in str(args.node).split(",")],
                  [int(p) for p in args.ports.split(",")],
                  tls_dir=args.tls_dir,
                  hosts=args.hosts.split(",") if args.hosts else None,
                  bind=args.bind,
                  resume=args.resume)
        return 0
    cfg = ScenarioConfig.load(args.config)
    try:
        results = launch(cfg, args.config, platform=args.platform,
                         nodes_per_proc=args.nodes_per_proc,
                         max_restarts=args.max_restarts,
                         restart_backoff_s=args.restart_backoff_s)
    except ChipContention as e:
        print(f"p2pfl_tpu.p2p.launch: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"nodes": results}))
    return 0 if len(results) == cfg.n_nodes else 1


if __name__ == "__main__":
    sys.exit(main())
