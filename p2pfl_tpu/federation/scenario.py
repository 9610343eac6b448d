"""Scenario: build + run a whole federation.

The successor of the reference's deploy-and-train path (Controller.
load_configurations_and_start_nodes → N processes → Node.
set_start_learning → per-node round loops, SURVEY.md §3.1-3.3),
collapsed into one host object driving one jitted round program:

    scenario = Scenario(ScenarioConfig(...))
    result = scenario.run()

Per round the host: (1) applies scheduled fault events and advances
the virtual membership clock (heartbeat eviction), (2) rotates SDFL
leadership among alive nodes, (3) recomputes the round plan if
membership/leadership changed, (4) invokes the compiled SPMD round,
(5) periodically evaluates, logs, and checkpoints. There are no grace
sleeps — the reference's 30 s + 5 s/neighbor startup dead time
(node_start.py:106,112) is replaced by compile time, which is cached
after the first round.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from p2pfl_tpu.adversary import (
    AttackSpec,
    ReputationMonitor,
    flip_labels,
    malicious_indices,
)
from p2pfl_tpu.config.schema import ScenarioConfig
from p2pfl_tpu.core.aggregators import FedAvg, get_aggregator
from p2pfl_tpu.datasets import CrossDeviceData, FederatedDataset
from p2pfl_tpu.federation.checkpoint import (
    all_checkpoints,
    load_checkpoint,
    save_checkpoint,
)
from p2pfl_tpu.federation.events import Events, Observable
from p2pfl_tpu.federation.membership import Membership
from p2pfl_tpu.federation.sampling import sample_clients, sample_cohorts
from p2pfl_tpu.learning.learner import make_step_fns
from p2pfl_tpu.models.base import build_model
from p2pfl_tpu.parallel.federated import (
    FederatedState,
    build_cross_device_stream_fns,
    build_eval_fn,
    build_round_fn,
    build_round_fn_cross_device,
    build_round_fn_sparse,
    cross_device_wn,
    init_federation,
    make_round_plan,
    round_flops,
    staleness_scale,
    with_staged_buffer,
)
from p2pfl_tpu.parallel.mesh import cohort_shard_mesh
from p2pfl_tpu.obs import devprof, flight
from p2pfl_tpu.obs import trace as obs_trace
from p2pfl_tpu.parallel.transport import MeshTransport, edge_offsets
from p2pfl_tpu.topology.topology import generate_topology
from p2pfl_tpu.utils.metrics import MetricsLogger
from p2pfl_tpu.utils.monitor import publish_status
from p2pfl_tpu.utils.telemetry import resource_snapshot

#: the spans of ``run()`` outside its rounds, and the parts of
#: ``scenario.log``: names the benchmark's readers import
#: (``benchmark/hostspans.py``). ``SPAN_RUN`` is the root of everything a
#: ``run()`` does: the rounds and the closing ``scenario.evaluate`` are
#: its children, ``SPAN_RUN_ENTER`` what comes before the first round,
#: ``SPAN_RUN_EXIT`` (two of them) what lies between the last round and
#: the closing evaluation and what follows that evaluation
SPAN_RUN = "scenario.run"
SPAN_RUN_ENTER = "scenario.run.enter"
SPAN_RUN_EXIT = "scenario.run.exit"
SPAN_LOG_METRICS = "scenario.log.metrics"
SPAN_LOG_RESOURCES = "scenario.log.resources"
SPAN_LOG_WRITE = "scenario.log.write"


@dataclasses.dataclass
class ScenarioResult:
    """What a run produces (the reference's equivalent is TB/W&B logs
    plus the SQLite scenario row)."""

    final_accuracy: float  # mean over alive nodes, central test set
    per_node_accuracy: list[float]
    rounds_run: int
    round_times_s: list[float]
    history: list[dict]  # metric records
    rounds_to_target: int | None = None  # first round hitting target_acc
    min_accuracy: float = 0.0  # min over ALIVE nodes (dead excluded)


class Scenario(Observable):
    """Build and drive a federation from a ScenarioConfig."""

    @obs_trace.program_scope()
    def __init__(self, config: ScenarioConfig, dataset: FederatedDataset | None = None):
        super().__init__()
        if config.cross_device.active:
            raise ValueError(
                "config.cross_device is active — Scenario drives one "
                "live row per node; use CrossDeviceScenario for the "
                "sampled K-of-N regime"
            )
        if config.privacy.secagg:
            # the sparse-transport × attack precedent: the SPMD round
            # has no per-pair wire to mask — every row reads the stacked
            # params directly, so "secure aggregation" here would be
            # theater. Fail loud; secagg is a socket-plane feature.
            raise ValueError(
                "privacy.secagg is a socket-plane feature (pairwise "
                "masks ride the PARAMS wire); the SPMD Scenario shares "
                "one device array and has nothing to mask — run the "
                "socket plane (p2p.launch) instead"
            )
        self.config = config
        n = config.n_nodes
        with obs_trace.stage("scenario.init.data"):
            self.dataset = dataset or FederatedDataset.make(config.data, n)
        self.model = build_model(config.model)
        if config.lora.active:
            # adapter-only federation: the wrapped model trains (and
            # federates) the adapter subtree over a frozen base derived
            # deterministically from (model config, seed) — the SAME
            # derivation every socket node process uses, so the planes
            # share one base bit-exactly
            from p2pfl_tpu.learning.lora import maybe_wrap_lora

            with obs_trace.stage("scenario.init.base"):
                self.model = maybe_wrap_lora(
                    self.model, config,
                    jnp.asarray(self.dataset.nodes[0].x[:1]),
                )
                jax.block_until_ready(self.model.base)
        with obs_trace.stage("scenario.init.build"):
            self.fns = make_step_fns(
                self.model,
                objective=config.model.objective,
                optimizer=config.training.optimizer,
                learning_rate=config.training.learning_rate,
                momentum=config.training.momentum,
                weight_decay=config.training.weight_decay,
                momentum_dtype=config.training.momentum_dtype,
                batch_size=config.data.batch_size,
            )
        self.topology = generate_topology(
            config.topology, n, **config.topology_kwargs
        )
        self.aggregator = get_aggregator(
            config.aggregator, **config.aggregator_kwargs
        )
        self.roles = [nc.role for nc in config.nodes]
        self.membership = Membership(n, config.protocol)
        # multi-host (jax.distributed) job: every process runs the same
        # host trajectory (deterministic from config.seed), but only
        # process 0 owns the log/status/profile artifacts
        self._proc0 = jax.process_index() == 0
        self.logger = MetricsLogger(config.log_dir if self._proc0 else None,
                                    config.name,
                                    tensorboard=config.tensorboard,
                                    wandb=config.wandb and self._proc0)
        if self.logger.dir is not None:
            # topology render next to the metrics (controller.py:301 /
            # monitoring-map analog) — best-effort: a render/save
            # failure must never abort the run for an optional PNG
            try:
                from p2pfl_tpu.utils.draw import draw_topology

                draw_topology(self.topology,
                              self.logger.dir / "topology.png",
                              roles=self.roles)
            except Exception:
                pass
            try:
                # 3-D/geo topology export for the dashboard map
                # (topologymanager.py:151-173 + 320-355) — atomic: the
                # webapp map tails this file while the run is live
                import json as _json

                from p2pfl_tpu.utils.fsio import atomic_write_text

                atomic_write_text(
                    self.logger.dir / "topology_3d.json",
                    _json.dumps(self.topology.to_3d(seed=config.seed)),
                )
            except Exception:
                pass
        self.transport = MeshTransport(n)
        self.leader = next(
            (i for i, nc in enumerate(config.nodes)
             if nc.role in ("aggregator", "server")),
            0,
        )
        self._rng = np.random.default_rng(config.seed)
        self._faults_by_round: dict[int, list] = {}
        for f in config.faults:
            self._faults_by_round.setdefault(f.round, []).append(f)
        self._base_trains = np.array(
            [r in ("trainer", "aggregator", "server") for r in self.roles]
        )

        # ---- adversary wiring: the malicious cohort, the update
        # transform, and the trust monitor all derive from config alone,
        # so the SPMD and socket paths agree on who attacks and how
        adv = config.adversary
        self.malicious = (
            malicious_indices(n, adv.fraction, adv.seed, tuple(adv.nodes))
            if adv.active else np.zeros(n, bool)
        )
        self.attack = (
            AttackSpec(kind=adv.kind, scale=adv.scale, seed=adv.seed)
            if adv.active else None
        )
        self.reputation = (
            ReputationMonitor(n, alpha=adv.reputation_alpha,
                              cutoff=adv.reputation_cutoff)
            if adv.reputation else None
        )

        # ---- privacy wiring (round 21): every training node's
        # outgoing update is clipped + noised in-jit, keyed by
        # (config.seed, node, round) — the same streams the socket
        # plane draws, so the planes privatize bit-identically. The
        # accountant's ε is a pure function of rounds completed, so
        # every process reads the same spend from config alone.
        priv = config.privacy
        self.dp_spec = None
        self.accountant = None
        if priv.dp:
            from p2pfl_tpu.privacy.dp import DPSpec, PrivacyAccountant

            self.dp_spec = DPSpec(
                clip_norm=priv.clip_norm,
                noise_multiplier=priv.noise_multiplier,
                seed=config.seed,
            )
            self.accountant = PrivacyAccountant(
                priv.noise_multiplier, delta=priv.delta
            )
        self.dp_mask = (
            self._base_trains.copy() if priv.dp else np.zeros(n, bool)
        )

        # ---- elasticity wiring (round 11): in async mode a straggler
        # of compute class k delivers updates ~k-1 rounds stale, and
        # the SPMD twin of the socket session's entry-weight discount
        # is the SAME host-side f32 formula applied as a COLUMN scale
        # on the mixing matrix (the reputation pattern: w = mix *
        # n_samples, so scaling column j reweights node j's
        # contribution in every aggregate — no round-fn change, no
        # recompile). Static across rounds, so it composes with the
        # plan cache.
        el = config.elastic
        self._stale_scale: np.ndarray | None = None
        if el.async_aggregation and el.staleness_beta > 0.0:
            stale_rounds = np.asarray(
                [nc.fit_slowdown - 1.0 for nc in config.nodes], np.float32
            )
            if np.any(stale_rounds > 0.0):
                self._stale_scale = staleness_scale(
                    stale_rounds, el.staleness_beta
                )

        # ---- device-side setup
        with obs_trace.stage("scenario.init.data"):
            x, y, smask, nsamp = self.dataset.stacked()
            if self.attack is not None and self.attack.kind == "labelflip":
                # data poisoning happens at the shard, not the update: flip
                # the malicious rows of the stacked train labels (identical
                # math to the socket path flipping its per-node shard)
                y = np.array(y, copy=True)
                for i in np.flatnonzero(self.malicious):
                    y[i] = flip_labels(y[i], self.dataset.num_classes)
            tr = self.transport
            # host arrays go to the mesh as they are: a jnp.asarray here
            # would land every stacked array whole on the default device
            # first (MeshTransport._place)
            self._data_args = tuple(
                tr.put_stacked(a) for a in (x, y, smask, nsamp)
            )
            self._x_test = tr.put_replicated(self.dataset.x_test)
            self._y_test = tr.put_replicated(self.dataset.y_test)
        with obs_trace.stage("scenario.init.build"):
            self.sparse_transport = self._choose_sparse()
            # ONE wire-precision knob (config.wire_dtype) across planes:
            # on the SPMD plane the exchange is device math, so bf16 is the
            # hardware-native reduced precision; int8 (a socket-plane
            # encoding with per-leaf scales) falls back to bf16 here
            self._exchange_dtype = (
                jnp.bfloat16 if config.wire_dtype in ("bf16", "int8") else None
            )
            if self.sparse_transport:
                round_fn = build_round_fn_sparse(
                    self.fns, self.topology, tr.mesh,
                    epochs=config.training.epochs_per_round,
                    exchange_dtype=self._exchange_dtype,
                    exchange_overlap=config.exchange_overlap,
                )
            else:
                # one shared robust aggregate when every aggregating row is
                # identical (single-leader CFL/SDFL; fully-connected DFL):
                # the per-row path is O(n) redundant aggregations there
                adj = self.topology.adjacency
                fully = bool(
                    np.all(adj | np.eye(n, dtype=bool))
                )
                shared = (
                    config.federation in ("CFL", "SDFL")
                    or (config.federation == "DFL" and fully)
                )
                round_fn = build_round_fn(
                    self.fns, aggregator=self.aggregator,
                    epochs=config.training.epochs_per_round,
                    exchange_dtype=self._exchange_dtype,
                    shared_aggregate=shared,
                    # DFL plans always adopt their own row (make_round_plan)
                    # -> the agg[adopt] whole-stack gather pass is elided;
                    # CFL/SDFL adopt the leader's row and keep it
                    identity_adopt=config.federation == "DFL",
                    attack=self.attack,
                    malicious=self.malicious,
                    update_stats=self.reputation is not None,
                    exchange_overlap=config.exchange_overlap,
                    dp=self.dp_spec,
                    dp_mask=self.dp_mask,
                )
            # what the model holds once and does not train (an adapter
            # federation's base) is the programs' LAST argument, on every
            # device of the mesh, and not a constant inside them
            from p2pfl_tpu.learning.lora import frozen_args, frozen_argument

            if config.lora.active:  # the one copy, where the mesh is
                self.model.base = tr.put_replicated(self.model.base)
            self._frozen = frozen_args(self.model)
            self._round_fn = tr.compile_round(
                frozen_argument(self.model, round_fn))
            self._eval_fn = tr.compile_eval(
                frozen_argument(self.model, build_eval_fn(self.fns)))
        with obs_trace.stage("scenario.init.federation"):
            fed0 = init_federation(self.fns, jnp.asarray(x[0, :1]), n,
                                   seed=config.seed)
            if config.exchange_overlap == "staged":
                # seed the double buffer at zero weight: staged round 0
                # reduces to pure local training (with_staged_buffer)
                fed0 = with_staged_buffer(fed0)
            self.fed = tr.put_stacked(fed0)
            self._maybe_resume()
        self._steps_per_round = (
            max(x.shape[1] // config.data.batch_size, 1)
            * config.training.epochs_per_round
        )
        # resumed runs continue the FL-aware global-step x-axis
        self.global_step = (
            int(self._node_host(self.fed.round)) * self._steps_per_round
        )
        self._plan_cache: dict[tuple, tuple] = {}
        # devprof round gauges (MFU/TFLOPs/HBM), refreshed per round
        # when P2PFL_DEVPROF is on and splatted into status records.
        # False = round FLOPs not probed yet (None = probed, uncounted)
        self.devprof_last: dict[str, Any] = {}
        self._devprof_flops: float | None | bool = False

    # ------------------------------------------------------------------
    def _node_host(self, x) -> np.ndarray:
        """Device array (node-sharded or replicated) -> full host copy
        on every process. Multi-host fetches route through
        ``mesh.fetch_global`` — which also serves processes owning no
        device of the federation submesh; single-process is a plain
        transfer."""
        if jax.process_count() > 1:
            from p2pfl_tpu.parallel.mesh import fetch_global

            return fetch_global(x)
        return np.asarray(x)

    def _choose_sparse(self) -> bool:
        """Pick the collective schedule for weight exchange.

        The ppermute path is legal only for DFL (identity adopt) with
        FedAvg and one node per mesh slot. Bandwidth model: the stacked
        all-gather moves (n-1) x |params| through each ICI link; each
        ppermute moves |params| — so sparse wins when #offsets < n-1
        (ring: 2 vs n-1). At equality the all-gather's single fused
        collective has better latency, so prefer dense.
        """
        cfg = self.config
        legal = (
            cfg.federation == "DFL"
            and self.transport.n_devices == cfg.n_nodes
            and type(self.aggregator) is FedAvg
            # the ppermute path never materializes the full params
            # stack, so there is no pre-exchange hook for update
            # poisoning, DP privatization, or trust_obs reputation
            and not (self.attack is not None and self.attack.poisons_updates)
            and self.reputation is None
            and self.dp_spec is None
        )
        if cfg.transport == "dense":
            return False
        if cfg.transport == "sparse":
            if not legal:
                raise ValueError(
                    "transport='sparse' needs DFL + FedAvg + one node "
                    "per device, and no update-poisoning adversary, "
                    "reputation, or DP privatization "
                    f"(n_nodes={cfg.n_nodes}, "
                    f"n_devices={self.transport.n_devices}, "
                    f"federation={cfg.federation})"
                )
            return True
        return legal and len(edge_offsets(self.topology)) < cfg.n_nodes - 1

    def _maybe_resume(self) -> None:
        if not self.config.checkpoint_dir:
            return
        restored = None
        # newest first, falling back past any corrupt/truncated file
        for path in reversed(all_checkpoints(self.config.checkpoint_dir)):
            try:
                restored = load_checkpoint(path, self.fed)
                break
            except ValueError:
                continue
        if restored is None:
            return
        self.fed = self.transport.put_stacked(restored)
        # replay the host trajectory through the checkpointed rounds —
        # identical fault application, clock advancement AND leadership
        # rotation (advancing self._rng through the same draw sequence)
        # as the uninterrupted run, so eviction timing, the leader, and
        # every subsequent mix weight match exactly
        start_round = int(self._node_host(self.fed.round))
        for r in range(start_round):
            alive = self._advance_membership(r, replay=True)
            self._rotate_leader(alive, replay=True)

    def _sync_join_row(self, node: int, round_num: int) -> None:
        """SPMD twin of the socket STATE_SYNC half of a live join: the
        joining row adopts the current leader row's params (the
        federation's "current global model"), so a mid-run joiner
        re-enters from the cohort's state instead of whatever its row
        drifted to while dead. Joins are rare, so the eager row copy
        (one gather+scatter across the stacked params) is fine."""
        src = self.leader
        if src == node:
            src = next(
                (i for i in self.membership.get_nodes() if i != node), None
            )
            if src is None:
                return
        params = jax.tree.map(
            lambda x: x.at[node].set(x[src]), self.fed.states.params
        )
        self.fed = self.fed.replace(
            states=self.fed.states.replace(params=params)
        )
        self.notify(Events.NODE_JOINED, {"node": node, "round": round_num})

    def _advance_membership(self, round_num: int,
                            replay: bool = False) -> np.ndarray:
        for fault in self._faults_by_round.get(round_num, []):
            self.membership.apply_fault(fault)
            # replayed rounds (checkpoint resume) skip the row copy:
            # the restored state already CONTAINS the post-join params,
            # and re-copying today's leader row would diverge from the
            # uninterrupted trajectory
            if fault.kind == "join" and not replay:
                self._sync_join_row(fault.node, round_num)
        # one round advances the virtual clock by one heartbeat period —
        # eviction after node_timeout_s therefore takes
        # ceil(timeout/period) rounds of silence, like the reference's
        # 20 s timeout at 4 s beats
        t = self.membership.clock + self.membership.protocol.heartbeat_period_s
        return self.membership.advance_to(t)

    def _rotate_leader(self, alive: np.ndarray, replay: bool = False) -> None:
        if self.config.federation == "SDFL":
            candidates = [
                i for i in np.flatnonzero(alive)
                if self.roles[i] in ("aggregator", "trainer")
            ]
            if candidates:
                new = int(self._rng.choice(candidates))
                if new != self.leader and not replay:
                    self.notify(Events.LEADERSHIP_TRANSFERRED,
                                {"from": self.leader, "to": new})
                self.leader = new
        elif not alive[self.leader] and self.config.federation == "CFL":
            # dead server: fail over to the lowest-index alive node
            alive_idx = np.flatnonzero(alive)
            if len(alive_idx):
                self.leader = int(alive_idx[0])

    def _voted_trains(self, alive: np.ndarray,
                      round_num: int = 0) -> np.ndarray | None:
        """Train-set vote, collapsed to its deterministic fixed point.

        The socket path floods per-node ballots (each node vouches for
        the trainable part of its live neighborhood) and elects the
        ``train_set_size`` best-vouched candidates. On the host every
        voter sees the same alive set, so the tally is computable
        directly: score[j] = #alive nodes adjacent to j (plus j
        itself), with a round-ROTATING index tie-break so a binding cap
        still covers every node's data over rounds. Returns None when
        the cap doesn't bind (the plan's static ``trains`` stands).
        """
        k = self.config.protocol.train_set_size
        n = self.config.n_nodes
        eligible = [
            i for i in np.flatnonzero(alive)
            if self.roles[i] in ("trainer", "aggregator", "server")
        ]
        if k <= 0 or k >= len(eligible):
            return None
        adj = self.topology.adjacency
        score = {
            j: 1 + int(np.sum(adj[np.flatnonzero(alive), j]))
            for j in eligible
        }
        winners = sorted(
            score, key=lambda j: (-score[j], (j - round_num) % n)
        )[:k]
        win = set(winners)
        if self.config.federation in ("CFL", "SDFL") and alive[self.leader]:
            if self.leader not in win:
                win.discard(winners[-1])
                win.add(self.leader)
        trains = np.zeros(self.config.n_nodes, bool)
        trains[sorted(win)] = True
        return trains

    def _plan_args(self, trains_override: np.ndarray | None = None):
        """Device arrays for the current round plan. Liveness is folded
        in on-device from ``fed.alive``, so the plan depends only on the
        leader and the voted train set — cached to avoid per-round
        host→device transfers."""
        if self.reputation is not None:
            # reputation-weighted FedAvg without touching the round fn:
            # w = mix * n_samples * contrib, so scaling mix COLUMN j by
            # node j's trust is exactly a per-contributor reweighting —
            # and a zeroed column is a masked row for the robust
            # aggregators. Trust changes every round, so this path
            # skips the plan cache (one [n,n] host->device put/round).
            plan = make_round_plan(
                self.topology, self.roles, self.config.federation,
                self.leader,
            )
            trains = (
                plan.trains if trains_override is None else trains_override
            )
            mix = (
                plan.mix.astype(np.float32)
                * self.reputation.weights_vector()[None, :]
            )
            if self._stale_scale is not None:
                mix = mix * self._stale_scale[None, :]
            tr = self.transport
            return (
                tr.put_stacked(mix),
                tr.put_stacked(plan.adopt),
                tr.put_stacked(trains),
            )
        key = (
            self.leader,
            None if trains_override is None else trains_override.tobytes(),
        )
        if key not in self._plan_cache:
            # bounded LRU: a binding rotating vote cap mints a fresh
            # trains vector per round per leader, which would grow the
            # cache without limit over a long scenario
            while len(self._plan_cache) >= 64:
                self._plan_cache.pop(next(iter(self._plan_cache)))
            plan = make_round_plan(
                self.topology, self.roles, self.config.federation, self.leader
            )
            trains = plan.trains if trains_override is None else trains_override
            mix = plan.mix
            if self._stale_scale is not None:
                mix = mix.astype(np.float32) * self._stale_scale[None, :]
            tr = self.transport
            self._plan_cache[key] = (
                tr.put_stacked(mix),
                tr.put_stacked(plan.adopt),
                tr.put_stacked(trains),
            )
        else:
            self._plan_cache[key] = self._plan_cache.pop(key)  # LRU touch
        return self._plan_cache[key]

    def _publish_statuses(self, r: int, alive: np.ndarray,
                          train_loss: np.ndarray, ev: dict | None) -> None:
        """Per-node live status for ``python -m p2pfl_tpu.monitor``
        (the node→controller heartbeat POST analog, node.py:916-937)."""
        if self.logger.dir is None:
            return
        status_dir = self.logger.dir / "status"
        n_alive = int(alive.sum())
        times = sorted(getattr(self, "round_times_s", []))
        p95 = (
            round(times[min(len(times) - 1, int(0.95 * len(times)))], 4)
            if times else None
        )
        for i in range(self.config.n_nodes):
            if not alive[i]:
                continue  # dead nodes go silent, like a crashed process
            publish_status(
                status_dir, i,
                {
                    "role": self.roles[i],
                    "round": r + 1,
                    "round_p95_s": p95,
                    "loss": float(train_loss[i]),
                    "accuracy": (
                        float(ev["per_node_accuracy"][i]) if ev else None
                    ),
                    "peers": n_alive - 1,
                    "leader": self.leader,
                    "trust": (
                        round(float(self.reputation.trust[i]), 4)
                        if self.reputation is not None else None
                    ),
                    "dp_epsilon": (
                        round(self.accountant.epsilon, 4)
                        if self.accountant is not None else None
                    ),
                    "dp_epsilon_budget": (
                        self.config.privacy.epsilon_budget
                        if self.accountant is not None else None
                    ),
                    "recompiles": obs_trace.xla_recompiles(),
                    # one SPMD program serves every node, so the
                    # devprof gauges (utilization/memory) are shared
                    **self.devprof_last,
                },
            )

    @obs_trace.program_scope()
    def evaluate(self) -> dict[str, Any]:
        tracer = obs_trace.get_tracer()
        with tracer.span("scenario.evaluate"):
            with tracer.span("scenario.evaluate.device"):
                # the fetch below blocks anyway: waiting here only puts
                # the device pass and the host fetch under separate spans
                metrics = jax.block_until_ready(
                    self._eval_fn(self.fed, self._x_test, self._y_test,
                                  *self._frozen))
            with tracer.span("scenario.evaluate.fetch"):
                acc = self._node_host(metrics["accuracy"]).astype(np.float64)
                loss = self._node_host(metrics["loss"]).astype(np.float64)
                alive = self._node_host(self.fed.alive)
                mean_acc = float(acc[alive].mean()) if alive.any() else 0.0
                return {
                    "per_node_accuracy": [float(a) for a in acc],
                    "per_node_loss": [float(l) for l in loss],
                    "mean_accuracy": mean_acc,
                    "min_accuracy": (
                        float(acc[alive].min()) if alive.any() else 0.0),
                }

    @obs_trace.program_scope()
    def run(self, rounds: int | None = None,
            target_accuracy: float | None = None) -> ScenarioResult:
        rounds = (rounds if rounds is not None
                  else self.config.training.rounds)
        # obs: span tracer (P2PFL_TRACE, or a live profiler session);
        # program_scope installed the recompile counter, so a mid-run
        # recompile storm (perf.md §7b) shows up as
        # xla/backend_compiles > 0 over the steady-state rounds.
        tracer = obs_trace.configure_from_env(
            default_dir=(self.logger.dir / "trace")
            if self.logger.dir else None,
        )
        # the root span's arguments: ``start_round`` comes from the
        # device, under SPAN_RUN_ENTER, so the ring's record has it and
        # the profiler's annotation, made here, has not
        run_args = {"rounds": rounds}
        with tracer.watch(), tracer.span(SPAN_RUN, args=run_args):
            return self._run(rounds, target_accuracy, tracer, run_args)

    def _run(self, rounds: int, target_accuracy: float | None, tracer,
             run_args: dict) -> ScenarioResult:
        cfg = self.config
        with tracer.span(SPAN_RUN_ENTER):
            if self.logger.dir is not None:
                flight.configure(dump_dir=self.logger.dir / "flight")
            round_times: list[float] = []
            self.round_times_s = round_times  # _publish_statuses reads p95
            rounds_to_target = None
            ev = None
            ev_round = -1  # round the last evaluation reflects
            start_round = int(self._node_host(self.fed.round))
            run_args["start_round"] = start_round
            # profile ONE steady-state round (the second of the run when
            # there is one — the first carries compile time), host work
            # and all, so the trace holds its scenario.* spans beside the
            # device ops; SURVEY §5.1's jax.profiler hook. try/finally:
            # an exception mid-profiled-round must not leave the profiler
            # running.
            profile_round = None
            if cfg.profile_dir and self._proc0:
                profile_round = start_round + (1 if rounds > 1 else 0)
            tracing = False
        try:
            for r in range(start_round, start_round + rounds):
                t0 = time.monotonic()
                if r == profile_round:
                    jax.profiler.start_trace(cfg.profile_dir)
                    tracing = True
                # the parent of everything the host does for round r:
                # what its children leave (observers, devprof gauges,
                # checkpoint) is its self time
                with tracer.span("scenario.round", args={"round": r}):
                    self.notify(Events.ROUND_STARTED, {"round": r})
                    with tracer.span("scenario.plan"):
                        alive = self._advance_membership(r)
                        self._rotate_leader(alive)
                        self.fed = self.fed.replace(
                            alive=self.transport.put_stacked(alive)
                        )
                        trains_vote = self._voted_trains(alive, r)
                        plan_args = self._plan_args(trains_vote)
                    with tracer.span("scenario.dispatch"):
                        self.fed, metrics = self._round_fn(
                            self.fed, *self._data_args, *plan_args,
                            *self._frozen,
                        )
                    with tracer.span("scenario.wait"):
                        jax.block_until_ready(self.fed.states.params)
                    self.notify(Events.AGGREGATION_FINISHED, {"round": r})
                    dt = time.monotonic() - t0
                    round_times.append(dt)
                    if devprof.enabled():
                        # the FLOP probe lowers the round program once
                        # per run (shapes are fixed), AFTER dt is read so
                        # its compile never bills itself to a round time
                        if self._devprof_flops is False:
                            self._devprof_flops = round_flops(
                                self._round_fn, self.fed, *self._data_args,
                                *plan_args, *self._frozen)
                        self.devprof_last = devprof.round_gauges(
                            self._devprof_flops, dt,
                            self.transport.n_devices)
                    self.global_step += self._steps_per_round

                    with tracer.span("scenario.fetch"):
                        train_loss = self._node_host(
                            metrics["train_loss"]).astype(np.float64)
                        if "counted" in metrics:
                            # the model's own counters of this round's
                            # steps (the same on every node where the
                            # layer saw all nodes' rows together)
                            obs_trace.note_counted({
                                k: self._node_host(v)
                                for k, v in metrics["counted"].items()})
                        if (self.reputation is not None
                                and "trust_obs" in metrics):
                            # round r ran on trust from round r-1 (one-
                            # round lag); fold in this round's scores for
                            # the next. Silent nodes (not training or
                            # dead) keep their trust — absence is not
                            # evidence.
                            contrib = np.logical_and(
                                self._base_trains if trains_vote is None
                                else trains_vote,
                                alive,
                            )
                            self.reputation.observe(
                                self._node_host(metrics["trust_obs"]).astype(
                                    np.float64),
                                contrib,
                            )
                    if self.accountant is not None:
                        # ε is a pure function of rounds completed, so a
                        # resumed run re-reads the same spend (r counts
                        # from the checkpoint's round, not zero)
                        self.accountant.steps = r + 1
                    with tracer.span("scenario.log"), \
                            tracer.span(SPAN_LOG_METRICS):
                        for i in range(cfg.n_nodes):
                            rec = {"Train/loss": float(train_loss[i]),
                                   "Train/round_time_s": dt}
                            if self.reputation is not None:
                                rec["Trust/score"] = float(
                                    self.reputation.trust[i])
                            self.logger.log_metrics(
                                rec, step=self.global_step, round=r, node=i,
                            )
                    with tracer.span("scenario.status"):
                        self._publish_statuses(r, alive, train_loss, ev)
                    if (cfg.training.eval_every
                            and (r + 1) % cfg.training.eval_every == 0):
                        ev = self.evaluate()
                        ev_round = r
                        for i, (a, l) in enumerate(
                            zip(ev["per_node_accuracy"], ev["per_node_loss"])
                        ):
                            self.logger.log_metrics(
                                {"Test/accuracy": a, "Test/loss": l},
                                step=self.global_step, round=r, node=i,
                            )
                        self.logger.log_metrics(
                            {"Test/mean_accuracy": ev["mean_accuracy"],
                             "Test/min_accuracy": ev["min_accuracy"]},
                            step=self.global_step, round=r,
                        )
                        if (target_accuracy is not None
                                and rounds_to_target is None
                                and ev["mean_accuracy"] >= target_accuracy):
                            rounds_to_target = r + 1
                    with tracer.span("scenario.log"):
                        with tracer.span(SPAN_LOG_RESOURCES):
                            resources = resource_snapshot()
                        with tracer.span(SPAN_LOG_WRITE):
                            self.logger.log_metrics(resources,
                                                    step=self.global_step,
                                                    round=r)
                            self.logger.round_marker(r, self.global_step)
                    if (cfg.checkpoint_every
                            and (r + 1) % cfg.checkpoint_every == 0):
                        if cfg.checkpoint_dir:
                            path = save_checkpoint(cfg.checkpoint_dir,
                                                   self.fed)
                            self.notify(Events.CHECKPOINT_SAVED,
                                        {"path": str(path)})
                    self.notify(Events.ROUND_FINISHED,
                                {"round": r, "time_s": dt})
                if tracing:
                    jax.profiler.stop_trace()
                    tracing = False
        finally:
            with tracer.span(SPAN_RUN_EXIT):
                if tracing:  # exception mid-profiled-round
                    jax.profiler.stop_trace()
                if tracer.enabled and self._proc0:
                    tracer.export(process_name=f"scenario[{cfg.name}]")

        last_round = start_round + rounds - 1
        if ev is None or ev_round != last_round:  # don't report stale eval
            ev = self.evaluate()
            if (target_accuracy is not None and rounds_to_target is None
                    and ev["mean_accuracy"] >= target_accuracy):
                rounds_to_target = last_round + 1
        with tracer.span(SPAN_RUN_EXIT):
            self.notify(Events.LEARNING_FINISHED, {})
            return ScenarioResult(
                final_accuracy=ev["mean_accuracy"],
                per_node_accuracy=ev["per_node_accuracy"],
                rounds_run=rounds,
                round_times_s=round_times,
                history=self.logger.history,
                rounds_to_target=rounds_to_target,
                min_accuracy=ev["min_accuracy"],
            )

    def close(self) -> None:
        self.logger.close()


class CrossDeviceScenario(Observable):
    """Sampled K-of-N cross-device driver (round 13).

    A client here is NOT a live row of the federation: it is an index
    into a lazy :class:`ClientPartition` (CrossDeviceData). Per round
    the host (1) applies scheduled faults and advances the SAME
    ``membership.py`` virtual clock Scenario uses — but over ALL
    ``n_clients`` virtual clients, so churn composes with sampling,
    (2) draws K clients (seeded by ``(cross_device.seed, round)``,
    replacement-free, optionally data-size-weighted), (3) reshapes them
    into ``cohort_size`` cohorts of ``n_slots`` and materializes their
    shards at the fixed shard size, (4) invokes the compiled
    cohort-scan round (``build_round_fn_cross_device``): one program,
    fixed shapes, zero steady-state recompiles regardless of which
    clients were drawn. A sampled-but-dead client simply rides through
    with zero training gate and zero aggregation weight.

    The mesh is ``n_slots = clients_per_round / cohort_size`` wide —
    an 8-slot dev mesh at cohort_size=32 simulates 256 participants
    per round out of a 10k–1M population.
    """

    @obs_trace.program_scope()
    def __init__(self, config: ScenarioConfig,
                 dataset: CrossDeviceData | None = None):
        super().__init__()
        cd = config.cross_device
        if not cd.active:
            raise ValueError(
                "CrossDeviceScenario needs config.cross_device.n_clients"
                " > 0"
            )
        self.config = config
        self.cd = cd
        self.data = dataset or CrossDeviceData.make(config.data,
                                                    cd.n_clients)
        self.model = build_model(config.model)
        self.fns = make_step_fns(
            self.model,
            objective=config.model.objective,
            optimizer=config.training.optimizer,
            learning_rate=config.training.learning_rate,
            momentum=config.training.momentum,
            weight_decay=config.training.weight_decay,
            momentum_dtype=config.training.momentum_dtype,
            batch_size=config.data.batch_size,
        )
        # the virtual clock spans every VIRTUAL client — the same
        # heartbeat/eviction law as the per-node plane, just wider
        self.membership = Membership(cd.n_clients, config.protocol)
        self._faults_by_round: dict[int, list] = {}
        for f in config.faults:
            self._faults_by_round.setdefault(f.round, []).append(f)
        self._sample_weights = (
            self.data.client_sizes.astype(np.float64)
            if cd.sampling == "weighted" else None
        )
        self._proc0 = jax.process_index() == 0
        self.logger = MetricsLogger(
            config.log_dir if self._proc0 else None, config.name,
            tensorboard=config.tensorboard,
            wandb=config.wandb and self._proc0,
        )
        # round-20 device scaling: with cohort_shards > 1 and enough
        # devices, the round runs the shard_map arm over a cohort mesh;
        # with too few devices it silently falls back to the chunked
        # single-device arm. Chunk structure is part of the round's
        # semantics, placement is not: within one device topology the
        # arms are bit-identical (pinned by tests/test_cross_device.py),
        # but a DIFFERENT topology (e.g. the fallback firing on a
        # 1-device host) may fuse the training body differently and
        # drift ~1 ulp — same reassociation caveat as perf.md §19.1.
        # The slot transport is rebuilt over the SAME device set as the
        # cohort mesh — one jit must not see two device orders.
        self._cohort_mesh = None
        if cd.cohort_shards > 1 and cd.cohort_shards <= jax.device_count():
            self._cohort_mesh = cohort_shard_mesh(cd.cohort_shards)
            self.transport = MeshTransport(cd.n_slots,
                                           n_devices=cd.cohort_shards)
        else:
            self.transport = MeshTransport(cd.n_slots)
        self._exchange_dtype = (
            jnp.bfloat16 if config.wire_dtype in ("bf16", "int8") else None
        )
        self._stream = cd.prefetch == "stream"
        if self._stream:
            # streamed arm (round 20): the round is driven step-by-step
            # so cohort t+1's host gather + device_put overlaps cohort
            # t's compute — see _run_streamed_round
            init_carry, step_fn, finalize = build_cross_device_stream_fns(
                self.fns,
                epochs=config.training.epochs_per_round,
                exchange_dtype=self._exchange_dtype,
                fused_accumulate=cd.accumulate == "fused",
            )
            self._stream_init_carry = init_carry
            self._stream_step = jax.jit(step_fn, donate_argnums=(1,))
            self._stream_finalize = jax.jit(finalize)
            self._wn_fn = jax.jit(cross_device_wn)
            self._stream_bufs = None  # two cohort_buffers: the double buffer
            self._round_fn = None
        else:
            round_fn = build_round_fn_cross_device(
                self.fns,
                epochs=config.training.epochs_per_round,
                exchange_dtype=self._exchange_dtype,
                fused_accumulate=cd.accumulate == "fused",
                cohort_shards=cd.cohort_shards,
                cohort_mesh=self._cohort_mesh,
            )
            self._round_fn = self.transport.compile_round(round_fn)
        self._eval_fn = self.transport.compile_eval(build_eval_fn(self.fns))
        sample_x = jnp.zeros((1,) + self.data.input_shape, jnp.float32)
        fed0 = init_federation(self.fns, sample_x, cd.n_slots,
                               seed=config.seed)
        # the mesh arm replicates the federation state (every device
        # scans ALL slots for its chunk); otherwise the slot axis
        # shards as before
        self.fed = (self.transport.put_replicated(fed0)
                    if self._cohort_mesh is not None
                    else self.transport.put_stacked(fed0))
        # live gauges for the monitor/launch status plumbing (round 20):
        # refreshed per round, splatted into status records
        self.crossdev_last: dict[str, Any] = {}
        self.devprof_last: dict[str, Any] = {}
        self._devprof_flops: float | None | bool = False
        self._x_test = self.transport.put_replicated(self.data.x_test)
        self._y_test = self.transport.put_replicated(self.data.y_test)
        # test introspection: the last round's draw and its liveness
        self.last_sampled: np.ndarray | None = None
        self.last_cohorts: np.ndarray | None = None
        self.last_cohort_alive: np.ndarray | None = None

    def _advance_membership(self, round_num: int) -> np.ndarray:
        for fault in self._faults_by_round.get(round_num, []):
            # join == recover here: clients are stateless between
            # rounds, so there is no row to state-sync
            self.membership.apply_fault(fault)
        t = (self.membership.clock
             + self.membership.protocol.heartbeat_period_s)
        return self.membership.advance_to(t)

    def _run_streamed_round(self, cohorts: np.ndarray,
                            c_alive: np.ndarray) -> dict[str, Any]:
        """One round through the double-buffered prefetch seam (round
        18): while the device runs cohort step t, the host gathers
        cohort t+1's shards into the OTHER of two reused host buffers
        and ``device_put``s them — at most two cohorts of client data
        resident (host or device) at any instant, for any N. The steps
        run the same ``_cross_device_body`` as the monolithic scan in
        the same order with the same globally-normalized weights, so a
        streamed round is bit-identical to ``prefetch="off"``.

        Gauges recorded into ``crossdev_last``:
        ``crossdev_prefetch_mb`` — host→device bytes shipped this
        round; ``crossdev_prefetch_stall_s`` — wall time blocked on
        gather + transfer completion (an upper bound on the stall the
        prefetch failed to hide; the gather itself runs while the
        device computes)."""
        cd = self.cd
        data = self.data
        c = cd.cohort_size
        if self._stream_bufs is None:
            self._stream_bufs = (data.cohort_buffers(cd.n_slots),
                                 data.cohort_buffers(cd.n_slots))
        # FedAvg weights need sizes only — host metadata, no client data
        sizes = data.cohort_sizes(cohorts)
        wn, got_any = self._wn_fn(jnp.asarray(sizes),
                                  jnp.asarray(c_alive))
        alive_dev = self.transport.put_replicated(c_alive)
        prefetch_bytes = 0
        stall_s = 0.0
        sh = self.transport.replicated

        def gather_put(t):
            nonlocal prefetch_bytes, stall_s
            t0 = time.monotonic()
            x, y, m, _ = data.cohort_batch(cohorts[t],
                                           out=self._stream_bufs[t % 2])
            # the sanctioned per-round-loop device_put: THE prefetch
            # seam (everywhere else fedlint's recompile-hazard rule
            # flags puts inside round loops)
            dev = tuple(
                jax.device_put(a, sh)  # fedlint: disable=recompile-hazard
                for a in (x, y, m)
            )
            # wait for the DMA (not the compute) before the host buffer
            # may be rewritten two steps from now
            jax.block_until_ready(dev)
            stall_s += time.monotonic() - t0
            return dev

        buf = gather_put(0)
        prefetch_bytes = sum(a.nbytes for a in buf) * c
        params0 = self.fed.states.params
        carry = jax.tree.map(jnp.copy, self._stream_init_carry(self.fed))
        losses = []
        for t in range(c):
            x_t, y_t, m_t = buf
            # async dispatch: the host returns before the step finishes,
            # so the next gather below overlaps this step's compute
            carry, loss_t = self._stream_step(
                params0, carry, x_t, y_t, m_t, alive_dev[t], wn[t])
            losses.append(loss_t)
            if t + 1 < c:
                if t >= 1:
                    # the buffer about to be refilled was read by step
                    # t-1, and the CPU backend's device_put ALIASES a
                    # 64-byte-aligned numpy buffer instead of copying
                    # it: that step must have finished, not merely its
                    # transfer, or it trains on half-rewritten data
                    # (seen as a ~1-in-3 parity failure on cold caches)
                    jax.block_until_ready(losses[t - 1])
                buf = gather_put(t + 1)
        self.fed = self._stream_finalize(self.fed, carry, got_any)
        self.crossdev_last["crossdev_prefetch_mb"] = round(
            prefetch_bytes / 1e6, 2)
        self.crossdev_last["crossdev_prefetch_stall_s"] = round(
            stall_s, 4)
        return {
            "train_loss": np.stack([np.asarray(l) for l in losses]),
            "alive": self.fed.alive,
        }

    def _publish_crossdev_status(self, r: int, mean_loss: float) -> None:
        """One status record for the whole cross-device driver (there
        are no per-node processes to speak for themselves) — the
        monitor/webapp throughput pane reads the crossdev_* gauges."""
        if self.logger.dir is None:
            return
        publish_status(
            self.logger.dir / "status", 0,
            {
                "role": "crossdev",
                "round": r + 1,
                "loss": mean_loss,
                "peers": self.cd.n_slots - 1,
                "recompiles": obs_trace.xla_recompiles(),
                **self.crossdev_last,
                **self.devprof_last,
            },
        )

    @obs_trace.program_scope()
    def evaluate(self) -> dict[str, Any]:
        """Central-test-set quality of the global model. Every slot
        holds the same aggregate post-round, so slot metrics agree; the
        mean is reported for symmetry with Scenario.evaluate."""
        with obs_trace.get_tracer().span("scenario.evaluate"):
            metrics = self._eval_fn(self.fed, self._x_test, self._y_test)
            acc = np.asarray(metrics["accuracy"]).astype(np.float64)
            loss = np.asarray(metrics["loss"]).astype(np.float64)
            return {
                "per_node_accuracy": [float(a) for a in acc],
                "per_node_loss": [float(l) for l in loss],
                "mean_accuracy": float(acc.mean()),
                "min_accuracy": float(acc.min()),
            }

    @obs_trace.program_scope()
    def run(self, rounds: int | None = None,
            target_accuracy: float | None = None) -> ScenarioResult:
        rounds = (rounds if rounds is not None
                  else self.config.training.rounds)
        tracer = obs_trace.get_tracer()
        # Scenario.run's root span, stall watch and SPAN_RUN_* spans
        run_args = {"rounds": rounds}
        with tracer.watch(), tracer.span(SPAN_RUN, args=run_args):
            return self._run(rounds, target_accuracy, tracer, run_args)

    def _run(self, rounds: int, target_accuracy: float | None, tracer,
             run_args: dict) -> ScenarioResult:
        cfg = self.config
        cd = self.cd
        with tracer.span(SPAN_RUN_ENTER):
            round_times: list[float] = []
            rounds_to_target = None
            ev = None
            ev_round = -1
            start_round = int(np.asarray(self.fed.round))
            run_args["start_round"] = start_round
            tr = self.transport
        for r in range(start_round, start_round + rounds):
            t0 = time.monotonic()
            # Scenario.run's spans; the streamed arm dispatches (and
            # stalls on its prefetch, a gauge) cohort by cohort
            with tracer.span("scenario.round", args={"round": r}):
                self.notify(Events.ROUND_STARTED, {"round": r})
                with tracer.span("scenario.plan"):
                    alive = self._advance_membership(r)
                    # row-major cohorts: cohort step t runs clients
                    # sampled[t*n_slots:(t+1)*n_slots] (sample_cohorts
                    # pins the assignment shared by every arm)
                    sampled, cohorts = sample_cohorts(
                        cd.n_clients, cd.clients_per_round, cd.cohort_size,
                        r, seed=cd.seed, weights=self._sample_weights,
                    )
                    c_alive = alive[cohorts]
                    if not self._stream:
                        x, y, mask, sizes = self.data.cohort_batch(sampled)
                        shape2 = (cd.cohort_size, cd.n_slots)
                        # leading axis is the SCAN axis (cohort_size),
                        # not the slot axis — replicate; the per-slot
                        # split happens inside the compiled round
                        args = tuple(
                            tr.put_replicated(
                                a.reshape(shape2 + a.shape[1:]))
                            for a in (x, y, mask, sizes)
                        ) + (tr.put_replicated(c_alive),)
                with tracer.span("scenario.dispatch"):
                    if self._stream:
                        metrics = self._run_streamed_round(cohorts, c_alive)
                    else:
                        self.fed, metrics = self._round_fn(self.fed, *args)
                with tracer.span("scenario.wait"):
                    jax.block_until_ready(self.fed.states.params)
                dt = time.monotonic() - t0
                round_times.append(dt)
                if devprof.enabled():
                    # streamed rounds have no single round program to
                    # cost (per-step dispatch) — their gauges carry wall
                    # + memory watermarks only; the monolithic scan
                    # costs once
                    if self._devprof_flops is False:
                        self._devprof_flops = (
                            round_flops(self._round_fn, self.fed, *args)
                            if not self._stream else None
                        )
                    self.devprof_last = devprof.round_gauges(
                        self._devprof_flops, dt, tr.n_devices)
                self.last_sampled = sampled
                self.last_cohorts = cohorts
                self.last_cohort_alive = c_alive
                self.notify(Events.AGGREGATION_FINISHED, {"round": r})

                with tracer.span("scenario.fetch"):
                    losses = np.asarray(
                        metrics["train_loss"]).astype(np.float64)
                live = c_alive.astype(bool)
                mean_loss = float(losses[live].mean()) if live.any() else 0.0
                # live throughput gauges (round 20): the monitor's cl/s
                # and prefetch columns; prefetch keys exist only on
                # streamed rounds (renderers show "-" when absent)
                self.crossdev_last["crossdev_clients_per_s"] = round(
                    len(sampled) / dt, 2) if dt > 0 else None
                with tracer.span("scenario.log"):
                    self._publish_crossdev_status(r, mean_loss)
                    with tracer.span(SPAN_LOG_METRICS):
                        self.logger.log_metrics(
                            {"Train/loss": mean_loss,
                             "Train/round_time_s": dt,
                             "CrossDev/clients_sampled": int(len(sampled)),
                             "CrossDev/clients_alive": int(live.sum())},
                            step=r, round=r,
                        )
                if (cfg.training.eval_every
                        and (r + 1) % cfg.training.eval_every == 0):
                    ev = self.evaluate()
                    ev_round = r
                    self.logger.log_metrics(
                        {"Test/mean_accuracy": ev["mean_accuracy"]},
                        step=r, round=r,
                    )
                    if (target_accuracy is not None
                            and rounds_to_target is None
                            and ev["mean_accuracy"] >= target_accuracy):
                        rounds_to_target = r + 1
                self.notify(Events.ROUND_FINISHED,
                            {"round": r, "time_s": dt})

        last_round = start_round + rounds - 1
        if ev is None or ev_round != last_round:
            ev = self.evaluate()
            if (target_accuracy is not None and rounds_to_target is None
                    and ev["mean_accuracy"] >= target_accuracy):
                rounds_to_target = last_round + 1
        with tracer.span(SPAN_RUN_EXIT):
            self.notify(Events.LEARNING_FINISHED, {})
            return ScenarioResult(
                final_accuracy=ev["mean_accuracy"],
                per_node_accuracy=ev["per_node_accuracy"],
                rounds_run=rounds,
                round_times_s=round_times,
                history=self.logger.history,
                rounds_to_target=rounds_to_target,
                min_accuracy=ev["min_accuracy"],
            )

    def close(self) -> None:
        self.logger.close()
