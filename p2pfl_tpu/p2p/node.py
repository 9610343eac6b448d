"""P2PNode: an asyncio federated node over TCP.

Role/behavior parity with the reference's Node (fedstellar/node.py) and
BaseNode (base_node.py), with the thread-per-connection design replaced
by one event loop per node:

- listener + per-peer streams + CONNECT handshake
  (base_node.py:197-278);
- heartbeats feeding wall-clock membership (heartbeater.py);
- gossip flooding of control messages with at-most-once dedup
  (gossiper.py, communication_protocol.py:146-160);
- the round state machine with role branches (node.py:427-524):
  AGGREGATOR/SERVER train + aggregate + gossip partial aggregates;
  TRAINER trains, ships its model, adopts the aggregate; IDLE only
  adopts; per-peer progress tracking (MODELS_AGGREGATED /
  MODELS_READY / MODEL_INITIALIZED) gates who still needs gossip
  (node.py:695-724);
- initial model diffusion from the starter node (node.py:299);
- SDFL leadership transfer (node.py:676-686).

Local training runs through any NodeLearner (JaxLearner — jitted on
the host's TPU); only weight payloads cross the network, in the safe
envelope from p2pfl_tpu.core.serialize.
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import logging
import math
import random
import secrets
import time
from typing import Any

import jax
import numpy as np

from p2pfl_tpu.config.schema import ElasticConfig, FaultEvent, ProtocolConfig
from p2pfl_tpu.core.aggregators import Aggregator
from p2pfl_tpu.core.serialize import (
    WIRE_DTYPES,
    decode_parameters,
    dequantize_int8,
    encode_parameters,
    quantize_int8,
)
from p2pfl_tpu.federation.events import Events
from p2pfl_tpu.federation.membership import Membership
from p2pfl_tpu.obs import flight
from p2pfl_tpu.obs.trace import NULL_SPAN, get_tracer
from p2pfl_tpu.p2p.protocol import (
    GOSSIPED,
    PERIODIC_FLOODS,
    DedupRing,
    Message,
    MsgType,
    read_message,
    write_message,
)

log = logging.getLogger("p2pfl_tpu.p2p")

#: transport-buffer ceiling for the idle-lane fast write (matches
#: asyncio's default 64 KiB high-water mark): under it a send goes
#: straight to the transport; over it the frame takes the bounded
#: queue and the drain task's drain() await applies real backpressure
_FAST_LANE_MAX = 1 << 16


@dataclasses.dataclass
class PeerState:
    """One live connection (node_connection.py's socket half).

    ``send_q`` + ``send_task`` form the connection's egress lane: every
    outbound frame is enqueued and a single per-peer drain task owns
    the writer. The queue is bounded (ProtocolConfig.send_queue_depth),
    so a peer that stops reading exerts backpressure on ITS lane only —
    broadcast enqueues to all lanes concurrently and never serializes
    on the slowest peer's TCP buffer. The single-writer discipline also
    guarantees frames never interleave and per-peer FIFO order holds
    (round-state messages rely on stream order, see _train_round)."""

    idx: int
    writer: asyncio.StreamWriter
    reader_task: asyncio.Task | None = None
    send_q: asyncio.Queue | None = None
    send_task: asyncio.Task | None = None
    # True only while the drain task is mid-write: the idle-lane fast
    # path (node._write) must not interleave with it
    draining: bool = False


@dataclasses.dataclass
class NodeProgress:
    """A node's round-progress as this node knows it
    (node_connection.py:275-335's tracking, decoupled from the
    connection: progress messages FLOOD, so state is known for every
    federation member, not just direct peers — that is what lets a
    gossiper reason about nodes it can only reach through a PROXY)."""

    models_aggregated: set[int] = dataclasses.field(default_factory=set)
    agg_round: int = -1  # round the models_aggregated set belongs to
    initialized: bool = False
    ready_round: int = -1


class P2PNode:
    """One federated node. Wire up a learner, start, connect, learn."""

    def __init__(
        self,
        idx: int,
        learner,
        host: str = "127.0.0.1",
        port: int = 0,
        role: str = "aggregator",
        n_nodes: int = 2,
        aggregator: Aggregator | None = None,
        protocol: ProtocolConfig | None = None,
        start_learning: bool = False,
        gossip_period_s: float | None = None,
        federation: str = "DFL",
        seed: int = 0,
        tls=None,
        netem=None,
        full_mesh: bool = False,
        attack=None,
        reputation=None,
        wire_dtype: str = "f32",
        elastic: ElasticConfig | None = None,
        fit_slowdown: float = 1.0,
        local_epochs: int | None = None,
        joiner: bool = False,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 0,
        resume: bool = False,
        sidecar=None,
        dp=None,
        masker=None,
    ):
        from p2pfl_tpu.p2p.session import AggregationSession, SidecarSession

        self.idx = idx
        self.learner = learner
        self.host = host
        self.port = port
        self.role = role
        self.n_nodes = n_nodes
        self.protocol = protocol or ProtocolConfig()
        self.start_learning_flag = start_learning
        # explicit argument wins; otherwise the ProtocolConfig knob
        # (GOSSIP_MODELS_FREC analog) paces gossip/poll ticks
        self.gossip_period_s = (
            gossip_period_s if gossip_period_s is not None
            else self.protocol.gossip_period_s
        )
        self.federation = federation
        # Declared-full-mesh relay suppression (set by the launcher for
        # topology="fully" ONLY): when every pair of nodes holds a
        # direct link by construction, the origin's broadcast already
        # reached everyone and epidemic re-relay multiplies control
        # traffic by the fanout for zero reach (measured ~1.2M frames
        # over 3 rounds at 24 nodes, exp_socket_profile.py). This must
        # be DECLARED, not inferred from len(peers) == n-1: in a line
        # 0-1-2 the middle node has n-1 peers while the ends cannot
        # reach each other except through its relay.
        self.full_mesh = full_mesh
        # mutual TLS (p2pfl_tpu.p2p.tls.TLSCredentials) — replaces the
        # reference's RSA/AES-ECB handshake (encrypter.py:48-193).
        # With TLS on, every self-originated message is origin-signed
        # and every received message's signature is checked against the
        # scenario CA, so a valid member cannot forge another node's
        # STOP / ballot / leadership transfer (see p2p.tls docstring).
        self.tls = tls
        if tls is not None:
            from p2pfl_tpu.p2p.tls import MessageSigner, MessageVerifier

            self._signer = MessageSigner(tls)
            self._verifier = MessageVerifier(tls.ca_cert)
        else:
            self._signer = None
            self._verifier = None
        self._rng = random.Random(seed * 7919 + idx)
        # deterministic link shaping (NetworkConfig / tcset analog,
        # base_node.py:82-85) — None when unshaped, so the default
        # send path stays a direct socket write
        from p2pfl_tpu.p2p.netem import shaper_from_config

        self.shaper = shaper_from_config(
            idx, netem, on_error=self._drop_conn,
            on_transition=self._on_netem_transition)
        # adversary hooks (p2pfl_tpu.adversary): ``attack`` is an
        # AttackSpec THIS node applies to its own outgoing update
        # (a malicious node attacks; honest nodes pass None);
        # ``reputation`` is a ReputationMonitor shared with the session
        # so finish-time aggregation is trust-weighted
        self.attack = attack
        self.reputation = reputation
        # privacy hooks (p2pfl_tpu.privacy): ``dp`` is a DPSpec — this
        # node clips + noises its own trained update post-fit, keyed by
        # (dp.seed, idx, round) so the SPMD row is bit-identical;
        # ``masker`` is a PairwiseMasker — outgoing updates are
        # pairwise-masked fixed-point trees and the session fuses in
        # the modular domain, unmasking only at quorum close
        self.dp = dp
        self.masker = masker
        # wire precision for PARAMS payloads (config.wire_dtype). The
        # knob names what this node WANTS to ship; what it actually
        # ships to a given target set is negotiated per send: every
        # CONNECT hello carries the supported-dtype list ("wd"), and a
        # reduced-precision payload goes out only when ALL targets of
        # that send advertised the dtype — otherwise the send falls
        # back to the f32 v1 envelope (one Message per target set, so
        # precision is per-send, never per-peer re-encoded). Peers that
        # predate the field advertise nothing and always get f32.
        if wire_dtype not in WIRE_DTYPES:
            raise ValueError(
                f"unknown wire_dtype {wire_dtype!r}; have {WIRE_DTYPES}")
        self.wire_dtype = wire_dtype
        self._peer_wire: dict[int, tuple[str, ...]] = {}
        # int8 error feedback: the quantization error of this node's
        # own shipped update, carried into the next round's send so the
        # rounding bias cancels over time instead of accumulating
        # (residual lives host-side; reset on leaf-structure change)
        self._ef_residual: list[Any] | None = None
        # params payload bytes shipped (encoded blob size × targets):
        # the wire-dtype A/B's numerator, isolated from control traffic
        self.params_bytes_out = 0
        # obs wiring: the process tracer (configured in place, so the
        # cached reference stays valid across enable/disable) + always-
        # counted wire totals. The plain ints cost two adds per frame
        # regardless of tracing; per-peer/per-type counter keys are
        # built only behind tracer.enabled (f-strings per frame are
        # exactly the allocation the disabled path must not pay).
        self._tracer = get_tracer()
        self._lane = f"node{idx}"
        self.bytes_in = 0
        self.bytes_out = 0
        # always-on per-peer wire totals (round 14): two dict-int adds
        # per frame, published with the status record so the health
        # plane can see per-LINK silence — a partition is invisible in
        # the plain totals (gossip inside one side keeps them growing)
        # but shows as cross-cut per-peer counters going one-sided
        self.peer_bytes_in: dict[int, int] = {}
        self.peer_bytes_out: dict[int, int] = {}
        # per-round wall clocks (appended by _learning_loop) — the p95
        # the status publisher reports comes from here
        self.round_wall_s: list[float] = []
        # per-round critical-path accumulators (round 18): plain-float
        # adds like bytes_in — always-on except _cp_wire_s, which needs
        # the sender's tc stamp and therefore only accrues while
        # tracing is on. _learning_loop snapshots them into
        # ``critpath_last`` at every round close (the status publisher
        # flattens that into critpath_* gauges) and zeroes them.
        self._cp_fit_s = 0.0
        self._cp_wait_s = 0.0
        self._cp_wire_s = 0.0
        self._cp_agg_mark = 0.0
        #: last completed round's fit/wire/wait/aggregate/other split
        #: (None until a round finishes)
        self.critpath_last: dict[str, float] | None = None
        # elasticity profile (round 11): async aggregation knobs feed
        # the session, heartbeat probe/backoff knobs feed membership,
        # and the per-node compute class (fit_slowdown / local_epochs)
        # shapes _fit. ``joiner`` marks a node entering a RUNNING
        # federation: its CONNECT hello declares the join ("jr") and
        # the established side answers with STATE_SYNC.
        el = elastic if elastic is not None else ElasticConfig()
        self.elastic = el
        self.fit_slowdown = float(fit_slowdown)
        self.local_epochs = local_epochs
        self.joiner = bool(joiner)
        # crash-consistent auto-resume (round 14): with a checkpoint
        # dir configured the node snapshots (params, round) every
        # ``checkpoint_every`` rounds; ``resume=True`` relaunches it
        # from the newest of (own checkpoint, peer STATE_SYNC)
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = int(checkpoint_every)
        self.resume = bool(resume)
        # the round the on-disk checkpoint carried; STATE_SYNC adoption
        # compares against it (newer wins) and clears it once decided
        self._resume_round: int | None = None
        # peers currently behind a scripted partition cut — outbound
        # frames to them are dropped at the write layer (both sides of
        # the cut hold the same set, so the sever is symmetric)
        self._severed: set[int] = set()
        # dial-back addresses, learned from CONNECT hellos — reconnect
        # probes redial these when a peer's heartbeats go silent
        self._peer_addrs: dict[int, tuple[str, int]] = {}
        # STATE_SYNC round target that arrived while a round body was
        # active — applied at the next round boundary (jumping
        # self.round mid-round would desync the live session)
        self._join_round_target: int | None = None
        # aggregation sidecar (round 16): ``sidecar`` is the host
        # process's shared aggd.SidecarClient — when present, payload
        # bytes bypass this loop entirely (protocol slot_sink → shm
        # arena → sidecar fuse) and the session is the slot-native
        # SidecarSession. ``loop_payload_touch_bytes`` counts every
        # payload byte the ROUND PATH still materializes/decodes on
        # the loop (the zero-copy pin asserts ≈0 under the sidecar;
        # one-time init diffusion is bootstrap, not round path, and
        # executor-side decodes never touch the loop).
        self.sidecar = sidecar
        self.loop_payload_touch_bytes = 0
        if sidecar is not None and masker is not None:
            # config.schema refuses this combination; a direct caller
            # gets the same loud failure instead of a sidecar fuse that
            # silently float-averages masked ring elements
            raise ValueError(
                "secagg masking needs the inline session: the sidecar "
                "fuses raw slot bytes as floats, not the modular sum"
            )
        if sidecar is not None:
            self.session: AggregationSession = SidecarSession(
                aggregator,
                timeout_s=self.protocol.aggregation_timeout_s,
                reputation=reputation, lane=self._lane,
                min_received=el.min_received if el.async_aggregation
                else 1.0,
                staleness_beta=el.staleness_beta
                if el.async_aggregation else 0.0,
                client=sidecar, spawn=self._track_task,
            )
        else:
            self.session = AggregationSession(
                aggregator,
                timeout_s=self.protocol.aggregation_timeout_s,
                reputation=reputation, lane=self._lane,
                min_received=el.min_received if el.async_aggregation
                else 1.0,
                staleness_beta=el.staleness_beta
                if el.async_aggregation else 0.0,
                masker=masker,
            )
        self.membership = Membership(
            n_nodes, self.protocol, virtual=False,
            retry_limit=el.heartbeat_retry_limit,
            backoff_base_s=el.heartbeat_backoff_base_s,
            backoff_max_s=el.heartbeat_backoff_max_s,
        )
        self.peers: dict[int, PeerState] = {}
        self.progress: dict[int, NodeProgress] = {}
        self.peer_roles: dict[int, str] = {}
        # flooded evaluation metrics per node (METRICS messages — the
        # reference defines the type but stubs the handler,
        # node.py:875-878; here they feed monitoring)
        self.peer_metrics: dict[int, dict[str, Any]] = {}
        # capacity scales with federation size: BEATs from every node
        # share this ring, and 100 ids evict before a flood quiesces
        # once ~100 gossip ids are in flight per eviction window
        self.dedup = DedupRing(capacity=max(100, 20 * n_nodes))
        self.round = 0
        self.total_rounds = 0
        # train-set ballots: round -> voter -> candidate tuple
        # (VOTE_TRAIN_SET flow, communication_protocol.py:47 +
        # node.py:881-887 vote intake)
        self._votes: dict[int, dict[int, tuple[int, ...]]] = {}
        self.epochs = 1
        self.initialized = False
        self.learning = False
        self.leader: int | None = None
        # every leadership token position this node observed, in order —
        # tests and monitoring assert on the rotation *history*, not the
        # chance-dependent final position
        self.leader_history: list[int] = []
        # weight messages that arrived for a FUTURE round (a fast peer
        # past the barrier) or outside an active round body — replayed
        # when this node's round body reaches them
        self._pending_params: list[tuple[PeerState, Message]] = []
        # highest beat sequence seen per node (replay fence — see the
        # BEAT handler)
        self._beat_seen: dict[int, int] = {}
        self._round_active = False
        # round-loop wall clock (set by _learning_loop): launch.py's
        # multi-process bench reads these to time ROUNDS, excluding
        # startup/compile/diffusion — comparable to run_simulation's
        # post-warm-up clock
        self.learn_t0: float | None = None
        self.learn_t1: float | None = None
        self._server: asyncio.Server | None = None
        self._tasks: list[asyncio.Task] = []
        self._learn_task: asyncio.Task | None = None
        self._crashed = False
        self.finished = asyncio.Event()

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def _track_task(self, coro, what: str) -> asyncio.Task:
        """Spawn ``coro`` as a tracked, exception-consuming task.

        A bare ``asyncio.create_task`` keeps no reference — the task
        can be garbage-collected mid-flight and a failure surfaces only
        as "exception was never retrieved" at interpreter exit (the
        round-11 prober class). Tracking in ``self._tasks`` pins the
        task and lets ``stop()`` cancel it; the done-callback prunes
        the list on completion (so reconnect churn doesn't accumulate
        dead tasks) and logs any exception instead of swallowing it.
        """
        task = asyncio.create_task(coro)
        self._tasks.append(task)

        def _done(t: asyncio.Task) -> None:
            if t in self._tasks:
                self._tasks.remove(t)
            if t.cancelled():
                return
            exc = t.exception()
            if exc is not None:
                log.error("node %d background task %r failed: %r",
                          self.idx, what, exc)
                flight.record("node.task_failed", node=self.idx,
                              what=what, error=repr(exc)[:200])

        task.add_done_callback(_done)
        return task

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._on_connection, self.host, self.port,
            ssl=self.tls.server_context() if self.tls else None,
        )
        self.port = self._server.sockets[0].getsockname()[1]
        self.membership.beat(self.idx, 0.0)
        if self.shaper is not None:
            # partition-plan time 0 = node start, not first send
            self.shaper.start_clock()
        if self.resume and self.checkpoint_dir:
            self._try_resume()
        self._track_task(self._heartbeat_loop(), "heartbeat_loop")

    def _try_resume(self) -> None:
        """Crash-consistent restart (round 14): adopt this node's own
        periodic checkpoint before any peer contact. A later STATE_SYNC
        only overrides it when the peer's round is NEWER (see
        ``_on_state_sync``). A torn checkpoint is reported loudly
        (the loader names the file) but does not kill the relaunch —
        the node falls back to the plain joiner path."""
        from p2pfl_tpu.federation.checkpoint import load_node_checkpoint

        ln = self.learner
        if (getattr(ln, "state", True) is None
                or getattr(ln, "fns", True) is None):
            ln.init()
        try:
            got = load_node_checkpoint(self.checkpoint_dir, self.idx,
                                       ln.get_parameters())
        except ValueError as e:
            log.warning("node %d resume failed, joining fresh: %s",
                        self.idx, e)
            flight.record("checkpoint.resume_failed", node=self.idx,
                          error=str(e)[:200])
            return
        if got is None:
            flight.record("checkpoint.resume_missing", node=self.idx)
            return
        params, rnd = got
        ln.set_parameters(params)
        self.initialized = True
        self.round = rnd
        self._resume_round = rnd
        flight.record("checkpoint.resume", node=self.idx, round=rnd)

    async def crash(self) -> None:
        """Failure injection (round 11 churn): abrupt teardown WITHOUT
        the STOP announcement — peers must detect the death through
        heartbeat silence and the reconnect-probe machine, exactly as
        for a real process kill. stop() after a crash is a no-op."""
        if self._crashed:
            return
        self._crashed = True
        flight.record("node.crash", node=self.idx, round=self.round)
        self.learning = False
        for t in [self._learn_task, *self._tasks]:
            if t is not None:
                t.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await t
        if self.shaper is not None:
            self.shaper.close()
        for peer in list(self.peers.values()):
            if peer.send_task:
                peer.send_task.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await peer.send_task
            if peer.reader_task:
                peer.reader_task.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await peer.reader_task
            peer.writer.close()
        self.peers.clear()
        if self._server:
            self._server.close()
        self._release_slot_refs()
        self.finished.set()
        # postmortem: the crash is exactly the moment the ring's
        # churn history stops being reconstructible any other way
        flight.dump(f"node{self.idx}.crash")

    def _release_slot_refs(self) -> None:
        """Return every shm slot this node still references — buffered
        future-round messages and the session's undecoded entries — to
        the host's sidecar arena. Crash/stop teardown MUST route here:
        a restarted node gets a fresh session, and slots stranded by
        the old one would bleed the shared arena dry."""
        if self.sidecar is None:
            return
        for _peer, msg in self._pending_params:
            if msg._slot is not None:
                self.sidecar.release(msg._slot)
                msg._slot = None
        release = getattr(self.session, "release_entries", None)
        if release is not None:
            release()

    # ------------------------------------------------------------------
    # partition control (round 14): the fault driver's scripted cut
    # ------------------------------------------------------------------
    def apply_partition(self, groups: list) -> None:
        """Sever every link crossing the ``groups`` cut, as seen from
        this node: outbound frames to peers in OTHER groups are dropped
        at the write layer. The driver applies the same cut on every
        node, so the sever is symmetric. A node absent from all groups
        is unaffected. Flows through membership as a ``partition``
        FaultEvent → Events.LINK_PARTITIONED + flight record."""
        mine = next((g for g in groups if self.idx in g), None)
        if mine is None:
            return
        others = {int(n) for g in groups if g is not mine for n in g}
        self._severed |= others - {self.idx}
        flight.record("node.partition", node=self.idx, round=self.round,
                      severed=sorted(self._severed))
        self.membership.apply_fault(
            FaultEvent(node=self.idx, kind="partition", groups=groups))

    def heal_partition(self) -> None:
        """The heal observation: reconnect all scripted cuts and grant
        eviction amnesty. Membership clears every sticky departure and
        re-arms an immediately-due probe; the existing probe machinery
        then redials each healed peer (``_peer_addrs``) and its first
        beat resurrects it — no operator action, no new merge math
        (the minority's model re-enters as a staleness-discounted
        ``add_model`` contribution via the round-11 stale-fold path)."""
        if not self._severed:
            return
        healed = sorted(self._severed)
        self._severed.clear()
        flight.record("node.heal", node=self.idx, round=self.round,
                      healed=healed)
        self.membership.apply_fault(FaultEvent(node=self.idx, kind="heal"))

    def _on_netem_transition(self, kind: str, groups: list) -> None:
        """Shaper-scheduled windows (NetworkConfig.partitions) reuse
        the same observation path as driver-scripted cuts. The shaper
        already drops the frames; here only the membership event +
        amnesty bookkeeping run. Severed-set updates are skipped for
        ``partition`` (the shaper owns the drop), but ``heal`` must
        still clear driver-applied state and trigger amnesty."""
        if kind == "partition":
            self.membership.apply_fault(
                FaultEvent(node=self.idx, kind="partition", groups=groups))
        else:
            self._severed.clear()
            self.membership.apply_fault(
                FaultEvent(node=self.idx, kind="heal"))

    def _link_severed(self, node: int) -> bool:
        """True while an open cut (driver- or shaper-scheduled)
        separates this node from ``node``."""
        if node in self._severed:
            return True
        return self.shaper is not None and self.shaper.severed_now(node)

    async def stop(self) -> None:
        if self._crashed:
            return
        # announce departure so peers drop us immediately instead of
        # waiting out the heartbeat timeout (Stop_cmd semantics).
        # Per-peer time bound, sent concurrently: one peer with a full
        # TCP send buffer must neither wedge our shutdown on drain()
        # nor starve the announcement to the healthy peers behind it.
        stop_msg = self._sign(Message(MsgType.STOP, self.idx))
        self.dedup.check_and_add(stop_msg.msg_id)

        async def announce(peer: PeerState) -> None:
            # routed through the peer's send lane (never a concurrent
            # direct write — that could interleave mid-frame with the
            # drain task); flush waits on the queue, bounded per peer
            with contextlib.suppress(Exception):
                await asyncio.wait_for(self._write(peer, stop_msg),
                                       timeout=1.0)
                if peer.send_q is not None:
                    await asyncio.wait_for(peer.send_q.join(), timeout=1.0)

        await asyncio.gather(
            *(announce(p) for p in list(self.peers.values()))
        )
        for t in [self._learn_task, *self._tasks]:
            if t is not None:
                t.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await t
        if self.shaper is not None:
            self.shaper.close()  # in-flight shaped messages are lost
        for peer in list(self.peers.values()):
            if peer.send_task:
                peer.send_task.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await peer.send_task
            if peer.reader_task:
                peer.reader_task.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await peer.reader_task
            peer.writer.close()
            with contextlib.suppress(Exception):
                await asyncio.wait_for(peer.writer.wait_closed(), timeout=1.0)
        self.peers.clear()
        if self._server:
            self._server.close()
            # NOT wait_closed(): on py3.12 it blocks until every peer
            # connection (including ones owned by other nodes) is gone
        self._release_slot_refs()

    def _transport_idx(self, writer: asyncio.StreamWriter) -> int | None:
        """The node index the connection's TLS certificate vouches for
        (None on plaintext federations)."""
        from p2pfl_tpu.p2p.tls import peer_index

        return peer_index(writer.get_extra_info("peercert"))

    def _hello_ok(self, hello: Message,
                  writer: asyncio.StreamWriter) -> bool:
        """CONNECT binding: with TLS on, the index claimed in the hello
        must be the one in the connection's certificate CN — otherwise
        member A could register a connection as member B and have every
        direct frame on it attributed to B. The hello's origin
        signature is checked too, binding its body (the dial-back port)
        to the same identity."""
        if self.tls is None:
            return True
        cert_idx = self._transport_idx(writer)
        if (cert_idx is not None and cert_idx == int(hello.sender)
                and self._verify_origin(hello)):
            return True
        log.warning(
            "node %d rejecting CONNECT: hello claims %s but certificate "
            "CN says %s", self.idx, hello.sender, cert_idx,
        )
        return False

    def _hello_body(self) -> dict:
        """CONNECT hello body: dial-back port, supported wire dtypes,
        and — when this node is entering a RUNNING federation — the
        live-join declaration ``"jr"`` (the last round it knows). The
        established side answers a ``"jr"`` hello with STATE_SYNC."""
        body = {"port": self.port, "wd": list(WIRE_DTYPES)}
        if self.joiner:
            body["jr"] = self.round
        return body

    async def connect_to(self, host: str, port: int) -> None:
        """Dial a neighbor (base_node.py connect_to)."""
        reader, writer = await asyncio.open_connection(
            host, port,
            ssl=self.tls.client_context() if self.tls else None,
        )
        await write_message(
            writer,
            self._sign(Message(MsgType.CONNECT, self.idx,
                               self._hello_body())),
        )
        hello = await read_message(reader)
        if not self._hello_ok(hello, writer):
            writer.close()
            raise ConnectionError("peer hello does not match its certificate")
        self._record_peer_wire(hello)
        peer = self._register_peer(int(hello.sender), reader, writer)
        self._on_hello_extras(peer, hello, host=host)
        log.debug("node %d connected to %d", self.idx, peer.idx)

    async def _on_connection(self, reader, writer) -> None:
        try:
            hello = await read_message(reader)
        except (asyncio.IncompleteReadError, ValueError):
            writer.close()
            return
        if hello.type is not MsgType.CONNECT or not self._hello_ok(
            hello, writer
        ):
            writer.close()
            return
        await write_message(
            writer,
            self._sign(Message(MsgType.CONNECT, self.idx,
                               self._hello_body())),
        )
        self._record_peer_wire(hello)
        peer = self._register_peer(int(hello.sender), reader, writer)
        self._on_hello_extras(peer, hello)

    def _on_hello_extras(self, peer: PeerState, hello: Message,
                         host: str | None = None) -> None:
        """Round-11 CONNECT extensions, applied once the connection is
        registered: remember the peer's dial-back address (reconnect
        probes redial it on heartbeat silence), and honor a live-join
        declaration ("jr") — clear any sticky departure so the joiner
        re-enters membership, and answer with the current model."""
        port = hello.body.get("port")
        if host is None:
            peername = peer.writer.get_extra_info("peername")
            host = peername[0] if peername else None
        if host is not None and port is not None:
            self._peer_addrs[peer.idx] = (host, int(port))
        if hello.body.get("jr") is None:
            return
        self.membership.apply_fault(
            FaultEvent(node=peer.idx, round=self.round, kind="join"))
        if self._tracer.enabled:
            self._tracer.count("peer_join")
        # Answer while learning OR after the run ended: a joiner that
        # dials in after the last round would otherwise wait forever
        # for a model that nobody is going to push. A finished node
        # replies with its FINAL state (round == total_rounds), so the
        # late joiner adopts the converged model, fast-forwards past
        # the whole schedule, and terminates immediately.
        if self.initialized and (self.learning or self.finished.is_set()):
            self._track_task(self._send_state_sync(peer), "state_sync")

    async def _send_state_sync(self, peer: PeerState) -> None:
        """Answer a joiner's hello with the current global model in
        CHECKPOINT format (federation.checkpoint.pack_model — the join
        path and the restart-from-disk path share one serialization)
        plus the run parameters it needs to fast-forward."""
        from p2pfl_tpu.federation.checkpoint import pack_model

        with self._tracer.span("p2p.state_sync", lane=self._lane,
                               args={"peer": peer.idx,
                                     "round": self.round}):
            flight.record("checkpoint.state_sync_out", node=self.idx,
                          peer=peer.idx, round=self.round)
            blob = pack_model(self.learner.get_parameters(), self.round)
            msg = self._sign(
                Message(MsgType.STATE_SYNC, self.idx,
                        {"round": self.round,
                         "rounds": self.total_rounds,
                         "epochs": self.epochs,
                         "leader": self.leader},
                        payload=blob)
            )
            try:
                await self._write(peer, msg)
            except (ConnectionError, RuntimeError):
                self._drop_conn(peer)

    def _record_peer_wire(self, hello: Message) -> None:
        """Remember the wire precisions the peer's CONNECT hello
        advertised ("wd"). Absent on pre-quantization peers — they are
        recorded as supporting nothing reduced, so every PARAMS send
        that targets them negotiates down to the f32 v1 envelope."""
        self._peer_wire[int(hello.sender)] = tuple(
            str(d) for d in hello.body.get("wd", ())
        )
        # once per CONNECT hello (NOT per send — _wire_dtype_for is hot)
        flight.record("wire.negotiate", node=self.idx,
                      peer=int(hello.sender),
                      peer_wd=list(self._peer_wire[int(hello.sender)]),
                      own=str(self.wire_dtype))

    def _register_peer(self, idx: int, reader, writer) -> PeerState:
        peer = PeerState(idx=idx, writer=writer)
        if self.shaper is None:
            # egress lane: bounded queue + one drain task per peer (the
            # shaped path has its own per-link queues in netem.py, so
            # only one writer owner ever exists per connection)
            peer.send_q = asyncio.Queue(
                maxsize=max(self.protocol.send_queue_depth, 1)
            )
            peer.send_task = asyncio.create_task(self._drain_send_q(peer))
        peer.reader_task = asyncio.create_task(self._read_loop(peer, reader))
        self.peers[idx] = peer
        self.membership.beat(idx)
        # tracked: protects against task GC and lets stop() cancel a
        # sync still draining a large init-weights write
        self._track_task(self._sync_peer(peer), "sync_peer")
        return peer

    async def _sync_peer(self, peer: PeerState) -> None:
        """Bring a NEW connection up to date with sticky state it may
        have missed as a one-shot flood — the deterministic replacement
        for the reference's paced Gossiper re-broadcast thread
        (gossiper.py:66-112): a late joiner learns our role, that
        learning is underway, and our round progress immediately."""

        async def send(msg: Message) -> None:
            # register our own msg_id first (as broadcast() does) so
            # the flood can't echo back and be re-processed/re-forwarded
            self._sign(msg)
            self.dedup.check_and_add(msg.msg_id)
            await self._write(peer, msg)

        try:
            await send(Message(MsgType.ROLE, self.idx, {"role": self.role}))
            if self.learning:
                await send(
                    Message(MsgType.START_LEARNING, self.idx,
                            {"rounds": self.total_rounds,
                             "epochs": self.epochs,
                             "leader": self.leader})
                )
                if self.initialized:
                    await send(Message(MsgType.MODEL_INITIALIZED, self.idx))
                    # a joiner that missed the initial diffusion gets
                    # the weights directly (diffusion loops have long
                    # exited by now)
                    await self._send_params(
                        peer, self.learner.get_parameters(), (), 1,
                        init=True,
                    )
                await send(
                    Message(MsgType.MODELS_READY, self.idx,
                            {"round": self.round})
                )
        except (ConnectionError, RuntimeError):
            self._drop_conn(peer)

    # ------------------------------------------------------------------
    # receive path
    # ------------------------------------------------------------------
    def _drop_conn(self, peer: PeerState) -> None:
        """Remove a dead connection — but only if it is STILL the
        registered one; a redialed replacement must not be evicted by
        the old connection's dying task."""
        if self.peers.get(peer.idx) is peer:
            self.peers.pop(peer.idx, None)
        if peer.send_task is not None and not peer.send_task.done():
            peer.send_task.cancel()
        if peer.send_q is not None:
            # discard queued frames and wake any producer blocked on a
            # full queue — the lane is dead, nothing will drain it
            while True:
                try:
                    peer.send_q.get_nowait()
                except asyncio.QueueEmpty:
                    break
                with contextlib.suppress(ValueError):
                    peer.send_q.task_done()

    def _teardown_conn(self, conn: PeerState) -> None:
        """Full lane teardown (send task included — an orphaned drain
        task parked on get() would outlive the run)."""
        self._drop_conn(conn)
        if conn.reader_task:
            conn.reader_task.cancel()
        conn.writer.close()

    def _evict_dead(self, node: int) -> None:
        """Reconnect budget exhausted: the crash is final as far as
        this node is concerned — same teardown as an explicit STOP, so
        round barriers and gossip stop waiting on the corpse. A later
        live re-join ("jr" hello) clears the sticky departure."""
        log.info("node %d evicting unreachable peer %d", self.idx, node)
        if self._tracer.enabled:
            self._tracer.count("peer_evicted")
        self.membership.evict(node)
        self.progress.pop(node, None)
        self.peer_roles.pop(node, None)
        conn = self.peers.pop(node, None)
        if conn is not None:
            self._teardown_conn(conn)
        self._secagg_on_evict(node)
        flight.dump(f"node{self.idx}.evicted_peer{node}")

    def _secagg_on_evict(self, node: int) -> None:
        """Dropout recovery: record the eviction and reveal this
        node's per-round pair seed against the corpse so every
        aggregator can reconstruct the dead pair's mask streams
        (Bonawitz reveal — unmasks nothing of any survivor)."""
        if (self.masker is None
                or self.masker.round_num is None
                or node not in self.masker.members
                or node in self.masker.evicted):
            return
        self.masker.note_evicted(node)
        seed = self.masker.reveal_share(node)
        flight.record("secagg.reveal", node=self.idx, dead=node,
                      round=self.masker.round_num)
        self._track_task(
            self.broadcast(Message(
                MsgType.SECAGG_SHARE, self.idx,
                {"dead": int(node),
                 "round": int(self.masker.round_num),
                 "seed": int(seed)},
            )),
            "secagg_share",
        )

    async def _drain_send_q(self, peer: PeerState) -> None:
        """Backpressure writer for one connection: drains the peer's
        bounded send queue in FIFO order. The queue only sees traffic
        when the lane is congested (see _write's idle-lane fast path),
        so this task is parked on get() in the steady state. A write
        failure drops the connection; the task then keeps consuming
        (discarding) so producers blocked on put() unwedge until
        stop()/drop cancels it."""
        dead = False
        while True:
            msg = await peer.send_q.get()
            try:
                if not dead:
                    peer.draining = True
                    try:
                        if peer.writer.is_closing():
                            # as in _write: on Python 3.12 a write to a
                            # closed transport is a TypeError out of
                            # asyncio, which would end this task
                            raise ConnectionResetError("peer writer closed")
                        await write_message(peer.writer, msg)
                        self._count_tx(peer, msg)
                    except (ConnectionError, RuntimeError, OSError):
                        dead = True
                        self._drop_conn(peer)
                    finally:
                        peer.draining = False
            finally:
                with contextlib.suppress(ValueError):
                    peer.send_q.task_done()

    def _count_rx(self, peer: PeerState, msg: Message) -> None:
        self.bytes_in += msg._wire_bytes
        pb = self.peer_bytes_in
        pb[peer.idx] = pb.get(peer.idx, 0) + msg._wire_bytes
        tr = self._tracer
        if tr.enabled:
            tr.count(f"rx_bytes/peer{peer.idx}", msg._wire_bytes)
            tr.count(f"rx_msgs/{msg.type.value}")

    def _count_tx(self, peer: PeerState, msg: Message) -> None:
        n = msg.wire_size()
        self.bytes_out += n
        pb = self.peer_bytes_out
        pb[peer.idx] = pb.get(peer.idx, 0) + n
        tr = self._tracer
        if tr.enabled:
            tr.count(f"tx_bytes/peer{peer.idx}", n)
            tr.count(f"tx_msgs/{msg.type.value}")

    async def _read_loop(self, peer: PeerState, reader) -> None:
        # with a sidecar, eligible PARAMS payloads land straight in the
        # shm arena (read_message's slot_sink) — the loop sees only the
        # header + a slot id, never the payload bytes
        sink = self._slot_sink if self.sidecar is not None else None
        try:
            while True:
                msg = await read_message(reader, slot_sink=sink)
                self._count_rx(peer, msg)
                await self._dispatch(peer, msg)
        except (asyncio.IncompleteReadError, ConnectionError, ValueError):
            self._drop_conn(peer)

    def _slot_sink(self, obj: dict, pl: int):
        """Divert decision for read_message: lease an arena slot for
        this payload, or None to keep the heap-bytes path. Eligible:
        unsigned PARAMS with contributor/weight metadata in the body
        ("c"/"w" — all session bookkeeping runs off the header), not
        init diffusion, not on a proxy (relays must re-ship the
        payload), and not a full-model adoption while this session
        waits (adoption decodes, so it stays on the heap)."""
        if obj.get("t") != MsgType.PARAMS.value or obj.get("g"):
            return None
        body = obj.get("b") or {}
        if body.get("init") or body.get("c") is None or body.get("w") is None:
            return None
        if self.role == "proxy":
            return None
        if self.session.waiting and body.get("aggregated"):
            return None
        lease = self.sidecar.lease(pl)
        if lease is None:
            return None  # arena exhausted/oversized: inline fallback
        slot, mv = lease
        return slot, mv, self.sidecar.release

    async def _dispatch(self, peer: PeerState, msg: Message) -> None:
        if not (0 <= msg.sender < self.n_nodes):
            # wire-supplied index guards every handler that indexes
            # membership/progress arrays — and garbage isn't forwarded
            return
        if msg.type in GOSSIPED:
            # peek-dedup first (duplicates cost no crypto), verify,
            # REGISTER ONLY WHAT VERIFIED. Registering before verifying
            # would let a malicious relay poison an id: forward a
            # corrupted copy of a mid-flood frame ahead of the honest
            # paths and the genuine message gets dropped as a duplicate
            # everywhere downstream — a one-member censorship primitive.
            if self.dedup.seen(msg.msg_id):
                return  # already processed — at-most-once
            if not self._verify_origin(msg):
                return  # forged: not processed, not forwarded, NOT SEEN
            self.dedup.check_and_add(msg.msg_id)
            # Relay damping on DECLARED full meshes (see __init__),
            # PERIODIC flood types only: the origin's direct broadcast
            # already reached everyone, so relays are pure redundancy —
            # but a DEAD A-B link with both ends otherwise fully
            # connected is invisible to the relaying third party C
            # (C still has n-1 peers), and C's relay is the only path
            # keeping A/B from falsely evicting each other. The relay
            # probability scales with the mesh so the EXPECTED number
            # of repair relays per beat stays ~1 regardless of n:
            # p = min(1, 1/(n-2)) over the n-2 third parties. At n=3
            # the lone third party always relays (a flat rate would
            # leave a severed A-B pair waiting ~1/p beats per crossing
            # and false-evicting inside node_timeout_s); at n=24 this
            # is ~0.045 — the measured relay traffic stays >95% gone.
            # One-shot floods (STOP, votes, leadership) always relay.
            # The peer-count guard restores full relaying whenever this
            # node's own links are down.
            relay_p = min(1.0, 1.0 / max(self.n_nodes - 2, 1))
            damped = (self.full_mesh
                      and msg.type in PERIODIC_FLOODS
                      and len(self.peers) >= self.n_nodes - 1
                      and self._rng.random() >= relay_p)
            if not damped:
                await self._forward(msg, exclude=peer.idx,
                                    limit=self.protocol.gossip_fanout)
        elif (msg.type in (MsgType.PARAMS, MsgType.STATE_SYNC)
              and not self._verify_origin(msg)):
            return
        t = msg.type
        if t is MsgType.BEAT:
            # sequence fence: the beat counter rides inside the signed
            # bytes, so a replayed BEAT (after its msg_id evicts from
            # the bounded dedup ring) cannot keep a crashed node alive
            # in membership — only strictly newer beats count
            seq = int(msg.body.get("n", 0))
            if seq > self._beat_seen.get(msg.sender, -1):
                self._beat_seen[msg.sender] = seq
                self.membership.beat(msg.sender)
        elif t is MsgType.ROLE:
            self.peer_roles[msg.sender] = msg.body["role"]
        elif t is MsgType.START_LEARNING:
            # finished-run fence: a replayed genuine START_LEARNING
            # must not restart a completed federation (and reset the
            # leader/history from its stale body)
            if not self.learning and not self.finished.is_set():
                self._start_learning(
                    msg.body["rounds"], msg.body["epochs"],
                    leader=msg.body.get("leader"),
                )
        elif t is MsgType.STOP_LEARNING:
            self._stop_learning()
        elif t is MsgType.METRICS:
            self.peer_metrics[msg.sender] = dict(msg.body)
        elif t is MsgType.STOP:
            # msg.sender left the federation (Stop_cmd semantics):
            # evict everywhere — membership (no timeout wait), progress
            # (round barriers), and the direct connection if one exists
            gone_id = int(msg.sender)
            self.membership.evict(gone_id)
            self.progress.pop(gone_id, None)
            self.peer_roles.pop(gone_id, None)
            conn = self.peers.pop(gone_id, None)
            if conn is not None:
                self._teardown_conn(conn)
            self._secagg_on_evict(gone_id)
        elif t is MsgType.PARAMS:
            await self._on_params(peer, msg)
        elif t is MsgType.STATE_SYNC:
            await self._on_state_sync(msg)
        elif t is MsgType.MODELS_AGGREGATED:
            # monotonic like MODELS_READY: flood paths (and post-
            # eviction replays) can deliver an older snapshot after a
            # newer one; within a round coverage only grows, so stale
            # rounds are ignored and same-round sets union
            pr = self._progress(msg.sender)
            r = int(msg.body.get("round", 0))
            if r > pr.agg_round:
                pr.models_aggregated = set(msg.body["contributors"])
                pr.agg_round = r
            elif r == pr.agg_round:
                pr.models_aggregated |= set(msg.body["contributors"])
        elif t is MsgType.MODEL_INITIALIZED:
            self._progress(msg.sender).initialized = True
        elif t is MsgType.MODELS_READY:
            pr = self._progress(msg.sender)
            # monotonic: flood paths can deliver an older snapshot (a
            # relayed _sync_peer message) after a newer one — a
            # regression would re-block the round barrier
            pr.ready_round = max(pr.ready_round, int(msg.body["round"]))
        elif t is MsgType.VOTE_TRAIN_SET:
            r = int(msg.body["round"])
            if r >= self.round:  # stale-round ballots are dead voters
                self._votes.setdefault(r, {})[msg.sender] = tuple(
                    int(c) for c in msg.body["candidates"]
                )
        elif t is MsgType.SECAGG_SHARE:
            # survivor's reveal for an evicted member's pair: file it
            # with the masker (stale-round shares are pruned at the
            # next begin_round), and mirror the eviction locally —
            # which also reveals OUR pair seed against the corpse once,
            # so reveals propagate quorum-wide even before every
            # survivor's own probe gives up on the dead node
            if self.masker is not None:
                self.masker.add_share(
                    int(msg.sender), int(msg.body["dead"]),
                    int(msg.body["round"]), int(msg.body["seed"]),
                )
                if int(msg.body["round"]) == self.masker.round_num:
                    self._secagg_on_evict(int(msg.body["dead"]))
        elif t is MsgType.TRANSFER_LEADERSHIP:
            # round fencing: the dedup ring is bounded, so a recorded
            # genuine transfer could be re-flooded rounds later after
            # its id evicts — a stale token must not reset leadership
            # (the body's round is inside the signed bytes)
            if int(msg.body.get("round", self.round)) >= self.round:
                self.leader = int(msg.body["to"])
                self.leader_history.append(self.leader)

    async def _on_params(self, peer: PeerState, msg: Message) -> None:
        """Traced entry: a tc-stamped frame (sender was tracing) is
        handled under a ``p2p.rx`` span parented to the sender's tx
        span — the cross-process edge — and its send→receive wall
        delta accrues into the round's wire seconds (skew-clamped; the
        critpath analyzer does the proper pairwise skew correction
        offline). Untraced (or legacy) frames skip straight through."""
        tr = self._tracer
        if tr.enabled and msg.tc is not None:
            rx_ns = time.time_ns()
            lat_s = (rx_ns - int(msg.tc[2])) / 1e9
            if 0.0 < lat_s < 60.0:
                self._cp_wire_s += lat_s
            with tr.span(
                "p2p.rx", lane=self._lane,
                args={"parent": msg.tc[1], "trace": msg.tc[0],
                      "tx_ns": int(msg.tc[2]), "rx_ns": rx_ns,
                      "from": msg.sender,
                      "round": int(msg.body.get("round", -1))},
            ):
                return await self._on_params_inner(peer, msg)
        return await self._on_params_inner(peer, msg)

    async def _on_params_inner(self, peer: PeerState,
                               msg: Message) -> None:
        # sender's tx span id: threads into session.add_model spans so
        # the ingest parents to the send even across a buffered replay
        cp = (msg.tc[1]
              if self._tracer.enabled and msg.tc is not None else None)
        if msg.body.get("init"):
            # whoever pushes initial weights evidently HAS the model —
            # count them initialized even if their MODEL_INITIALIZED
            # flood was lost or predates our connection, or our own
            # diffusion loop would chase their ack until its deadline
            self._progress(msg.sender).initialized = True
            if not self.initialized:
                payload = decode_parameters(msg.payload)
                self.learner.set_parameters(payload.params)
                self.initialized = True
                await self.broadcast(
                    Message(MsgType.MODEL_INITIALIZED, self.idx)
                )
                # relay the initial weights onward — on multi-hop
                # topologies (ring/random) the starter only reaches its
                # direct neighbors, so every receiver re-diffuses
                # (node.py:702-724 diffusion-until-initialized)
                self._track_task(self._diffuse_initial(),
                                 "diffuse_initial")
            return
        if self.role == "proxy" and msg.msg_id:
            # PROXY: relay weight traffic onward so it bridges nodes
            # with no direct link (node.py:492-515, 999-1017 — the
            # reference stores and re-gossips on a timer; here the
            # relay is immediate, deduped by msg_id so two proxies
            # can't ping-pong the same message)
            if self.dedup.check_and_add(msg.msg_id):
                await self._forward(msg, exclude=peer.idx)
        # round fencing: a round-r model must never enter a round-r'
        # session (a stale full aggregate would instantly "cover" a
        # fresh session and erase this round's training). Messages for
        # a future round — or for the current round while we are still
        # in the previous round's barrier (self.round is incremented
        # BEFORE the barrier, so the session is stale there) — are
        # buffered and replayed at that round's start.
        msg_round = int(msg.body.get("round", self.round))
        if msg_round > self.round or (
            msg_round == self.round and not self._round_active
        ):
            self._pending_params.append((peer, msg))
            return
        if msg_round < self.round:
            # Async elasticity (round 11): a straggler's update for a
            # RECENT round folds into the current session with a
            # staleness-discounted weight (1/(1+s)^beta, applied inside
            # add_model) instead of being dropped — FedBuff-style late
            # inclusion. Only raw contributions qualify: a stale FULL
            # aggregate is last round's RESULT, and adopting it would
            # instantly cover the fresh session and erase this round's
            # training (the exact hazard the round fence exists for).
            staleness = self.round - msg_round
            if (self.session.async_mode and self._round_active
                    and not self.session.waiting
                    and not msg.body.get("aggregated")):
                if msg._slot is not None:
                    # slot-native stale fold: staleness discounts the
                    # WEIGHT (params-agnostic), and the header's
                    # "c"/"w" metadata is all the session needs —
                    # the payload stays undecoded in the arena
                    contribs = frozenset(
                        int(c) for c in msg.body.get("c") or ())
                    ts = self.session.train_set
                    if contribs and not (ts and contribs >= ts):
                        covered = self.session.add_slot(
                            msg._slot, msg._slot_len, contribs,
                            int(msg.body.get("w", 1)),
                            staleness=staleness, parent=cp,
                        )
                        msg._slot = None  # session owns it now
                        if self._tracer.enabled:
                            self._tracer.count("stale_params_folded")
                        if covered:
                            await self.broadcast(
                                Message(
                                    MsgType.MODELS_AGGREGATED, self.idx,
                                    {"contributors": sorted(covered),
                                     "round": self.round},
                                )
                            )
                        return
                    self.sidecar.release(msg._slot)
                    msg._slot = None
                    return
                if (self.sidecar is not None and "c" in msg.body
                        and "w" in msg.body):
                    # arena was exhausted at the sink: the payload is
                    # loop-side bytes, but it still folds UNDECODED —
                    # add_blob retries the lease or queues the blob
                    contribs = frozenset(
                        int(c) for c in msg.body.get("c") or ())
                    ts = self.session.train_set
                    if contribs and not (ts and contribs >= ts):
                        covered = self.session.add_blob(
                            msg.payload, contribs,
                            int(msg.body.get("w", 1)),
                            staleness=staleness, parent=cp,
                        )
                        if self._tracer.enabled:
                            self._tracer.count("stale_params_folded")
                        if covered:
                            await self.broadcast(
                                Message(
                                    MsgType.MODELS_AGGREGATED, self.idx,
                                    {"contributors": sorted(covered),
                                     "round": self.round},
                                )
                            )
                    return
                self.loop_payload_touch_bytes += len(msg.payload)
                payload = decode_parameters(msg.payload)
                contribs = frozenset(payload.contributors)
                ts = self.session.train_set
                if contribs and not (ts and contribs >= ts):
                    covered = self.session.add_model(
                        payload.params, payload.contributors,
                        payload.weight, staleness=staleness, parent=cp,
                    )
                    if self._tracer.enabled:
                        self._tracer.count("stale_params_folded")
                    if covered:
                        await self.broadcast(
                            Message(
                                MsgType.MODELS_AGGREGATED, self.idx,
                                {"contributors": sorted(covered),
                                 "round": self.round},
                            )
                        )
                return
            if msg._slot is not None:
                self.sidecar.release(msg._slot)
                msg._slot = None
            return
        if self.session.waiting and not msg.body.get("aggregated"):
            if msg._slot is not None:
                self.sidecar.release(msg._slot)
                msg._slot = None
            return  # waiting nodes adopt only a *finished* aggregate
        if msg._slot is not None:
            if self.session.waiting:
                # buffered-then-replayed aggregate meeting a session
                # that turned waiting (e.g. voted out between rounds):
                # adoption needs the decoded tree. Rare, counted — the
                # zero-copy pin tolerates it only because the sink
                # never diverts adoption payloads on the live path.
                n = msg._slot_len
                self.loop_payload_touch_bytes += n
                blob = bytes(self.sidecar.view(msg._slot, n))
                self.sidecar.release(msg._slot)
                msg._slot = None
                payload = decode_parameters(blob)
                covered = self.session.add_model(
                    payload.params, payload.contributors, payload.weight,
                    parent=cp,
                )
            else:
                covered = self.session.add_slot(
                    msg._slot, msg._slot_len,
                    tuple(int(c) for c in msg.body.get("c") or ()),
                    int(msg.body.get("w", 1)), parent=cp,
                )
                msg._slot = None  # session owns it now
        elif (self.sidecar is not None and not self.session.waiting
                and "c" in msg.body and "w" in msg.body):
            # sink lease failed (arena momentarily exhausted): fold the
            # raw blob without decoding — same undecoded plane, just
            # via the descriptor queue instead of a slot
            covered = self.session.add_blob(
                msg.payload,
                tuple(int(c) for c in msg.body.get("c") or ()),
                int(msg.body.get("w", 1)), parent=cp,
            )
        else:
            self.loop_payload_touch_bytes += len(msg.payload)
            payload = decode_parameters(msg.payload)
            covered = self.session.add_model(
                payload.params, payload.contributors, payload.weight,
                parent=cp,
            )
        if covered:
            await self.broadcast(
                Message(
                    MsgType.MODELS_AGGREGATED, self.idx,
                    {"contributors": sorted(covered), "round": self.round},
                )
            )

    async def _on_state_sync(self, msg: Message) -> None:
        """Joiner side of the live-join handshake: adopt the
        established node's model (checkpoint format) and fast-forward
        to its round, then enter the running federation. Only declared
        joiners act on STATE_SYNC, the round fast-forward never rewinds,
        and the model is adopted at most once (first answer wins — the
        init-params catch-up from _sync_peer may already have landed).

        A checkpoint-resumed relaunch (round 14) arrives already
        initialized with its disk state at ``_resume_round``; the first
        STATE_SYNC then decides ONCE which side is newer — the peer's
        model is adopted only when its round is strictly ahead of the
        checkpoint, otherwise the (at least as fresh) disk state
        stands."""
        if not self.joiner:
            return
        rnd = int(msg.body.get("round", 0))
        adopt_over_resume = (self.initialized
                             and self._resume_round is not None
                             and rnd > self._resume_round)
        if self._resume_round is not None and not self.learning:
            flight.record("checkpoint.resume_decision", node=self.idx,
                          checkpoint_round=self._resume_round,
                          sync_round=rnd, adopt_sync=adopt_over_resume)
            self._resume_round = None  # first answer decides
        flight.record("checkpoint.state_sync_in", node=self.idx,
                      peer=int(msg.sender), round=rnd)
        with self._tracer.span("p2p.join", lane=self._lane,
                               args={"round": rnd, "from": msg.sender}):
            if rnd > self.round:
                if self.learning:
                    # defer for the WHOLE round body, not just the
                    # active-session window: _train_round awaits in its
                    # vote phase before _round_active is set, and a
                    # direct jump there would let the body's trailing
                    # round increment skip past the jump target. The
                    # learning loop applies the target at the next
                    # round boundary.
                    self._join_round_target = max(
                        self._join_round_target or 0, rnd)
                else:
                    self.round = rnd
            if not self.initialized or adopt_over_resume:
                ln = self.learner
                if (getattr(ln, "state", True) is None
                        or getattr(ln, "fns", True) is None):
                    ln.init()
                from p2pfl_tpu.federation.checkpoint import unpack_model

                try:
                    params, _ = unpack_model(
                        msg.payload, ln.get_parameters())
                except ValueError:
                    log.warning(
                        "node %d: STATE_SYNC blob from %d does not "
                        "match the local model", self.idx, msg.sender)
                    return
                ln.set_parameters(params)
                self.initialized = True
                await self.broadcast(
                    Message(MsgType.MODEL_INITIALIZED, self.idx))
            if self._tracer.enabled:
                self._tracer.count("join_state_sync")
            if not self.learning and not self.finished.is_set():
                self._start_learning(
                    int(msg.body.get("rounds", 0)),
                    int(msg.body.get("epochs", 1)),
                    leader=msg.body.get("leader"),
                )

    # ------------------------------------------------------------------
    # send path
    # ------------------------------------------------------------------
    def _sign(self, msg: Message) -> Message:
        """Origin-sign a self-originated message (no-op without TLS).
        Forwarded messages keep the ORIGIN's signature — only messages
        this node creates pass through here."""
        if self._signer is not None and not msg.sig:
            msg.sig = self._signer.sign(msg.signing_bytes())
            msg.cert = self._signer.cert_pem
            msg._head = None  # signature changes the framed-header memo
        return msg

    def _verify_origin(self, msg: Message) -> bool:
        """True iff the message's origin signature is valid for the
        claimed sender (always true on plaintext federations)."""
        if self._verifier is None:
            return True
        tr = self._tracer
        # a tc-stamped frame parents its verify span to the sender's
        # tx span (args built only on the traced path)
        args = None
        if tr.enabled and msg.tc is not None:
            args = {"parent": msg.tc[1], "from": msg.sender}
        with tr.span("p2p.verify", lane=self._lane, args=args):
            ok = self._verifier.verify(
                msg.cert, msg.sig, msg.signing_bytes(), msg.sender
            )
        if ok:
            if tr.enabled:
                tr.count("verify_ok")
            return True
        if tr.enabled:
            tr.count("verify_fail")
        log.warning(
            "node %d dropping %s with unverifiable origin claim sender=%d",
            self.idx, msg.type.value, msg.sender,
        )
        return False

    async def broadcast(self, msg: Message, exclude: int | None = None) -> None:
        self._sign(msg)
        if msg.type in GOSSIPED:
            self.dedup.check_and_add(msg.msg_id)
        await self._forward(msg, exclude)

    def _try_fast_write(self, peer: PeerState, msg: Message) -> bool:
        """Idle-lane fast path: when nothing is queued, the drain task
        is parked, and the transport buffer is under the high-water
        mark, write synchronously — no queue hop, no task wakeup, not
        even a drain() await (flow control is the buffer check itself;
        measured: routing EVERY frame through the queue cost ~17% on
        the 24-node control-bound round and ~38% on the payload-bound
        one). The checks and the write run without an await between
        them, so the sole-writer-per-connection invariant holds.
        Returns True when the frame was handled (written or the
        connection dropped), False when the caller must queue."""
        if peer.idx in self._severed:
            return True  # scripted partition: the frame dies on the cut
        q = peer.send_q
        if (q is None or not q.empty() or peer.draining
                or self.peers.get(peer.idx) is not peer):
            return False
        tr = peer.writer.transport
        if tr.is_closing() or tr.get_write_buffer_size() >= _FAST_LANE_MAX:
            return False
        try:
            peer.writer.writelines(msg.wire_segments())
        except (ConnectionError, RuntimeError, OSError):
            self._drop_conn(peer)
        else:
            self._count_tx(peer, msg)
        return True

    async def _write(self, peer: PeerState, msg: Message) -> None:
        """Single egress point: the idle-lane fast write when the
        peer's lane is clear, else enqueue onto its bounded send lane
        (the drain task owns the socket under congestion), or the link
        shaper's delayed/lossy schedule when network emulation is on.
        Blocks only when THIS peer's bounded queue is full
        (backpressure); never raises for delivery errors — those
        surface on the drain/link worker, which drops the connection."""
        if peer.idx in self._severed:
            return  # scripted partition (fault driver): symmetric drop
        if self.shaper is not None:
            await self.shaper.send(peer, msg)
        elif self._try_fast_write(peer, msg):
            return
        elif peer.send_q is not None and self.peers.get(peer.idx) is peer:
            tr = self._tracer
            if tr.enabled:
                # queue depth AT enqueue (incl. this frame): the lane's
                # congestion high-water mark — a depth pinned at the
                # bound means the bounded queue, not the socket, paces
                # this peer's egress
                tr.high_water(f"send_q_depth/peer{peer.idx}",
                              peer.send_q.qsize() + 1)
            await peer.send_q.put(msg)
        elif not peer.writer.is_closing():
            # pre-registration writes (none today) fall through direct.
            # So does a frame for a peer torn down under us (crash,
            # evict): that is a delivery error, which never raises here
            # — and on Python 3.12 a write to its closed transport is a
            # TypeError out of asyncio, not an OSError
            await write_message(peer.writer, msg)

    async def _forward(self, msg: Message, exclude: int | None = None,
                       limit: int = 0) -> None:
        """Send to peers. ``limit`` > 0 relays to a random subset
        instead (the GOSSIP_MESSAGES_PER_ROUND-style fan-out cap,
        gossiper.py:66-112): on dense overlays every receiver
        re-forwarding to ALL peers is O(peers^2) per flood; capped
        epidemic relay with at-most-once dedup reaches everyone whp
        in O(log n) hops at O(peers * fanout) traffic.

        Never serializes on a slow peer: idle lanes are written inline
        (synchronous, cheap); congested lanes are enqueued CONCURRENTLY
        — before round 7 this was a sequential write-then-drain loop,
        so one wedged TCP buffer stalled the fanout to every peer
        behind it."""
        targets = [p for p in self.peers.values() if p.idx != exclude]
        if limit > 0 and len(targets) > limit:
            targets = self._rng.sample(targets, limit)
        congested = [
            p for p in targets
            if self.shaper is not None or not self._try_fast_write(p, msg)
        ]
        if not congested:
            return

        async def enqueue(peer: PeerState) -> None:
            try:
                await self._write(peer, msg)
            except (ConnectionError, RuntimeError):
                self._drop_conn(peer)

        await asyncio.gather(*(enqueue(p) for p in congested))

    def _wire_dtype_for(self, peers, *, init: bool = False) -> str | None:
        """Negotiate the wire precision for one PARAMS send. Reduced
        precision requires EVERY target to have advertised it in its
        CONNECT hello; the initial model diffusion always ships f32
        (quantizing the common starting point would seed every node
        with a slightly different model and break same-seed parity
        with the f32 wire)."""
        if init or self.wire_dtype == "f32":
            return None
        if all(self.wire_dtype in self._peer_wire.get(p.idx, ())
               for p in peers):
            return self.wire_dtype
        return None

    def _apply_error_feedback(self, params):
        """Fold the residual of the previous int8 send into this one.

        Quantization is deterministic, so adding the carried error to
        the floating leaves BEFORE encode and recording the new
        carried error (carried-input minus its dequantized image) is
        exactly error-feedback compression — the wire still sees a
        plain int8 envelope. The residual is reset whenever the leaf
        structure changes (model swap between runs)."""
        leaves, treedef = jax.tree.flatten(
            jax.tree.map(np.asarray, params))
        res = self._ef_residual
        if res is None or len(res) != len(leaves) or any(
            r is not None and r.shape != np.shape(leaf)
            for r, leaf in zip(res, leaves)
        ):
            res = [
                np.zeros_like(leaf, dtype=np.float32)
                if np.issubdtype(leaf.dtype, np.floating) else None
                for leaf in leaves
            ]
        carried = [
            leaf.astype(np.float32) + r if r is not None else leaf
            for leaf, r in zip(leaves, res)
        ]
        tree = jax.tree.unflatten(treedef, carried)
        deq = jax.tree.leaves(dequantize_int8(*quantize_int8(tree)))
        self._ef_residual = [
            np.asarray(c, np.float32) - np.asarray(d, np.float32)
            if r is not None else None
            for c, d, r in zip(carried, deq, res)
        ]
        return tree

    async def _send_params(self, peers, params, contributors,
                           weight, _ef: bool = False, **body) -> None:
        """Ship a weights payload to one peer or a list of peers.

        The Message is built ONCE for the whole target list: the
        payload encode, the content hash, the signature, and the framed
        header are all per-message-lifetime costs — every additional
        recipient costs only a queue put of the same object (the frame
        memo makes the drain tasks reuse identical segments).

        ``_ef`` marks this node's OWN trained update: when the
        negotiated wire dtype is int8, the error-feedback residual is
        applied to it (aggregates/partials ship without EF — their
        error has no stable per-node carrier)."""
        if isinstance(peers, PeerState):
            peers = [peers]
        if not peers:
            return
        body.setdefault("round", self.round)
        # contributor/weight metadata rides the HEADER too (round 16):
        # a sidecar receiver runs its whole session bookkeeping —
        # supersede/evict, quorum, staleness folds — off these fields
        # without ever decoding the payload envelope
        body["c"] = [int(c) for c in contributors]
        body["w"] = int(weight)
        wd = self._wire_dtype_for(peers, init=bool(body.get("init")))
        if wd == "int8" and _ef:
            params = self._apply_error_feedback(params)
        blob = encode_parameters(params, tuple(contributors), int(weight),
                                 wire_dtype=wd)
        self.params_bytes_out += len(blob) * len(peers)
        msg = self._sign(
            Message(MsgType.PARAMS, self.idx, body, payload=blob,
                    # explicit id: PARAMS is a direct message, but
                    # proxies relay it and need at-most-once dedup
                    msg_id=secrets.token_hex(8))
        )
        # causal trace context (round 18): stamp the header's tc
        # BEFORE the first encode (the framed-header memo is built
        # once for the whole target list) and time the send under a
        # tx span whose id rides the wire — rx-side spans parent to
        # it, turning the merged trace into a cross-process graph.
        # Untraced path: msg.tc stays None and the header bytes are
        # identical to the pre-tc format (pinned by test).
        tr = self._tracer
        tx_span = NULL_SPAN
        if tr.enabled:
            sid = tr.next_span_id()
            msg.tc = (tr.trace_id, sid, time.time_ns())
            tx_span = tr.span(
                "p2p.tx", lane=self._lane,
                args={"sid": sid, "round": int(body["round"]),
                      "n_peers": len(peers), "bytes": len(blob)})
        with tx_span:
            congested = [
                p for p in peers
                if self.shaper is not None
                or not self._try_fast_write(p, msg)
            ]
            if not congested:
                return

            async def ship(peer: PeerState) -> None:
                try:
                    await self._write(peer, msg)
                except (ConnectionError, RuntimeError):
                    self._drop_conn(peer)

            await asyncio.gather(*(ship(p) for p in congested))

    # ------------------------------------------------------------------
    # control plane loops
    # ------------------------------------------------------------------
    async def _heartbeat_loop(self) -> None:
        period = self.protocol.heartbeat_period_s
        beats = 0
        while True:
            self.membership.beat(self.idx)
            # the sequence is wall-clock-derived (ms), not a zero-based
            # counter: it must stay monotonic across a process restart
            # or a recovered node's fresh beats would read as replays.
            # Skew doesn't matter — receivers compare per-sender only.
            await self.broadcast(
                Message(MsgType.BEAT, self.idx,
                        {"n": int(time.time() * 1000)})
            )
            beats += 1
            if beats % 2 == 0:
                # role refresh every 2nd beat (heartbeater.py:66-78
                # SEND_ROLE cadence) — keeps role views converged even
                # if the initial ROLE flood was missed
                await self.broadcast(
                    Message(MsgType.ROLE, self.idx, {"role": self.role})
                )
            self.membership.advance_to(self.membership.clock + period)
            await self._probe_suspects()
            await asyncio.sleep(period)

    async def _probe_suspects(self) -> None:
        """Actual peer-death detection (round 11): probe each SUSPECT
        (heartbeat-timed-out; NODE_DIED already fired) whose backoff
        window elapsed. A real process death closes its sockets, so by
        the time heartbeat silence is noticed the read loop has already
        dropped the peer entry — redial, and membership clears the
        suspicion on the replacement's first beat. A STILL-registered
        open lane is the opposite case: heartbeat silence there is far
        more often event-loop lag (CPU-bound fits starve the loop in
        packed layouts) than death, and tearing down a healthy lane
        drops in-flight round traffic — so leave it alone and only burn
        a retry, which keeps a genuinely wedged-but-open connection on
        the same bounded path to eviction. Once the retry budget is
        exhausted the death goes sticky (_evict_dead)."""
        for node in self.membership.probes_due():
            if self._link_severed(node):
                # a probe cannot succeed across an open partition cut —
                # but the in-process emulation's TCP dial WOULD (the cut
                # drops frames, it doesn't close sockets), so count the
                # failure here instead of letting the dial lie
                if self.membership.probe_failed(node):
                    self._evict_dead(node)
                continue
            conn = self.peers.get(node)
            if conn is not None and not conn.writer.is_closing():
                if self.membership.probe_failed(node):
                    self._evict_dead(node)
                elif self._tracer.enabled:
                    self._tracer.count("probe_defer")
                continue
            addr = self._peer_addrs.get(node)
            ok = False
            if addr is not None:
                if conn is not None:
                    # lane already closing: finish the teardown so the
                    # redial replaces it instead of racing it
                    self._teardown_conn(conn)
                with self._tracer.span("p2p.probe", lane=self._lane,
                                       args={"peer": node}):
                    try:
                        await asyncio.wait_for(
                            self.connect_to(*addr),
                            timeout=self.protocol.heartbeat_period_s,
                        )
                        ok = True
                    except Exception:
                        ok = False
            if ok:
                flight.record("membership.probe", node=node, ok=True)
                if self._tracer.enabled:
                    self._tracer.count("probe_ok")
            elif self.membership.probe_failed(node):
                self._evict_dead(node)
            elif self._tracer.enabled:
                self._tracer.count("probe_fail")

    # ------------------------------------------------------------------
    # learning
    # ------------------------------------------------------------------
    def set_start_learning(self, rounds: int, epochs: int = 1) -> None:
        """Initiator entry point (node.py:224)."""
        self._track_task(self._kickoff(rounds, epochs), "kickoff")

    async def _kickoff(self, rounds: int, epochs: int) -> None:
        await self.broadcast(
            Message(
                MsgType.START_LEARNING, self.idx,
                {"rounds": rounds, "epochs": epochs, "leader": self.idx
                 if self.role in ("server", "aggregator") else None},
            )
        )
        # initial model diffusion (node.py:299): push our weights until
        # every peer reports initialized. The starter must flood its own
        # MODEL_INITIALIZED too: an adopter re-diffuses until EVERY peer
        # — starter included — reports initialized, and nothing else
        # ever acks the starter, so a node that enters its learning
        # loop already-adopted would block in _diffuse_initial for the
        # whole aggregation timeout waiting on it.
        self.initialized = True
        await self.broadcast(Message(MsgType.MODEL_INITIALIZED, self.idx))
        self._start_learning(rounds, epochs, leader=self.idx)

    def _start_learning(self, rounds, epochs, leader=None) -> None:
        self.learning = True
        self.total_rounds = rounds
        self.epochs = epochs
        if leader is not None:
            self.leader = leader
            self.leader_history.append(leader)
        self._track_task(
            self.broadcast(
                Message(MsgType.ROLE, self.idx, {"role": self.role})
            ),
            "role_announce",
        )  # heartbeater.py:74 SEND_ROLE analog — peers learn who aggregates
        self._learn_task = asyncio.create_task(self._learning_loop())

    def _stop_learning(self) -> None:
        self.learning = False
        if self._learn_task:
            self._learn_task.cancel()
        self.finished.set()

    def _progress(self, idx: int) -> NodeProgress:
        if idx not in self.progress:
            self.progress[idx] = NodeProgress()
        return self.progress[idx]

    def _aggregated_by(self, idx: int) -> set[int]:
        """What node ``idx`` has aggregated THIS round (stale rounds
        read as empty — the reference clears per-peer aggregation state
        at round end, node.py:646)."""
        pr = self.progress.get(idx)
        if pr is None or pr.agg_round != self.round:
            return set()
        return pr.models_aggregated

    def _train_set(self) -> set[int]:
        alive = set(self.membership.get_nodes())
        return (alive & (set(self.peers) | {self.idx}))

    def _trainable(self, nodes: set[int]) -> set[int]:
        """Nodes that may carry training duty: proxies and idles are
        never train-set candidates (they forward/adopt but don't
        contribute — node.py:492-524)."""
        out = set()
        for i in nodes:
            role = self.peer_roles.get(i) if i != self.idx else self.role
            if role not in ("proxy", "idle"):
                out.add(i)
        return out

    async def _vote_train_set(self) -> set[int]:
        """Elect this round's train set (node.py:537-630 vote flow,
        VOTE_TIMEOUT + TRAIN_SET_SIZE knobs, participant.json.example:70).

        Every node's ballot is the trainable part of its own live
        neighborhood (itself + direct peers it believes alive) — the
        nodes it can vouch for. Ballots flood the overlay; the tally
        elects the ``train_set_size`` best-vouched-for candidates with
        index tie-break, so every node computes the same winners from
        the same ballots. Dead voters (evicted by membership) are
        dropped from the tally. If the ballot flood does NOT complete
        within ``vote_timeout_s``, the tally would depend on which
        ballots arrived where — so the election falls back to a
        deterministic ballot-independent function of the local alive
        view instead (identical winners whenever membership views
        agree, which heartbeats converge far faster than vote floods).
        """
        loop = asyncio.get_event_loop()
        alive = set(self.membership.get_nodes())
        ballot = sorted(
            self._trainable(alive & (set(self.peers) | {self.idx}))
        )
        votes = self._votes.setdefault(self.round, {})
        votes[self.idx] = tuple(ballot)
        await self.broadcast(
            Message(MsgType.VOTE_TRAIN_SET, self.idx,
                    {"round": self.round, "candidates": ballot})
        )
        deadline = loop.time() + self.protocol.vote_timeout_s
        complete = False
        while loop.time() < deadline:
            alive = set(self.membership.get_nodes())
            if alive <= set(votes):
                complete = True  # every live node's ballot arrived
                break
            await asyncio.sleep(self.gossip_period_s)
        if not complete:
            # Deterministic incomplete-ballot path: a partial tally
            # depends on WHICH ballots happened to arrive here before
            # the timeout, so two slow-gossip nodes could elect
            # different train sets and their aggregation sessions
            # would only close by timeout. Fall back to a
            # ballot-independent election over the trainable alive
            # MEMBERSHIP view (beats flood, so it spans multi-hop
            # overlays — restricting to direct peers would diverge on
            # a ring); nodes that share a membership view (heartbeats
            # converge much faster than a vote flood) agree again.
            alive = set(self.membership.get_nodes())
            cands = self._trainable(alive)
            tally = {c: 1 for c in cands}
        else:
            tally = {}
            for voter, cands in votes.items():
                if voter in alive:  # dead voters dropped (node.py:537-548)
                    for c in cands:
                        tally[c] = tally.get(c, 0) + 1
        k = self.protocol.train_set_size
        if k <= 0 or k > len(tally):
            k = len(tally)
        # tie-break ROTATES with the round so a binding cap still
        # covers every node's data over time (the reference's vote
        # uses random weights for the same effect, node.py:573-598);
        # round number is barrier-agreed, so all nodes elect the same set
        winners = sorted(
            tally,
            key=lambda c: (-tally[c], (c - self.round) % self.n_nodes),
        )[:k]
        win = set(winners) or {self.idx}
        # the leader must aggregate, so it is always seated (CFL server /
        # SDFL token holder); it displaces the weakest winner
        if (self.leader is not None and self.leader in alive
                and self.leader not in win):
            if winners and len(win) >= k:
                win.discard(winners[-1])
            win.add(self.leader)
        # ballots for finished rounds are garbage; future ones are kept
        self._votes = {r: v for r, v in self._votes.items() if r > self.round}
        return win

    async def _learning_loop(self) -> None:
        ln = self.learner
        # per-node profile (round 11): a compute-class epochs override
        # beats the federation-wide START_LEARNING value
        ln.set_epochs(self.local_epochs
                      if self.local_epochs is not None else self.epochs)
        if getattr(ln, "state", True) is None or getattr(ln, "fns", True) is None:
            ln.init()
        if self.initialized:
            await self._diffuse_initial()
        else:
            # wait for the initializer's weights
            while not self.initialized:
                await asyncio.sleep(self.gossip_period_s)
        self.learn_t0 = time.monotonic()
        while self.round < self.total_rounds:
            if self._join_round_target is not None:
                # deferred join fast-forward (STATE_SYNC landed while a
                # round body was active): jump at the boundary, where
                # no session references the old round number
                self.round = max(self.round, self._join_round_target)
                self._join_round_target = None
                if self.round >= self.total_rounds:
                    break
            t0 = time.monotonic()
            round_no = self.round
            self._cp_fit_s = self._cp_wait_s = self._cp_wire_s = 0.0
            self._cp_agg_mark = self.session.agg_wall_s
            with self._tracer.span("node.round", lane=self._lane,
                                   args={"round": self.round}):
                await self._train_round()
            wall = time.monotonic() - t0
            self.round_wall_s.append(wall)
            self._cp_snapshot(round_no, wall)
            self._maybe_checkpoint()
        self.learn_t1 = time.monotonic()
        # final evaluation, shared with the federation (the metrics
        # flood the reference stubbed out, node.py:611-620 + 875-878)
        try:
            metrics = await asyncio.get_running_loop().run_in_executor(
                None, self.learner.evaluate
            )
            self.peer_metrics[self.idx] = {"round": self.round, **metrics}
            await self.broadcast(
                Message(MsgType.METRICS, self.idx,
                        {"round": self.round, **metrics})
            )
        except Exception:  # evaluation is best-effort reporting
            log.exception("node %d final evaluate failed", self.idx)
        self.learning = False
        self.finished.set()

    def _maybe_checkpoint(self) -> None:
        """Round-boundary per-node checkpoint (round 14). Runs on the
        loop — the blob is one small msgpack serialize plus an fsynced
        file replace; a crash between rounds then restarts from a state
        at most ``checkpoint_every`` rounds old. Failures are reported
        and swallowed: checkpointing must never kill a healthy round
        loop (a full disk is an ops alert, not a training fault)."""
        if (not self.checkpoint_dir or self.checkpoint_every <= 0
                or self.round % self.checkpoint_every != 0):
            return
        from p2pfl_tpu.federation.checkpoint import save_node_checkpoint

        try:
            save_node_checkpoint(self.checkpoint_dir, self.idx,
                                 self.learner.get_parameters(), self.round)
            self.membership.notify(Events.CHECKPOINT_SAVED,
                                   {"node": self.idx, "round": self.round})
        except Exception as e:
            log.warning("node %d checkpoint failed at round %d: %s",
                        self.idx, self.round, e)

    async def _diffuse_initial(self) -> None:
        params = self.learner.get_parameters()
        loop = asyncio.get_event_loop()
        deadline = loop.time() + self.protocol.aggregation_timeout_s
        # re-send pacing: a resend before the previous copy could even
        # arrive and be acknowledged (via the MODEL_INITIALIZED flood)
        # just convoys megabytes behind itself — especially under
        # shaped/delayed links. The reference paces diffusion at
        # GOSSIP_MODELS_FREC = 1 Hz for the same reason.
        retry_s = max(self.gossip_period_s * 4, 0.5)
        last_sent: dict[int, float] = {}
        while (
            any(not self._progress(i).initialized for i in self.peers)
            and loop.time() < deadline
        ):
            now = loop.time()
            due = []
            for idx, peer in list(self.peers.items()):
                if (not self._progress(idx).initialized
                        and now - last_sent.get(idx, -1e9) >= retry_s):
                    last_sent[idx] = now
                    due.append(peer)
            if due:
                # one encode+sign for the whole sweep — every due peer
                # gets the same Message object off its own send lane
                await self._send_params(due, params, (), 1, init=True)
            await asyncio.sleep(self.gossip_period_s)

    def _effective_role(self) -> str:
        """SDFL: the aggregator role follows the leadership token
        (node.py:649-686); other schemes use the static role."""
        if self.federation == "SDFL":
            return "aggregator" if self.leader == self.idx else "trainer"
        return self.role

    async def _fit(self) -> None:
        """Local training off the event loop: a blocking device call in
        line would starve heartbeats/gossip for the whole epoch and get
        peers evicted by membership timeouts.

        ``fit_slowdown`` (heterogeneous compute classes, round 11)
        stretches the fit by sleeping ``elapsed * (k - 1)`` AFTER the
        real fit: a straggler is exactly k× its own natural speed, with
        no absolute-time guess that would drift across models/hosts —
        and the sleep yields the loop, so heartbeats keep flowing."""
        t0 = time.monotonic()
        with self._tracer.span("node.fit", lane=self._lane,
                               args={"round": self.round}):
            await asyncio.get_running_loop().run_in_executor(
                None, self.learner.fit
            )
        if self.fit_slowdown > 1.0:
            await asyncio.sleep(
                (time.monotonic() - t0) * (self.fit_slowdown - 1.0)
            )
        # slowdown sleep included: the critical path cares how long
        # this node's update took to exist, not why
        self._cp_fit_s += time.monotonic() - t0

    def _cp_snapshot(self, round_no: int, wall: float) -> None:
        """Fold the round's accumulators into ``critpath_last`` — the
        per-node fit/wire/wait/aggregate/other split the status
        publisher flattens into critpath_* gauges (monitor WAIT%
        column, webapp breakdown pane).

        Wire seconds accrue per received frame and overlap the quorum
        wait (arrivals land while this node sleeps in the wait loops),
        so wire is carved OUT of wait: of the time spent waiting, wire
        is the part the bytes were actually in flight/queued, wait is
        the part the peers simply hadn't finished. ``other`` is the
        residual (vote, encode, bookkeeping), clamped at zero — the
        five components always sum to the measured round wall."""
        fit = self._cp_fit_s
        agg = max(0.0, self.session.agg_wall_s - self._cp_agg_mark)
        wire = min(self._cp_wire_s, self._cp_wait_s)
        wait = self._cp_wait_s - wire
        other = max(0.0, wall - fit - wait - wire - agg)
        self.critpath_last = {
            "round": round_no, "round_s": round(wall, 6),
            "fit_s": round(fit, 6), "wire_s": round(wire, 6),
            "wait_s": round(wait, 6), "agg_s": round(agg, 6),
            "other_s": round(other, 6),
        }

    def round_p95_s(self) -> float | None:
        """p95 of completed round wall times (None before the first
        round finishes) — the tail statistic the status publisher and
        monitor columns report; a mean would hide the one straggler
        round a stalled peer causes."""
        if not self.round_wall_s:
            return None
        xs = sorted(self.round_wall_s)
        return xs[min(len(xs) - 1, int(0.95 * len(xs)))]

    def _poisons_updates(self) -> bool:
        return self.attack is not None and self.attack.poisons_updates

    def _poison_own_update(self, ref) -> None:
        """Malicious node: transform the trained params ONCE, in place
        via set_parameters — the poisoned tree then backs both the own-
        session add_model AND every _send_params, exactly like the SPMD
        path's poisoned row entering every mix (its own included).
        ``ref`` is the round-start params (pre-fit snapshot); keyed by
        (seed, idx, round) so the SPMD row is bit-identical."""
        from p2pfl_tpu.adversary.attacks import poison_update

        flight.record("attack.inject", node=self.idx, round=self.round,
                      attack=type(self.attack).__name__)
        self.learner.set_parameters(
            poison_update(self.learner.get_parameters(), ref,
                          self.idx, self.round, self.attack)
        )

    def _privatize_own_update(self, ref) -> None:
        """DP-FedAvg: clip + noise the trained params ONCE in place —
        the privatized tree then backs the own-session add_model AND
        every _send_params, exactly like the SPMD path's privatized row
        entering every mix. ``ref`` is the round-start params; keyed by
        (dp.seed, idx, round) so the SPMD row is bit-identical."""
        from p2pfl_tpu.privacy.dp import dp_key, privatize_update_jit

        flight.record("dp.privatize", node=self.idx, round=self.round)
        self.learner.set_parameters(
            privatize_update_jit(
                self.learner.get_parameters(), ref,
                self.dp.clip_norm, self.dp.noise_multiplier,
                dp_key(self.dp.seed, self.idx, self.round),
            )
        )

    async def _train_round(self) -> None:
        train_set = await self._vote_train_set()
        self.session.clear()
        if self.masker is not None:
            # fresh pair-mask streams for this round's member set; a
            # mid-round eviction then knows exactly which pairs may
            # need reconstruction at quorum close
            self.masker.begin_round(self.round, train_set)
        # Snapshot the effective role and token position for the WHOLE
        # round: a TRANSFER_LEADERSHIP that lands mid-round must not
        # flip this round's behavior (it takes effect next round), or a
        # node that both led and received the token would rotate twice
        # in one round.
        role = self._effective_role()
        leader_at_start = self.leader
        if self.idx not in train_set and role in ("aggregator", "trainer"):
            # voted out this round: no training duty, adopt only
            # (the reference's is-in-train-set gate, node.py:425-427)
            role = "idle"
        # session mode is set BEFORE fit (which runs in an executor)
        # and BEFORE replaying buffered messages: an aggregate arriving
        # mid-fit or buffered from a fast peer must be adopted by a
        # waiting node, not mistaken for a regular partial contribution
        if role in ("aggregator", "server"):
            self.session.set_nodes_to_aggregate(train_set)
            # round-start params: the delta reference for reputation
            # scoring — and under secagg the dtype/shape template the
            # masked sum dequantizes against at close (set BEFORE the
            # pending replay below — a replayed model can complete
            # coverage and finish the session immediately)
            if self.reputation is not None or self.masker is not None:
                self.session.set_reference(self.learner.get_parameters())
        else:
            self.session.set_waiting_aggregated_model()
        self._round_active = True
        # replay weight messages that arrived before this round's
        # session was ready for them
        pending, self._pending_params = self._pending_params, []
        for peer, msg in pending:
            if peer.idx in self.peers:
                # inner entry: the rx span + wire-latency accrual fired
                # at true arrival; replaying through the traced wrapper
                # would double-count the frame's wire seconds
                await self._on_params_inner(peer, msg)
            elif msg._slot is not None and self.sidecar is not None:
                # the sender is gone; return its buffered payload's slot
                self.sidecar.release(msg._slot)
                msg._slot = None
        if role in ("aggregator", "server"):
            ref = (self.learner.get_parameters()
                   if self._poisons_updates() or self.dp is not None
                   else None)
            await self._fit()
            if self._poisons_updates():
                self._poison_own_update(ref)
            if self.dp is not None:
                # privatize AFTER any poisoning (the clip then also
                # bounds injected updates — deployment semantics,
                # matching the SPMD round fn's ordering)
                self._privatize_own_update(ref)
            n_samples = self.learner.get_num_samples()[0]
            own = self.learner.get_parameters()
            if self.masker is not None:
                # the masked tree is what enters the session AND what
                # gossip forwards — the raw update never leaves the
                # learner
                own = self.masker.mask_update(own, n_samples)
            covered = self.session.add_model(own, (self.idx,), n_samples)
            await self.broadcast(
                Message(MsgType.MODELS_AGGREGATED, self.idx,
                        {"contributors": sorted(covered),
                         "round": self.round})
            )
            await self._gossip_until_done(train_set, role, leader_at_start)
        elif role == "trainer":
            ref = (self.learner.get_parameters()
                   if self._poisons_updates() or self.dp is not None
                   else None)
            await self._fit()
            if self._poisons_updates():
                self._poison_own_update(ref)
            if self.dp is not None:
                self._privatize_own_update(ref)
            n_samples = self.learner.get_num_samples()[0]
            own = self.learner.get_parameters()
            if self.masker is not None:
                own = self.masker.mask_update(own, n_samples)
            target = (
                leader_at_start if leader_at_start in self.peers else None
            )
            sent_to = (
                [self.peers[target]] if target is not None
                else list(self.peers.values())
            )
            await self._send_params(
                sent_to, own, (self.idx,), n_samples, _ef=True,
            )
            await self._wait_done()
        else:  # idle / proxy: adopt whatever aggregate arrives
            await self._wait_done()

        if self.session.result is not None:
            params, _ = self.session.result
            self.learner.set_parameters(params)
        self._round_active = False  # barrier window: buffer, don't drop
        self.round += 1
        self.learner.finalize_round()
        if self.federation == "SDFL" and role == "aggregator":
            # Rotate the aggregator token (node.py:676-686 "random",
            # excluding self like the reference's choice of neighbors).
            # Rotation is decided by the node that LED this round (the
            # snapshot above), and broadcast BEFORE MODELS_READY: the
            # per-peer TCP stream is ordered, so no peer can observe our
            # round completion (and exit its round barrier) without
            # having the new token — the next round always starts with
            # exactly one leader everywhere.
            candidates = sorted(
                (train_set & set(self.membership.get_nodes())) - {self.idx}
            )
            if candidates:
                new_leader = self._rng.choice(candidates)
                self.leader = new_leader
                self.leader_history.append(new_leader)
                await self.broadcast(
                    Message(MsgType.TRANSFER_LEADERSHIP, self.idx,
                            # self.round was just incremented: the token
                            # names the round it takes effect in, and
                            # receivers reject transfers for past rounds
                            {"to": new_leader, "round": self.round})
                )
        await self.broadcast(
            Message(MsgType.MODELS_READY, self.idx, {"round": self.round})
        )
        await self._wait_neighbors_ready()

    async def _gossip_until_done(
        self, train_set: set[int], role: str, leader_at_start: int | None
    ) -> None:
        """Partial-aggregation gossip (node.py:692-700 + 726-809):
        send each stale peer the aggregate of models it lacks, until
        the session completes (coverage or timeout). ``role`` and
        ``leader_at_start`` are the caller's round-start snapshot — the
        live token may have moved mid-round."""
        fanout = max(self.protocol.gossip_models_per_round, 1)
        loop = asyncio.get_event_loop()
        # wait-on-quorum accounting: this loop's wall time, net of any
        # aggregation that ran inside it (session.agg_wall_s delta) —
        # partial-encode/gossip work in here is noise against the
        # multi-second quorum waits the breakdown exists to expose
        tw0 = time.monotonic()
        agg0 = self.session.agg_wall_s
        with self._tracer.span("node.wait", lane=self._lane,
                               args={"round": self.round,
                                     "kind": "gossip"}):
            try:
                await self._gossip_body(train_set, role,
                                        leader_at_start, fanout, loop)
            finally:
                self._cp_wait_s += max(
                    0.0, (time.monotonic() - tw0)
                    - (self.session.agg_wall_s - agg0))

    async def _gossip_body(self, train_set, role, leader_at_start,
                           fanout, loop) -> None:
        last_status = None
        last_change_t = loop.time()
        deadline = loop.time() + self.session.timeout_s
        self._gossip_sent: dict[int, tuple[frozenset, float]] = {}
        # who is expected to AGGREGATE this round: in CFL/SDFL only the
        # round's leader fuses models (trainers adopt its offer — they
        # will never show coverage themselves, so waiting on them would
        # deadlock until timeout); in DFL every train-set node with an
        # aggregating role does (the reference's split between
        # aggregation-gossip and diffusion, node.py:692-724)
        if self.federation in ("CFL", "SDFL"):
            aggregators = (
                {leader_at_start} if leader_at_start is not None else set()
            )
        else:
            aggregators = {
                i for i in train_set
                if self.peer_roles.get(i, "aggregator")
                in ("aggregator", "server")
            }
        while True:
            done = self.session.check_and_run()
            proxies = [
                p for i, p in self.peers.items()
                if self.peer_roles.get(i) == "proxy"
            ]
            # target = an aggregating NODE that hasn't covered the
            # WHOLE train set yet (node.py:695 candidate condition) —
            # gossip continues even after our own session completes,
            # or a node whose session fills up early (it received
            # everyone during its fit) would never ship its own model.
            # Progress floods, so this covers nodes reachable only
            # through a PROXY — but only REACHABLE targets may consume
            # fanout slots (building a partial for an undeliverable
            # node would waste both the aggregation and the slot), and
            # only LIVE ones: a crashed aggregator (heartbeat-evicted,
            # no STOP) must stop consuming fanout slots and proxy
            # bandwidth even while a proxy path to its address exists.
            live = set(self.membership.get_nodes())
            # In async mode a peer stops being a gossip target once its
            # coverage meets the QUORUM its own session closes on: full
            # train-set coverage is unreachable whenever a voted member
            # crashed mid-round, and chasing it would pin every round
            # at the aggregation deadline — exactly the serialization
            # the buffered session exists to remove. Sync mode keeps
            # the full-coverage bar (quorum is the whole train set).
            quorum = (self.session.quorum()
                      if self.session.async_mode else None)

            def _stale_target(has: set[int]) -> bool:
                if train_set <= has:
                    return False
                return quorum is None or len(has & train_set) < quorum

            targets = [
                (i, self._aggregated_by(i))
                for i in sorted((aggregators - {self.idx}) & live)
                if _stale_target(self._aggregated_by(i))
                and (i in self.peers or proxies)
            ]
            if (done and not targets) or loop.time() > deadline:
                break
            random.shuffle(targets)
            for i, has in targets[:fanout]:
                # re-send pacing: the same partial to the same stale
                # target is only repeated after a retry window (loss
                # recovery) — its progress flood needs at least an RTT
                # to reflect the last send, and blind per-tick resends
                # of megabyte payloads convoy every other message on
                # the link (see _diffuse_initial)
                now = loop.time()
                key = frozenset(has)
                prev = self._gossip_sent.get(i)
                if (prev is not None and prev[0] == key
                        and now - prev[1] < max(self.gossip_period_s * 4, 0.5)):
                    continue
                partial = self.session.get_partial_aggregation(has)
                if partial is None:
                    continue
                self._gossip_sent[i] = (key, now)
                params, contribs, weight = partial
                if i in self.peers:
                    await self._send_params(
                        self.peers[i], params, contribs, weight
                    )
                else:
                    # no direct link: hand the partial to proxies to
                    # relay (node.py:492-515) — one Message for all
                    await self._send_params(proxies, params, contribs,
                                            weight)
            # convergence exit (node.py:761-777, GOSSIP_EXIT_ON_X_EQUAL_
            # ROUNDS): the reference's gossip tick is 1 Hz, so "20
            # equal rounds" means ~20 quiet SECONDS — measure quiet
            # time by wall clock so fast tick rates don't turn the knob
            # into a hair trigger. On exit, stop SENDING only: the
            # reference exits just its gossip loop; aggregation still
            # completes by coverage or timeout (aggregator.py:46-76).
            status = (
                self.session.covered,
                tuple((i, tuple(sorted(has))) for i, has in sorted(targets)),
            )
            now = loop.time()
            if status != last_status:
                last_status, last_change_t = status, now
            if (self.protocol.gossip_exit_on_equal_rounds > 0
                    and now - last_change_t
                    >= self.protocol.gossip_exit_on_equal_rounds):
                while not self.session.check_and_run():
                    await asyncio.sleep(self.gossip_period_s)
                break
            await asyncio.sleep(self.gossip_period_s)
        # aggregation finished; if a full aggregate exists, also offer it
        # to trainer/idle peers waiting for one (CFL/SDFL broadcast)
        if self.session.result is not None and (
            role == "server"
            or (leader_at_start == self.idx and role == "aggregator")
        ):
            params, contribs = self.session.result
            await self._send_params(
                list(self.peers.values()),
                params, contribs or tuple(sorted(train_set)), 1,
                aggregated=True,
            )

    async def _wait_done(self) -> None:
        tw0 = time.monotonic()
        with self._tracer.span("node.wait", lane=self._lane,
                               args={"round": self.round,
                                     "kind": "adopt"}):
            try:
                deadline = (asyncio.get_event_loop().time()
                            + self.session.timeout_s)
                while not self.session.done.is_set():
                    if asyncio.get_event_loop().time() > deadline:
                        # keep local params (timeout, nothing arrived)
                        break
                    await asyncio.sleep(self.gossip_period_s)
            finally:
                self._cp_wait_s += time.monotonic() - tw0

    async def _wait_neighbors_ready(self) -> None:
        """Round barrier: wait until every alive node we've heard from
        reports this round (MODELS_READY gating, node.py:713; floods,
        so multi-hop members count too), bounded by the timeout.

        In async mode the barrier relaxes to the SAME quorum the
        session closes on: waiting for every straggler here would
        re-serialize the rounds the buffered aggregation just
        de-serialized — the whole async speedup would die at the
        barrier. Stragglers left behind catch up via the stale-params
        fold (see _on_params)."""
        tw0 = time.monotonic()
        with self._tracer.span("node.wait", lane=self._lane,
                               args={"round": self.round,
                                     "kind": "barrier"}):
            try:
                deadline = (asyncio.get_event_loop().time()
                            + self.session.timeout_s)
                frac = self.session.min_received
                while asyncio.get_event_loop().time() < deadline:
                    alive = set(self.membership.get_nodes())
                    known = set(self.peers) | set(self.progress)
                    others = [i for i in alive & known if i != self.idx]
                    behind = [
                        i for i in others
                        if self._progress(i).ready_round < self.round
                    ]
                    if not behind:
                        return
                    if self.session.async_mode and others:
                        need = max(1, math.ceil(frac * len(others)))
                        if len(others) - len(behind) >= need:
                            return
                    await asyncio.sleep(self.gossip_period_s)
            finally:
                self._cp_wait_s += time.monotonic() - tw0
