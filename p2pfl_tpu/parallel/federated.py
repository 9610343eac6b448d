"""The federated round as one SPMD program.

Reference semantics being reproduced (fedstellar/node.py round state
machine, SURVEY.md §3.3-3.4), re-expressed as fixed-shape device math:

- every node trains local epochs      → vmapped ``lax.scan`` training
- weights flow along topology edges   → masked collective (an einsum
  over the gathered node axis; XLA lowers the gather to all-gather
  over ICI when the node axis is sharded)
- each aggregator fuses what arrived  → per-row weighted FedAvg (or a
  robust aggregator vmapped over rows)
- trainers/idle adopt an aggregate    → ``adopt`` index gather
- dead nodes (heartbeat eviction / fault injection) → ``alive`` mask:
  they neither contribute weight nor update their own params.

Per-round *data* (who aggregates whom ``M``, whose aggregate each node
adopts ``adopt``, who is alive) are device arrays, not compile-time
constants — so DFL, CFL, SDFL leadership rotation, and mid-run faults
all reuse ONE compiled program.

The three federation schemes map as (node.py:427-524 role branches):
- DFL:  M = adjacency + self-loops; adopt = identity.
- CFL:  M[server] = everyone; adopt = server for all nodes.
- SDFL: like CFL with the current leader; leader rotates on the host
        (node.py:649-686 TRANSFER_LEADERSHIP analog).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from p2pfl_tpu.core.aggregators import Aggregator, FedAvg
from p2pfl_tpu.learning.learner import StepFns, TrainState
from p2pfl_tpu.topology.topology import Topology

Params = Any


class FederatedState(struct.PyTreeNode):
    """Whole-federation state: every leaf has a leading ``[n]`` axis.

    ``stale`` is the double buffer for ``exchange_overlap="staged"``:
    ``(prev post-fit params stack, prev contribution weights [n])`` —
    what round r ships to neighbors while round r's fit is still
    running. ``None`` (the default) everywhere the mode is off, so
    existing constructors, specs and tests are untouched."""

    states: TrainState  # stacked per-node TrainState
    alive: jax.Array  # [n] bool
    round: jax.Array  # scalar int32
    stale: Any = None  # (params stack, weights [n]) | None


@dataclasses.dataclass(frozen=True)
class RoundPlan:
    """Host-computed per-round schedule, fed to the jitted round fn.

    ``mix``    [n,n] float32 — row i: relative weight of node j's model
               in i's aggregate (0 = no edge). Sample-count and alive
               weighting are folded in by the round fn.
    ``adopt``  [n] int32 — node i installs the aggregate computed at
               row ``adopt[i]`` (identity for DFL; leader for CFL/SDFL).
    ``trains`` [n] bool — which nodes run local SGD this round
               (trainer/aggregator/server yes; proxy/idle no —
               node.py:492-524).
    """

    mix: np.ndarray
    adopt: np.ndarray
    trains: np.ndarray


def make_round_plan(
    topology: Topology,
    roles: list[str],
    federation: str = "DFL",
    leader: int = 0,
) -> RoundPlan:
    n = topology.n
    trains = np.array([r in ("trainer", "aggregator", "server") for r in roles])
    if federation == "DFL":
        mix = topology.adjacency.astype(np.float32) + np.eye(n, dtype=np.float32)
        adopt = np.arange(n, dtype=np.int32)
    elif federation in ("CFL", "SDFL"):
        mix = np.zeros((n, n), np.float32)
        mix[leader] = 1.0  # leader aggregates everyone (incl. itself)
        adopt = np.full((n,), leader, np.int32)
    else:
        raise ValueError(f"unknown federation {federation!r}")
    return RoundPlan(mix=mix, adopt=adopt.astype(np.int32), trains=trains)


def make_mixing_matrix(topology: Topology, scheme: str = "uniform") -> np.ndarray:
    """Expose Topology.mixing_matrix at this layer (decentralized-
    averaging weights; ``W^k`` powers emulate k gossip ticks/round)."""
    return topology.mixing_matrix(scheme).astype(np.float32)


def staleness_scale(staleness, beta: float) -> np.ndarray:
    """Staleness discount ``1 / (1 + s)^beta`` (round 11, elastic
    federation) — THE formula for folding late updates into an
    aggregate, shared verbatim by both planes so their weighting is
    bit-comparable: the socket plane applies it per-entry in
    ``AggregationSession._aggregate``, the SPMD plane as a column scale
    on the mixing matrix (``Scenario._plan_args``), both on the host in
    float32. ``staleness`` is rounds-behind (0 = fresh); negative
    values clamp to fresh; ``beta=0`` is the identity."""
    s = np.maximum(np.asarray(staleness, np.float32), 0.0)
    if beta == 0.0:
        return np.ones_like(s)
    return (1.0 / np.power(1.0 + s, np.float32(beta))).astype(np.float32)


def _tree_sel(cond: jax.Array, a, b):
    """Per-node select: cond [n] broadcast over each stacked leaf."""

    def leaf(x, y):
        c = cond.reshape((cond.shape[0],) + (1,) * (x.ndim - 1))
        return jnp.where(c, x, y)

    return jax.tree.map(leaf, a, b)


def _train_and_select(fns: StepFns, states: TrainState, alive, trains,
                      x, y, smask, epochs: int):
    """Local epochs on every node, keeping updates only where
    ``trains & alive`` (proxy/idle/dead nodes stay frozen —
    node.py:492-524). Shared by the dense and sparse round builders so
    training-selection semantics can't drift between them.

    The selection rides into the SGD step as a per-node update gate
    (learner.train_epochs ``gate``) rather than a post-hoc full-tree
    ``where`` — gated-off params are bit-exact and the round saves two
    whole-model memory passes (~12 ms at the 64-node north star). Only
    the small rng/step leaves still need explicit selection."""
    sel = jnp.logical_and(trains, alive)
    new_states, train_metrics = jax.vmap(
        fns.train_epochs, in_axes=(0, 0, 0, 0, None, 0)
    )(states, x, y, smask, epochs, sel.astype(jnp.float32))
    states = TrainState(
        params=new_states.params,
        opt_state=new_states.opt_state,
        rng=jnp.where(sel[:, None], new_states.rng, states.rng),
        step=jnp.where(sel, new_states.step, states.step),
    )
    return states, train_metrics


def init_federation(
    fns: StepFns, sample_x: jax.Array, n_nodes: int, seed: int = 0,
    same_init: bool = True,
) -> FederatedState:
    """Stacked init. ``same_init=True`` reproduces the reference's
    initial-model diffusion (node.py:299: every node starts from the
    initializer's weights) without the gossip: init once, broadcast."""
    # the pallas_gemm auto-select gate measures candidate kernels at
    # the VMAPPED shape — tell it the federation width before any
    # model application traces (docs/perf.md §6.4)
    from p2pfl_tpu.ops import pallas_gemm

    pallas_gemm.set_nodes_hint(n_nodes)
    rngs = (
        jnp.stack([jax.random.PRNGKey(seed)] * n_nodes)
        if same_init
        else jax.random.split(jax.random.PRNGKey(seed), n_nodes)
    )
    states = jax.vmap(fns.init, in_axes=(0, None))(rngs, sample_x)
    if same_init:
        # distinct per-node training rngs even with identical params
        states = states.replace(
            rng=jax.vmap(jax.random.fold_in, in_axes=(0, 0))(
                states.rng, jnp.arange(n_nodes)
            )
        )
    return FederatedState(
        states=states,
        alive=jnp.ones((n_nodes,), bool),
        round=jnp.int32(0),
    )


def reseed_params(fed: FederatedState, fns: StepFns,
                  params: Params) -> FederatedState:
    """Restart a federation from ONE param tree: every node adopts
    ``params`` with FRESH optimizer state (``fns.tx.init`` per node),
    keeping rng/step/alive/round. The pretrain -> fine-tune handoff of
    the lora bench phase: both A/B arms resume from the identical
    full-weight (or adapter) snapshot, so their accuracies differ only
    by what federation ships, not by where training started."""
    n = fed.alive.shape[0]
    stack = jax.tree.map(
        lambda x: jnp.broadcast_to(
            jnp.asarray(x), (n,) + jnp.shape(jnp.asarray(x))
        ).copy(),
        params,
    )
    states = TrainState(
        params=stack,
        opt_state=jax.vmap(fns.tx.init)(stack),
        rng=fed.states.rng,
        step=fed.states.step,
    )
    return fed.replace(states=states)


def with_staged_buffer(fed: FederatedState) -> FederatedState:
    """Seed the staged-exchange double buffer: the CURRENT params at
    ZERO contribution weight. The first staged round then mixes nothing
    from neighbors (denominator = own fresh weight only) and reduces to
    pure local training — the well-defined cold start of one-round-
    stale gossip (tests pin this)."""
    # copied, not aliased: the round fn donates its input state, and a
    # buffer appearing twice in the donated tree is an XLA error
    return fed.replace(
        stale=(
            jax.tree.map(jnp.copy, fed.states.params),
            jnp.zeros((fed.alive.shape[0],), jnp.float32),
        )
    )


def build_round_fn(
    fns: StepFns,
    aggregator: Aggregator | None = None,
    epochs: int = 1,
    exchange_dtype: Any | None = None,
    shared_aggregate: bool = False,
    identity_adopt: bool = False,
    attack=None,
    malicious: np.ndarray | None = None,
    update_stats: bool = False,
    exchange_overlap: str = "off",
    dp=None,
    dp_mask: np.ndarray | None = None,
) -> Callable:
    """Build the jittable ``round_fn(fed, x, y, mask, n_samples, plan
    arrays) -> (fed, metrics)``.

    FedAvg gets the fast path: per-leaf ``einsum('ij,j...->i...')`` —
    one MXU-friendly contraction per leaf, with the row-normalized
    weight matrix folding topology × alive × sample counts. Robust
    aggregators (Krum/median/trimmed mean) are vmapped per row over the
    gathered stack.

    ``exchange_dtype`` (e.g. ``jnp.bfloat16``) down-casts the model
    stack entering the FedAvg contraction — halving the exchange's HBM
    (and, sharded, ICI) bytes; accumulation stays f32 via
    ``preferred_element_type``. The reference moves f32 pickles
    (lightninglearner.py:73-77); bf16-rounding gossip inputs costs
    ~0.4% relative weight error, re-trained away within the next local
    epoch — the bench's rounds-to-80% guards the claim empirically.
    ``None`` keeps the exchange in full precision (the parity-test
    default).

    ``shared_aggregate=True`` computes ONE robust aggregate from the
    union of the mixing rows instead of one per row — for plans whose
    aggregating rows are all identical (fully-connected DFL, or
    CFL/SDFL where only the leader's row is nonzero). The vmapped
    per-row path is O(n) redundant aggregations and O(n x |params|)
    transient memory for those plans; on big models (ViT + Krum at 32
    nodes) that redundancy is the difference between fitting and
    faulting. Semantically identical where the contract holds; rows
    with no incoming weight still keep their own params.

    ``identity_adopt=True`` is the caller's PROMISE that every plan fed
    to this round fn has ``adopt == arange(n)`` (always true for DFL,
    make_round_plan): the ``agg[adopt]`` gather is a full extra
    read+write pass over the model stack that XLA cannot elide for a
    runtime index array, so the promise buys one whole-stack memory
    pass per round (~4 ms at the 64-node north star). CFL/SDFL route
    through a leader and must keep the default.

    ``attack`` + ``malicious`` inject adversarial nodes: after local
    training and BEFORE the weight exchange, the rows of the params
    stack selected by the STATIC host mask ``malicious`` are replaced
    by ``adversary.poison_update`` of themselves — the same transform
    the socket node applies to its outgoing params, keyed by
    (attack.seed, node index, fed.round) so the two paths poison
    bit-identically. The mask is a compile-time constant (changing the
    malicious cohort recompiles — it is scenario config, not round
    data). ``update_stats=True`` additionally returns per-node trust
    observations (``metrics["trust_obs"]``, adversary.cohort_scores of
    each node's delta vs the round-start params) for the host-side
    ReputationMonitor. The sparse round builder below supports
    neither: it never materializes the full params stack, so there is
    no pre-exchange hook — robustness runs use this dense builder.

    ``dp`` (a ``privacy.dp.DPSpec``) + ``dp_mask`` privatize outgoing
    updates AFTER any attack injection and before the exchange: the
    rows selected by the STATIC host mask ``dp_mask`` are replaced by
    ``privacy.dp.privatize_stacked`` of themselves vs the round-start
    params — clip to L2 ``clip_norm``, add Gaussian noise of std
    ``clip_norm * noise_multiplier``, keyed by (dp.seed, node index,
    fed.round) exactly like the socket node privatizing its learner
    post-fit, so the two planes are bit-identical. Ordering matters:
    poison-then-privatize means DP clipping also bounds what a
    malicious row can inject, which is the deployment semantics.

    ``exchange_overlap="staged"`` double-buffers the exchange: the
    off-diagonal mix terms read the PREVIOUS round's post-fit params
    (``fed.stale``, seeded by :func:`with_staged_buffer`) at their then
    contribution weights, while the self term stays this round's fresh
    fit — one-round-stale gossip. The shipped buffer is final at round
    start, so the exchange has no data dependence on the current fit
    and the scheduler can hide it under the local epochs. Requires the
    FedAvg fast path and composes with neither attack injection nor
    trust scoring (both are defined on what a node ships THIS round).
    """
    aggregator = aggregator or FedAvg()
    fedavg_fast = type(aggregator) is FedAvg
    attack_active = (
        attack is not None
        and malicious is not None
        and bool(np.any(malicious))
        and getattr(attack, "poisons_updates", False)
    )
    dp_active = (
        dp is not None
        and dp_mask is not None
        and bool(np.any(dp_mask))
    )
    if exchange_overlap not in ("off", "staged"):
        raise ValueError(
            f"unknown exchange_overlap {exchange_overlap!r}; "
            "have ('off', 'staged')"
        )
    staged = exchange_overlap == "staged"
    if staged and not fedavg_fast:
        raise ValueError(
            "exchange_overlap='staged' requires the FedAvg fast path — "
            "robust aggregators score THIS round's updates"
        )
    if staged and (attack_active or update_stats):
        raise ValueError(
            "exchange_overlap='staged' composes with neither attack "
            "injection nor trust scoring: both are defined on the "
            "fresh update a node ships this round"
        )

    def round_fn(fed: FederatedState, x, y, smask, n_samples, mix, adopt, trains):
        alive = fed.alive

        # ---- local training (every node; results masked in afterward)
        ref_params = fed.states.params  # round-start params (delta ref)
        states, train_metrics = _train_and_select(
            fns, fed.states, alive, trains, x, y, smask, epochs
        )

        # ---- adversarial injection: malicious rows poison their
        # outgoing update before it enters ANY mix (incl. their own row,
        # matching the socket node poisoning its learner post-fit)
        if attack_active:
            from p2pfl_tpu.adversary.attacks import poison_stacked

            states = states.replace(
                params=poison_stacked(
                    states.params, ref_params, malicious, fed.round, attack
                )
            )

        # ---- DP-FedAvg: masked rows privatize their outgoing update
        # (clip + noise vs round-start params) before it enters ANY mix
        # — after poisoning, so the clip also bounds injected updates,
        # and before the staged buffer capture, so stale hops ship
        # privatized params too (matching the socket node privatizing
        # its learner post-fit)
        if dp_active:
            from p2pfl_tpu.privacy.dp import privatize_stacked

            states = states.replace(
                params=privatize_stacked(
                    states.params, ref_params, dp_mask, fed.round, dp
                )
            )

        # ---- weight exchange + aggregation
        # contribution gate: only alive *training* nodes inject models
        # (proxy/idle forward/adopt but never contribute — node.py:492-524)
        contrib = jnp.logical_and(trains, alive)
        w_fresh = n_samples.astype(jnp.float32) * contrib
        new_stale = fed.stale
        if staged:
            # double buffer: off-diagonal terms weigh the PREVIOUS
            # round's post-fit params at their then weights; only the
            # self term reads this round's fresh fit. A zero stale
            # weight (with_staged_buffer's seed, or a node dead last
            # round) contributes nothing — round 0 is pure local SGD.
            stale_params, stale_w = fed.stale
            eye = jnp.eye(alive.shape[0], dtype=jnp.float32)
            w = mix * ((1.0 - eye) * stale_w[None, :]
                       + eye * w_fresh[None, :])
            new_stale = (states.params, w_fresh)
        else:
            w = mix * w_fresh[None, :]
        if fedavg_fast:
            denom = jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-9)
            wn = w / denom
            # identity-adopt fast path: keep is known BEFORE mixing, so
            # the keep-select fuses into the mix epilogue — one output
            # pass instead of a separate whole-stack where (~2 ms at
            # the 64-node north star)
            keep_early = (
                jnp.logical_and(alive, jnp.sum(w, axis=1) > 0)
                if identity_adopt else None
            )
            mix_dt = exchange_dtype or jnp.float32

            def _keep(mixed, p):
                if keep_early is None:
                    return mixed
                c = keep_early.reshape(
                    (keep_early.shape[0],) + (1,) * (p.ndim - 1))
                return jnp.where(c, mixed, p)

            if staged:
                wn_off = wn * (1.0 - eye)
                wn_diag = jnp.diagonal(wn)

                @jax.named_scope("exchange.mix")
                def leaf_mix_staged(p, ps):
                    flat_s = ps.reshape(ps.shape[0], -1).astype(mix_dt)
                    flat_f = p.reshape(p.shape[0], -1).astype(mix_dt)
                    out = jax.lax.dot(  # stale hops: no fit dependence
                        wn_off.astype(mix_dt), flat_s,
                        preferred_element_type=jnp.float32,
                    )
                    out = out + wn_diag[:, None] * flat_f.astype(
                        jnp.float32)
                    return _keep(out.reshape(p.shape).astype(p.dtype), p)

                agg = jax.tree.map(leaf_mix_staged, states.params,
                                   stale_params)
            else:
                @jax.named_scope("exchange.mix")
                def leaf_mix(p):
                    flat = p.reshape(p.shape[0], -1).astype(mix_dt)
                    out = jax.lax.dot(  # [n,n]@[n,d] — MXU, f32 accum
                        wn.astype(mix_dt), flat,
                        preferred_element_type=jnp.float32,
                    )
                    return _keep(out.reshape(p.shape).astype(p.dtype), p)

                agg = jax.tree.map(leaf_mix, states.params)
        else:
            # wire-precision semantics for robust aggregators too: the
            # stack entering aggregation is what crosses the "wire"
            stack_ex = (
                states.params if exchange_dtype is None
                else jax.tree.map(lambda p: p.astype(exchange_dtype),
                                  states.params)
            )
            if shared_aggregate:
                # uniform-row contract: one aggregate serves everyone
                w_union = jnp.max(w, axis=0)
                out = aggregator.aggregate(
                    stack_ex, n_samples.astype(jnp.float32),
                    mask=w_union > 0,
                )
                agg = jax.tree.map(
                    lambda o, p: jnp.broadcast_to(
                        o.astype(p.dtype)[None], p.shape
                    ),
                    out, states.params,
                )
            else:
                def per_row(row_w):
                    out = aggregator.aggregate(
                        stack_ex, n_samples.astype(jnp.float32),
                        mask=row_w > 0,
                    )
                    return jax.tree.map(
                        lambda o, p: o.astype(p.dtype), out, states.params
                    )

                agg = jax.vmap(per_row)(w)

        # nodes with an all-zero row (nothing arrived before "timeout",
        # aggregator.py:53-76) keep their own params
        got_any = jnp.sum(w, axis=1) > 0
        if identity_adopt and fedavg_fast:
            params = agg  # keep-select already fused into leaf_mix
        else:
            if identity_adopt:
                pass  # adopt == arange(n) by contract: gather elided
            elif not (shared_aggregate and not fedavg_fast):
                # shared aggregates are already identical across rows,
                # so the adopt gather would only copy
                agg = jax.tree.map(lambda a: a[adopt], agg)
            keep = jnp.logical_and(
                alive, got_any if identity_adopt else got_any[adopt])
            params = _tree_sel(keep, agg, states.params)

        fed = FederatedState(
            states=states.replace(params=params),
            alive=alive,
            round=fed.round + 1,
            stale=new_stale,
        )
        metrics = {
            "train_loss": train_metrics["loss"],  # [n]
            "alive": alive,
        }
        if "counted" in train_metrics:
            # what the model itself counts in a step (an expert layer's
            # dropped pairs and load): [n, epochs, steps, ...], fetched
            # with the losses
            metrics["counted"] = train_metrics["counted"]
        if update_stats:
            from p2pfl_tpu.adversary.reputation import spmd_trust_obs

            # scored on the post-attack params — what each node "sent"
            metrics["trust_obs"] = spmd_trust_obs(
                states.params, ref_params, contrib
            )
        return fed, metrics

    return round_fn


def build_round_fn_sparse(
    fns: StepFns,
    topology: Topology,
    mesh,
    epochs: int = 1,
    exchange_dtype: Any | None = None,
    exchange_overlap: str = "off",
) -> Callable:
    """The sparse-topology round: O(degree) ``ppermute`` hops over ICI
    instead of the dense all-gather einsum.

    One federated node per mesh slot (requires ``topology.n ==
    mesh.size``), DFL only (``adopt`` must be the identity — CFL/SDFL
    route everything through one leader, where a gather is the natural
    collective, so they stay on :func:`build_round_fn`). The per-round
    plan arrays keep the SAME signature as the dense round fn, so the
    two programs are drop-in interchangeable and parity-testable
    (exact parity with ``exchange_dtype=None``; a wire dtype rounds
    wire payloads identically on both paths but the dense einsum
    additionally rounds the [n,n] weight matrix — see
    ``neighbor_exchange``).

    On a ring (the reference's watts_strogatz(n,2,0) topology,
    topologymanager.py:213-228) this moves 2 × |params| per node per
    round instead of n × |params| — the reference's per-neighbor TCP
    sends (node.py:726-809) become exactly #offsets ppermutes.
    """
    from jax.sharding import PartitionSpec

    from p2pfl_tpu.parallel.mesh import NODES_AXIS
    from p2pfl_tpu.parallel.transport import neighbor_exchange

    if topology.n != mesh.size:
        raise ValueError(
            f"sparse round needs one node per mesh slot: "
            f"{topology.n} nodes vs {mesh.size} devices"
        )
    if exchange_overlap not in ("off", "staged"):
        raise ValueError(
            f"unknown exchange_overlap {exchange_overlap!r}; "
            "have ('off', 'staged')"
        )
    staged = exchange_overlap == "staged"

    Pn = PartitionSpec(NODES_AXIS)
    Pr = PartitionSpec()
    fed_spec = FederatedState(
        states=Pn, alive=Pn, round=Pr,
        stale=(Pn, Pn) if staged else None,
    )

    def round_fn(fed: FederatedState, x, y, smask, n_samples, mix, adopt, trains):
        # every block arrives with a leading node axis of size 1
        del adopt  # identity by contract (DFL)
        alive = fed.alive

        states, train_metrics = _train_and_select(
            fns, fed.states, alive, trains, x, y, smask, epochs
        )

        contrib = jnp.logical_and(trains, alive)
        my_w = (n_samples.astype(jnp.float32) * contrib)[0]
        local = jax.tree.map(lambda p: p[0], states.params)
        if staged:
            # ship the PREVIOUS round's post-fit buffer on the hops —
            # ready at round start, so the ppermutes need not wait for
            # this round's fit (see neighbor_exchange)
            stale_p, stale_w = fed.stale
            agg, total = neighbor_exchange(
                local, my_w, mix[0], topology, NODES_AXIS,
                exchange_dtype=exchange_dtype,
                stale_params=jax.tree.map(lambda p: p[0], stale_p),
                stale_weight=stale_w[0],
            )
            new_stale = (states.params, my_w[None])
        else:
            agg, total = neighbor_exchange(
                local, my_w, mix[0], topology, NODES_AXIS,
                exchange_dtype=exchange_dtype,
            )
            new_stale = fed.stale
        keep = jnp.logical_and(alive[0], total > 0)
        params = jax.tree.map(
            lambda a, p: jnp.where(keep, a.astype(p.dtype), p[0])[None],
            agg, states.params,
        )
        fed = FederatedState(
            states=states.replace(params=params),
            alive=alive,
            round=fed.round + 1,
            stale=new_stale,
        )
        metrics = {"train_loss": train_metrics["loss"], "alive": alive}
        return fed, metrics

    # check_vma off: the round mixes collectives the replication
    # checker rejects spuriously
    return jax.shard_map(
        round_fn,
        mesh=mesh,
        in_specs=(fed_spec, Pn, Pn, Pn, Pn, Pn, Pn, Pn),
        out_specs=(fed_spec, {"train_loss": Pn, "alive": Pn}),
        check_vma=False,
    )


def cross_device_wn(c_sizes, c_alive):
    """Globally normalized FedAvg weights over ALL ``C x n_slots``
    sampled clients, plus the empty-round flag. Shared by the monolithic
    scan, both sharded arms, and the streamed driver so the weighting —
    and therefore the aggregate — cannot drift between them."""
    w = c_sizes.astype(jnp.float32) * c_alive  # [C, n_slots]
    denom = jnp.maximum(jnp.sum(w), 1e-9)
    return w / denom, jnp.sum(w) > 0


def _cross_device_plan(params0, fused_accumulate: bool):
    """Per-leaf route for the fit-epilogue accumulate: ``True`` sends
    the leaf through the fused ``pallas_gemm.fedavg_accum`` stream,
    ``False`` keeps the exact-XLA gemm-row contraction. The key is the
    learner's ``_fused_sgd_step`` key verbatim (same per-slot 2-D
    shape, same ``sgd_accum`` kind, same nodes hint), so one measured
    decision covers both call sites. Off-TPU the gate forces xla
    (unless the ``P2PFL_PALLAS_GEMM`` env knob forces pallas — the
    interpret-mode parity-test route), so tier-1 numerics are
    unchanged. Plan is all-False for the unfused layout: the reference
    arm stays the reference."""
    from p2pfl_tpu.ops import pallas_gemm

    def leaf_plan(p):
        if not fused_accumulate or p.ndim < 2:
            return False  # per-slot scalar: nothing to stream
        leaf = p.shape[1:]
        rows = int(np.prod(leaf[:-1], dtype=np.int64)) if len(leaf) > 1 else 1
        shape2 = (rows, int(leaf[-1]))
        return pallas_gemm.choose(
            "sgd_accum", (shape2, shape2), p.dtype) == "pallas"

    return jax.tree.map(leaf_plan, params0)


def _cross_device_acc0(params0, fused_accumulate: bool, plan):
    """Zero accumulators, one per leaf, in the layout the route wants:
    pallas leaves carry a per-slot 2-D stream ``[n_slots, rows, cols]``
    (summed over slots once at round end), fused-gemm leaves ONE flat
    f32 row ``[1, d]``, unfused leaves the full ``[n_slots, d]``."""

    def leaf0(p, use_pallas):
        if use_pallas:
            leaf = p.shape[1:]
            rows = (int(np.prod(leaf[:-1], dtype=np.int64))
                    if len(leaf) > 1 else 1)
            return jnp.zeros((p.shape[0], rows, int(leaf[-1])),
                             jnp.float32)
        rows = 1 if fused_accumulate else p.shape[0]
        return jnp.zeros(
            (rows, int(np.prod(p.shape[1:], dtype=np.int64))),
            jnp.float32)

    return jax.tree.map(leaf0, params0, plan)


def _cross_device_body(fns: StepFns, epochs: int, mix_dt,
                       fused_accumulate: bool, params0, n_slots: int,
                       plan) -> Callable:
    """One cohort step of the cross-device scan: train the cohort from
    the round-start ``params0``, fold its weighted contribution into
    the accumulator. THE body — the monolithic scan, both sharded
    arms, and the streamed driver all run exactly this function, which
    is what makes their per-step values bit-identical by construction
    rather than by test luck."""
    trains = jnp.ones((n_slots,), bool)
    from p2pfl_tpu.ops.pallas_gemm import fedavg_accum

    def body(carry, inputs):
        opt_state, rng, step, acc = carry
        x_t, y_t, m_t, alive_t, wn_t = inputs
        states_t = TrainState(
            params=params0, opt_state=opt_state, rng=rng, step=step
        )
        states_t, tm = _train_and_select(
            fns, states_t, alive_t, trains, x_t, y_t, m_t, epochs
        )

        # hoisted out of the leaf loop: one weight operand per step,
        # not one broadcast+cast per leaf
        w_t = jnp.broadcast_to(
            wn_t[None, :], (n_slots, n_slots)
        ).astype(mix_dt)

        def leaf_acc(a, p, use_pallas):
            if use_pallas:
                # per-slot fused stream: acc[s] += wn[s] * p[s] in one
                # pass through the sgd_accum kernel (null optimizer
                # half); the slot axis collapses once at round end
                return jax.vmap(
                    lambda ai, pi, wi: fedavg_accum(
                        pi.reshape(ai.shape).astype(mix_dt), ai, wi)
                )(a, p, wn_t)
            flat = p.reshape(p.shape[0], -1).astype(mix_dt)
            partial = jax.lax.dot(
                w_t, flat,
                preferred_element_type=jnp.float32,
            )
            if fused_accumulate:
                # the barrier pins the gemm before the row slice —
                # without it XLA may turn slice-of-dot into a gemv
                # whose reduction order is 1 ulp off the gemm row,
                # breaking the tolerance-0 parity gates
                partial = jax.lax.optimization_barrier(partial)[0:1]
            return a + partial

        acc = jax.tree.map(leaf_acc, acc, states_t.params, plan)
        carry = (states_t.opt_state, states_t.rng, states_t.step, acc)
        return carry, tm["loss"]

    return body


def _cross_device_leaf_out(keep, n_slots: int, fused_accumulate: bool):
    """Round-end epilogue per leaf: collapse the accumulator back to
    the ``[n_slots, ...]`` param stack, keeping the old params where
    the round was empty or the slot dead."""

    def leaf_out(a, p, use_pallas):
        if use_pallas:
            # per-slot partials [n_slots, rows, cols]: the slot sum IS
            # the sum over all C x n_slots clients (weights were
            # globally normalized up front)
            row = a.sum(axis=0).reshape((1,) + p.shape[1:]).astype(p.dtype)
            out = jnp.broadcast_to(row, p.shape)
        elif fused_accumulate:
            row = a.reshape((1,) + p.shape[1:]).astype(p.dtype)
            out = jnp.broadcast_to(row, p.shape)
        else:
            out = a.reshape(p.shape).astype(p.dtype)
        c = keep.reshape((n_slots,) + (1,) * (p.ndim - 1))
        return jnp.where(c, out, p)

    return leaf_out


def _ordered_chunk_sum(stacked, n_chunks: int):
    """Sum a ``[D, ...]`` stack of per-chunk partials chunk 0 first —
    an unrolled, order-pinned add chain, identical code whether the
    stack came off the shard_map or the single-device chunk scan. This
    is the deterministic re-association of the cross-chunk psum: by
    doing the reduce OUTSIDE the mapped region in a fixed order, the
    sharded and single-device arms produce bit-identical sums instead
    of collective-implementation-defined ones."""
    total = stacked[0]
    for i in range(1, n_chunks):
        total = total + stacked[i]
    return total


def build_round_fn_cross_device(
    fns: StepFns,
    epochs: int = 1,
    exchange_dtype: Any | None = None,
    fused_accumulate: bool = True,
    cohort_shards: int = 1,
    cohort_mesh: Any | None = None,
) -> Callable:
    """The cross-device round (round 13): one compiled program runs a
    ``lax.scan`` over stacked cohorts, so an ``n_slots``-wide mesh
    simulates ``cohort_size x n_slots`` sampled participants per round.

    Signature: ``round_fn(fed, cx, cy, cmask, c_sizes, c_alive) ->
    (fed, metrics)`` with cohort-stacked data ``cx [C, n_slots, S,
    ...]``, ``cy/cmask [C, n_slots, S]``, ``c_sizes/c_alive [C,
    n_slots]`` (``C = cohort_size``). ``fed`` is the GLOBAL model
    broadcast across slots (init_federation same_init) — clients are
    transient, so every scan step trains its cohort from the
    round-start params, and the example-weighted FedAvg sums over all
    ``C x n_slots`` sampled clients at once against the globally
    normalized weights ``wn = w / max(sum(w), 1e-9)``.

    Two accumulation layouts produce that sum (round 17):

    * ``fused_accumulate=True`` (default): every slot of the aggregate
      is identical by construction, so the scan carries ONE flat f32
      row per leaf (``[1, d]``) instead of the full ``[n_slots, d]``
      accumulator — per step the cohort's weighted partial is folded
      into the fit epilogue as ``acc += dot(W_t, flat_t)[0:1]``. The
      slice sits behind an ``optimization_barrier`` so XLA cannot
      rewrite slice-of-dot into a gemv with a different reduction
      order: the dot INSTRUCTION is byte-identical to the unfused
      reference's, which is what makes tolerance-0 parity hold at
      every shape rather than by backend-kernel coincidence (a
      ``[1, n] @ [n, d]`` row-dot is 1 ulp off the gemm row at some
      CPU shapes). The carry (and its zeros init) is ``n_slots`` times
      smaller, the read-modify-write of the accumulator per scan step
      drops from ``2 * n_slots * d`` to ``2 * d`` floats, and the
      round-end broadcast back to ``[n_slots, ...]`` happens once in
      the keep/where epilogue.
    * ``fused_accumulate=False``: the round-13 reference — per step
      ``dot(W_t, flat_t)`` where every row of ``W_t`` is the cohort's
      weight slice, accumulated at full ``[n_slots, d]``. Kept as the
      parity anchor; the tolerance-0 gate in tests/test_cross_device.py
      pins fused == unfused (params AND opt_state).

    Both layouts run the SAME ``[n_slots, n_slots] @ [n_slots, d]``
    dot with f32 accumulation — deliberately the dot shape of the
    dense round's ``leaf_mix``, so at ``cohort_size == 1`` with every
    client sampled the cross-device round stays bit-identical to the
    dense stacked round (the round-13 parity gate) under either
    layout.

    A sampled-but-dead client (``c_alive`` false — membership clock
    composition) trains nothing (the ``_train_and_select`` gate) and
    carries zero aggregation weight; its slot's data that step is inert
    padding. Optimizer state / rng / step thread through the scan as
    slot-level carries (cross-device clients own no persistent state).
    ``exchange_dtype`` rounds each cohort's params entering the
    accumulation dot, mirroring the dense wire-precision knob.

    All shapes are fixed by ``(n_slots, C, shard_size)`` — resampling
    clients each round never recompiles (the crossdev_xla_recompiles
    bench key pins this, for both layouts).

    **Sharded cohort scan (round 20).** ``cohort_shards = D > 1``
    splits the C cohort steps into D contiguous chunks; each chunk
    scans from the SAME round-start carry (params0, opt_state0, rng0,
    step0, zero accumulator), so chunks are independent and can run on
    D devices at once. The chunk structure is part of the round's
    *semantics*, not a layout detail: the final opt_state/rng/step come
    from the LAST chunk, and the D per-chunk accumulator partials are
    reduced by an order-pinned unrolled add chain (chunk 0 first) —
    the cross-chunk psum deterministically re-associated OUTSIDE the
    mapped region. Both arms — ``cohort_mesh=None`` (an outer
    ``lax.scan`` over chunks on one device) and a ``cohort_shard_mesh``
    (``shard_map`` over the ``cohorts`` axis) — run the identical body
    and the identical reduce, which is what makes sharded vs
    single-device bit-for-bit (params AND opt_state, tolerance 0) at
    the same ``cohort_shards``, with zero post-warm-up recompiles on
    either arm. ``D = 1`` degenerates to exactly the monolithic scan
    above — the round-13/17 gates are untouched. Requires
    ``C % cohort_shards == 0``.

    **Fused accumulate route (round 20, closing the round-17 loose
    end).** Per leaf, the measured ``pallas_gemm.choose("sgd_accum")``
    gate may route the fit-epilogue accumulate through
    ``fedavg_accum`` — the learner's fused SGD+accumulate kernel with
    the optimizer half nulled — as a per-slot streaming
    ``acc[s] += wn[s] * p[s]`` whose slot axis collapses once at round
    end. Exact-XLA gemm fallback per leaf; CPU resolves xla so tier-1
    numerics are bit-unchanged. The pallas route re-associates the
    ``[n,n]@[n,d]`` contraction (sum over slots then steps), so its
    parity vs the gemm path is allclose, not tolerance-0 — pinned by
    tests/test_cross_device.py with the env knob forcing both ways.
    """
    mix_dt = exchange_dtype or jnp.float32
    if cohort_shards < 1:
        raise ValueError(f"cohort_shards must be >= 1, got {cohort_shards}")
    if cohort_mesh is not None and cohort_mesh.size != cohort_shards:
        raise ValueError(
            f"cohort_mesh has {cohort_mesh.size} devices but "
            f"cohort_shards={cohort_shards}")

    def round_fn(fed: FederatedState, cx, cy, cmask, c_sizes, c_alive):
        n_slots = fed.alive.shape[0]
        params0 = fed.states.params  # round-start global model

        # FedAvg weights over ALL C x n_slots sampled clients,
        # normalized once — the per-step dots then just accumulate
        wn, got_any = cross_device_wn(c_sizes, c_alive)

        plan = _cross_device_plan(params0, fused_accumulate)
        acc0 = _cross_device_acc0(params0, fused_accumulate, plan)
        carry0 = (fed.states.opt_state, fed.states.rng, fed.states.step,
                  acc0)
        body = _cross_device_body(fns, epochs, mix_dt, fused_accumulate,
                                  params0, n_slots, plan)

        n_cohorts = cx.shape[0]
        if cohort_shards == 1:
            carry, losses = jax.lax.scan(
                body, carry0, (cx, cy, cmask, c_alive, wn)
            )
            opt_state, rng, step, acc = carry
        else:
            d = cohort_shards
            if n_cohorts % d != 0:
                raise ValueError(
                    f"cohort_size {n_cohorts} not divisible by "
                    f"cohort_shards {d}")
            chunked = jax.tree.map(
                lambda a: a.reshape((d, n_cohorts // d) + a.shape[1:]),
                (cx, cy, cmask, c_alive, wn),
            )
            if cohort_mesh is None:
                # single-device arm: the chunks run sequentially from
                # the SAME chunk-local carries as the mesh arm. The
                # loop is Python-unrolled rather than an outer
                # lax.scan: nesting the chunk scan inside a while
                # loop changes how XLA fuses the training body and
                # drifts ~1 ulp from the shard_map program; unrolled,
                # each chunk compiles at top level exactly like one
                # device's shard_map shard, and the arms are
                # bit-identical (d is a small static constant)
                outs = []
                for i in range(d):
                    chunk_i = jax.tree.map(lambda a, i=i: a[i],
                                           chunked)
                    carry, losses_c = jax.lax.scan(
                        body, carry0, chunk_i)
                    outs.append((jax.tree.map(lambda t: t[None],
                                              carry),
                                 losses_c[None]))
                carries, losses_d = jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, axis=0), *outs)
            else:
                from p2pfl_tpu.parallel.mesh import COHORTS_AXIS
                from jax.sharding import PartitionSpec

                Pc = PartitionSpec(COHORTS_AXIS)
                Pr = PartitionSpec()

                # params0/carry0 are passed explicitly (replicated):
                # shard_map must not close over tracers
                def shard_body(params0_, carry0_, x_c, y_c, m_c, a_c,
                               w_c):
                    body_ = _cross_device_body(
                        fns, epochs, mix_dt, fused_accumulate,
                        params0_, n_slots, plan)
                    # local view: one chunk with a leading axis of 1
                    carry, losses_c = jax.lax.scan(
                        body_, carry0_,
                        (x_c[0], y_c[0], m_c[0], a_c[0], w_c[0]))
                    return (jax.tree.map(lambda t: t[None], carry),
                            losses_c[None])

                sharded = jax.shard_map(
                    shard_body,
                    mesh=cohort_mesh,
                    in_specs=(Pr, Pr, Pc, Pc, Pc, Pc, Pc),
                    out_specs=(Pc, Pc),
                    check_vma=False,
                )
                carries, losses_d = sharded(params0, carry0, *chunked)
            # finals from the LAST chunk; accumulator partials reduced
            # chunk 0 first — the order-pinned psum re-association
            opt_state = jax.tree.map(lambda t: t[-1], carries[0])
            rng = carries[1][-1]
            step = carries[2][-1]
            acc = jax.tree.map(lambda s: _ordered_chunk_sum(s, d),
                               carries[3])
            losses = losses_d.reshape((n_cohorts,) + losses_d.shape[2:])

        # an empty round (every sampled client dead) keeps the global
        # model — the cross-device analog of the dense got_any keep
        keep = jnp.logical_and(fed.alive, got_any)
        leaf_out = _cross_device_leaf_out(keep, n_slots,
                                          fused_accumulate)
        params = jax.tree.map(leaf_out, acc, params0, plan)
        fed = FederatedState(
            states=TrainState(
                params=params, opt_state=opt_state, rng=rng, step=step
            ),
            alive=fed.alive,
            round=fed.round + 1,
            stale=fed.stale,
        )
        metrics = {
            "train_loss": losses,  # [C, n_slots] per-cohort-step
            "alive": fed.alive,
        }
        return fed, metrics

    return round_fn


def build_cross_device_stream_fns(
    fns: StepFns,
    epochs: int = 1,
    exchange_dtype: Any | None = None,
    fused_accumulate: bool = True,
) -> tuple[Callable, Callable, Callable]:
    """The cross-device round unrolled for streamed client state
    (round 20): ``(init_carry, step, finalize)`` instead of one scan
    over pre-materialized cohorts, so the host can gather and
    ``device_put`` cohort t+1 while the device trains cohort t
    (``CrossDeviceScenario``'s double-buffered prefetch seam) — an
    N=100k..1M round materializes TWO cohorts of client data at any
    instant instead of all C.

    ``step(params0, carry, x_t, y_t, m_t, alive_t, wn_t)`` is exactly
    one ``_cross_device_body`` step — the SAME body the monolithic scan
    runs — so a streamed round is bit-identical to
    ``build_round_fn_cross_device`` at ``cohort_shards=1`` with the
    same cohort assignment. ``wn_t`` rows come from
    ``cross_device_wn`` over the full ``[C, n_slots]`` sizes/alive
    (client sizes need no client data — ``CrossDeviceData.
    client_sizes`` is host metadata), computed once at round start.
    ``finalize(fed, carry, got_any)`` runs the keep/where epilogue and
    advances the round counter. The caller jits ``step`` once
    (``donate_argnums`` the carry) and calls it C times per round —
    fixed shapes, zero recompiles after warm-up.
    """
    mix_dt = exchange_dtype or jnp.float32

    def init_carry(fed: FederatedState):
        plan = _cross_device_plan(fed.states.params, fused_accumulate)
        acc0 = _cross_device_acc0(fed.states.params, fused_accumulate,
                                  plan)
        return (fed.states.opt_state, fed.states.rng, fed.states.step,
                acc0)

    def step(params0, carry, x_t, y_t, m_t, alive_t, wn_t):
        n_slots = alive_t.shape[0]
        plan = _cross_device_plan(params0, fused_accumulate)
        body = _cross_device_body(fns, epochs, mix_dt, fused_accumulate,
                                  params0, n_slots, plan)
        return body(carry, (x_t, y_t, m_t, alive_t, wn_t))

    def finalize(fed: FederatedState, carry, got_any):
        opt_state, rng, step_, acc = carry
        n_slots = fed.alive.shape[0]
        plan = _cross_device_plan(fed.states.params, fused_accumulate)
        keep = jnp.logical_and(fed.alive, got_any)
        leaf_out = _cross_device_leaf_out(keep, n_slots,
                                          fused_accumulate)
        params = jax.tree.map(leaf_out, acc, fed.states.params, plan)
        return FederatedState(
            states=TrainState(
                params=params, opt_state=opt_state, rng=rng, step=step_
            ),
            alive=fed.alive,
            round=fed.round + 1,
            stale=fed.stale,
        )

    return init_carry, step, finalize


def build_eval_fn(fns: StepFns) -> Callable:
    """Evaluate every node's model on the (replicated) test set.

    Returns per-node metrics ``{loss: [n], accuracy: [n]}`` — the
    federated analog of the reference's per-node ``__evaluate``
    (node.py:435, Trainer.test per process).
    """

    def eval_fn(fed: FederatedState, x_test, y_test):
        mask = jnp.ones((x_test.shape[0],), bool)
        return jax.vmap(fns.evaluate, in_axes=(0, None, None, None))(
            fed.states.params, x_test, y_test, mask
        )

    return eval_fn


def round_flops(round_jit, fed: FederatedState, *args) -> float | None:
    """Counted FLOPs of one compiled federated round program.

    Thin adapter over ``obs.cost_model.program_flops`` so the round-fn
    layer and the live devprof gauge share one cost model with the
    bench (same cost_analysis read, same caveats — see cost_model's
    docstring). Lowers at avals: no device work is queued. Callers
    cache — shapes are fixed for a scenario's lifetime, so the answer
    never changes mid-run."""
    from p2pfl_tpu.obs import cost_model

    return cost_model.program_flops(
        round_jit, *cost_model.avals((fed, *args)))
