"""ICI transport: explicit collective schedules for weight exchange.

The reference moves weights with per-peer TCP threads + 2 KB fragments
(node_connection.py:146-242, communication_protocol.py:737-769). Here
the "wire" is the TPU interconnect, and a topology is a *collective
schedule*:

- dense graphs → one all-gather + masked einsum (what
  federated.build_round_fn emits through XLA's SPMD partitioner);
- ring graphs → two ``ppermute`` hops (left+right neighbor), O(degree)
  ICI traffic instead of O(n) — this module's ``neighbor_exchange``;
- arbitrary sparse graphs → a sequence of ``ppermute`` steps, one per
  distinct edge offset (a ring with chords of offset k adds one
  ppermute of shift k).

``MeshTransport`` wraps a mesh + jitted round/eval fns with the right
input shardings, so callers (federation.Scenario) never touch
jax.sharding directly.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from p2pfl_tpu.parallel.mesh import (
    NODES_AXIS,
    federation_mesh,
    replicated_sharding,
    stacked_sharding,
)
from p2pfl_tpu.topology.topology import Topology

#: the names under which jax reports the two programs a transport jits
#: (``compile_round``, ``compile_eval``): the ``__name__`` every round
#: and evaluation builder of ``parallel/federated.py`` gives its
#: function, which ``lora.frozen_argument`` keeps. What
#: ``obs.trace.trace_lower_by_function()`` files their tracing under
ROUND_PROGRAM = "round_fn"
EVAL_PROGRAM = "eval_fn"


def edge_offsets(topology: Topology) -> list[int]:
    """Distinct circulant offsets present in the adjacency matrix.

    For ring/torus-like graphs this is a short list (ring: {1, n-1});
    each offset becomes one ``ppermute`` in ``neighbor_exchange``. For
    non-circulant graphs this over-approximates (an offset is included
    if ANY node has that edge) — correctness is preserved because
    per-edge masks zero out non-edges after the permute.
    """
    a = topology.adjacency
    n = topology.n
    offs = []
    for k in range(1, n):
        if any(a[i, (i + k) % n] for i in range(n)):
            offs.append(k)
    return offs


def neighbor_exchange(
    params: Any,
    my_weight: jnp.ndarray,
    row: jnp.ndarray,
    topology: Topology,
    axis_name: str = NODES_AXIS,
    exchange_dtype: Any | None = None,
    stale_params: Any | None = None,
    stale_weight: jnp.ndarray | None = None,
) -> tuple[Any, jnp.ndarray]:
    """Weighted neighborhood average via ``ppermute`` — for use inside
    ``shard_map`` with one node per mesh slot.

    ``params``: this node's (unstacked) pytree; ``my_weight``: this
    node's contribution weight (sample count × alive × trains — zero
    means "I contribute nothing", matching the round fn's contribution
    gate); ``row``: this node's full mixing row ``[n]`` (0 = no edge).

    Each circulant offset k contributes one ppermute shifting every
    node's (params, weight) k steps around the mesh; receivers scale by
    ``row[sender] * sender_weight``. Offsets over-approximate on
    non-circulant graphs, but ``row`` zeroes non-edges, so correctness
    holds. Total ICI traffic = (#offsets) × |params| instead of
    all-gather's n × |params| — O(degree) for rings/chords.

    Returns ``(mean_f32, total_weight)``; the caller keeps its own
    params where ``total_weight == 0`` (the nothing-arrived timeout
    analog, aggregator.py:53-76).

    ``exchange_dtype`` (e.g. bf16) down-casts params before each
    ``ppermute`` — halving ICI bytes per hop; accumulation stays f32.
    The self contribution goes through the same wire cast so every
    model entering the aggregation saw identical rounding (matching
    the dense einsum's whole-stack cast). Exact dense/sparse parity
    holds for ``exchange_dtype=None`` (the default): with a wire dtype
    the two schedules still agree on what crosses the wire but differ
    in weight rounding and accumulation order.

    ``stale_params``/``stale_weight`` switch the hops to DOUBLE-
    BUFFERED (staged) mode: what crosses the wire is the PREVIOUS
    round's post-fit tree at its then contribution weight, while the
    self contribution stays this round's fresh ``params``/``my_weight``
    — one-round-stale gossip. The point is scheduling freedom: the
    shipped buffer is already final when the round starts, so XLA can
    hoist the ppermute sends before/under the local fit instead of
    fencing them behind it (exchange_overlap="staged",
    docs/perf.md §11). A zero ``stale_weight`` round (the seeded
    buffer) degenerates to pure local training.
    """
    n = topology.n
    idx = jax.lax.axis_index(axis_name)
    w_self = row[idx] * my_weight

    def cast(tree):
        return (
            tree if exchange_dtype is None
            else jax.tree.map(lambda p: p.astype(exchange_dtype), tree)
        )

    wire = cast(params)
    if stale_params is not None:
        hop_tree, hop_w = cast(stale_params), stale_weight
    else:
        hop_tree, hop_w = wire, my_weight
    acc = jax.tree.map(lambda p: p.astype(jnp.float32) * w_self, wire)
    total = w_self
    for k in edge_offsets(topology):
        perm = [(i, (i + k) % n) for i in range(n)]  # src -> dst
        shifted = jax.tree.map(
            lambda p: jax.lax.ppermute(p, axis_name, perm), hop_tree
        )
        w_recv = jax.lax.ppermute(hop_w, axis_name, perm)
        sender = (idx - k) % n
        wk = row[sender] * w_recv
        acc = jax.tree.map(
            lambda a, s: a + s.astype(jnp.float32) * wk, acc, shifted
        )
        total = total + wk
    denom = jnp.maximum(total, 1e-9)
    return jax.tree.map(lambda a: a / denom, acc), total


class MeshTransport:
    """Places federation arrays on a device mesh and jit-compiles round
    programs with node-axis shardings.

    This is the runtime seam the reference fills with BaseNode's socket
    listener + NodeConnection threads (base_node.py:70-79, 197-232):
    `start()` there opens sockets; here it builds a Mesh. `broadcast()`
    there writes to N sockets; here a round's exchange IS the program.
    """

    def __init__(self, n_nodes: int, n_devices: int | None = None):
        devices = jax.devices()
        if n_devices is None:
            # largest device count ≤ n_nodes that divides n_nodes evenly
            n_devices = min(len(devices), n_nodes)
            while n_nodes % n_devices:
                n_devices -= 1
        self.mesh = federation_mesh(n_devices)
        self.n_nodes = n_nodes
        self.n_devices = n_devices
        self._stacked = stacked_sharding(self.mesh)
        self._replicated = replicated_sharding(self.mesh)

    def _place(self, x, sharding):
        """Placement straight from the HOST copy — each device is sent
        its own shard, nothing is first materialised whole on the
        default device (on a four-chip host that is chip 0, for every
        stacked array of the federation). In a multi-process
        (jax.distributed) job ``device_put`` cannot target
        non-addressable devices, so each process fills only the shards
        it owns via ``make_array_from_callback`` (the dcn.make_global
        recipe). An array already on a device is resharded
        device-to-device."""
        if jax.process_count() > 1:
            arr = np.asarray(x)
            # explicit dtype: a process whose devices all fall outside
            # the federation mesh fills no shards, and the dtype can't
            # be inferred from an empty shard list (dcn.make_global)
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx], dtype=arr.dtype
            )
        if not isinstance(x, jax.Array):
            x = np.asarray(x)
        return jax.device_put(x, sharding)

    def put_stacked(self, tree):
        """Shard each leaf's leading node axis; replicate scalars and
        leaves that don't carry the node axis (e.g. FederatedState.round)."""

        def place(x):
            shape = np.shape(x)
            if len(shape) >= 1 and shape[0] == self.n_nodes:
                return self._place(x, self._stacked)
            return self._place(x, self._replicated)

        return jax.tree.map(place, tree)

    @property
    def replicated(self):
        """The mesh-replicated sharding, for callers that place buffers
        with a raw ``jax.device_put`` (the cross-device streamed
        prefetch seam) and must land on the transport's device set."""
        return self._replicated

    def put_replicated(self, tree):
        return jax.tree.map(
            lambda x: self._place(x, self._replicated), tree
        )

    def compile_round(self, round_fn: Callable):
        """jit a round fn. Shardings are inferred from the committed
        input arrays (put_stacked/put_replicated), the idiomatic
        jax.sharding flow; donating the federation state buys in-place
        param buffers on device."""
        return jax.jit(round_fn, donate_argnums=(0,))

    def compile_eval(self, eval_fn: Callable):
        return jax.jit(eval_fn)
