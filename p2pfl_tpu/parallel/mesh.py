"""Device mesh construction and sharding for federations.

The federation's unit of placement: a 1-D ``nodes`` mesh axis. With N
federated nodes on D devices, the stacked node axis (leading axis of
every federation array — params, data shards, masks) is sharded over
``nodes``; when N > D each device carries N/D nodes and XLA runs the
inner vmap locally. When D == 1 (a single TPU chip) the same program
runs fully local — the collectives degenerate to copies, so one code
path covers chip, slice, and the 8-device virtual CPU CI mesh.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NODES_AXIS = "nodes"

#: the cross-device round's second placement axis (round 20): cohort
#: CHUNKS, not nodes. The cohort scan's C steps split into D
#: contiguous chunks, one per device; each device scans its chunk from
#: the same round-start params. Deliberately a separate 1-D mesh from
#: ``federation_mesh`` — the cross-device plane has no persistent node
#: axis to shard (slots are transient), so the whole mesh goes to the
#: cohort axis.
COHORTS_AXIS = "cohorts"


def cohort_shard_mesh(n_devices: int,
                      devices: list | None = None) -> Mesh:
    """A 1-D ``cohorts`` mesh over ``n_devices`` for the sharded
    cross-device scan (``build_round_fn_cross_device`` with
    ``cohort_shards > 1``)."""
    if devices is None:
        devices = jax.devices()
        if n_devices > len(devices):
            raise ValueError(
                f"asked for {n_devices} cohort-shard devices, "
                f"have {len(devices)}")
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (COHORTS_AXIS,))


def federation_mesh(n_devices: int | None = None,
                    devices: list | None = None) -> Mesh:
    """A 1-D mesh over ``n_devices`` (default: all local devices)."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if n_devices > len(devices):
                raise ValueError(
                    f"asked for {n_devices} devices, have {len(devices)}")
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (NODES_AXIS,))


def stacked_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for arrays whose leading axis is the node axis."""
    return NamedSharding(mesh, P(NODES_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_stacked(tree, mesh: Mesh):
    """Place a stacked pytree (leading node axis on every leaf) onto the
    mesh. Requires the node count to divide evenly over devices."""
    sh = stacked_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)


def fetch_global(x) -> np.ndarray:
    """Device array -> full host copy, valid on EVERY process of a
    multi-process job — including processes that own no device of the
    array's (sub)mesh (e.g. 6 federated nodes over 4 hosts x 2 devices:
    the divisor rule meshes 6 of 8 devices and host 3 holds nothing).

    ``process_allgather`` alone cannot serve a meshless process: its
    gather runs (and leaves its output) on the ARRAY's mesh, so a
    process outside that mesh can neither read a replicated shard nor
    fetch the gathered result. When the array's devices are a strict
    subset of the global devices, shard-owning processes resolve the
    full value locally (shard read for replicated, allgather for
    sharded) and ``broadcast_one_to_all`` — a true global collective —
    ships process 0's copy everywhere (process 0 owns mesh device 0 by
    construction, so it always has the value).

    Every branch below that leads to a COLLECTIVE must be decided from
    metadata that is identical on all processes (process_count, the
    array's device_set vs the global device list). Deciding from
    ``is_fully_addressable`` deadlocks: with n_nodes <= devices-per-
    host the whole submesh lives on host 0, host 0 sees a fully-
    addressable array and returns early, while every other host walks
    into ``broadcast_one_to_all`` and blocks alone.
    """
    if jax.process_count() == 1 or not hasattr(x, "sharding"):
        return np.asarray(x)  # single process / plain host value
    from jax.experimental import multihost_utils

    submesh = len(x.sharding.device_set) < len(jax.devices())
    if not submesh:
        # full mesh: every process owns shards, allgather serves all
        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    # submesh: shard owners resolve locally, everyone joins the
    # broadcast (including owners — it is a global collective)
    if x.is_fully_addressable:
        local = np.asarray(x)
    elif x.addressable_shards:
        if x.sharding.is_fully_replicated:
            local = np.asarray(x.addressable_shards[0].data)
        else:
            local = np.asarray(
                multihost_utils.process_allgather(x, tiled=True)
            )
    else:
        local = np.zeros(x.shape, x.dtype)  # ignored: not the source
    return np.asarray(multihost_utils.broadcast_one_to_all(local))
