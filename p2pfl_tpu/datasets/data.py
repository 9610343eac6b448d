"""Federated dataset views: per-node shards and SPMD-stacked arrays.

The reference gives each node a ``LightningDataModule`` holding its
shard (mnist.py:100-118) and a DataLoader; here the whole federation's
data is materialized as **stacked arrays with a leading node axis** —
``x: [n_nodes, S, ...]`` — padded to a common shard size S with a
boolean sample mask. That leading axis is exactly what gets sharded
over the TPU mesh (or vmapped single-chip), so "every node trains an
epoch" is one XLA program instead of N DataLoader processes.

Per-node train/val split mirrors ``val_percent``
(mnist.py:56-59: batch 32, 10% val).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from p2pfl_tpu.config.schema import DataConfig
from p2pfl_tpu.datasets.partition import (
    ClientPartition,
    lazy_partition_indices,
    partition_indices,
)
from p2pfl_tpu.datasets.sources import DatasetSplits, get_dataset


@dataclasses.dataclass
class NodeData:
    """One node's shard — the per-node view the learner consumes."""

    x: np.ndarray
    y: np.ndarray
    x_val: np.ndarray
    y_val: np.ndarray

    @property
    def n_samples(self) -> int:  # FedAvg weight (lightninglearner get_num_samples)
        return len(self.x)


@dataclasses.dataclass
class FederatedDataset:
    """All shards of a federation, ragged (per-node) and stacked (SPMD)."""

    name: str
    num_classes: int
    input_shape: tuple[int, ...]
    nodes: list[NodeData]
    x_test: np.ndarray
    y_test: np.ndarray
    synthetic: bool = False

    @property
    def n_nodes(self) -> int:
        return len(self.nodes)

    def stacked(self, pad_to: int | None = None):
        """Pad each node's train shard to a common size and stack.

        Returns ``(x, y, mask, n_samples)`` with shapes
        ``[n, S, ...], [n, S, ...], [n, S], [n]``. Padding rows are
        masked out of loss/metrics and, being weight-0, out of FedAvg.
        ``x`` keeps the shards' own type where it is an integer (token
        ids) and a label keeps the axes that follow its row axis (one a
        position for token rows).
        """
        sizes = [nd.n_samples for nd in self.nodes]
        s = pad_to or max(sizes)
        if s < max(sizes):
            raise ValueError(f"pad_to={s} < largest shard {max(sizes)}")
        n = self.n_nodes
        x0, y0 = self.nodes[0].x, self.nodes[0].y
        x = np.zeros((n, s) + self.input_shape,
                     x0.dtype if x0.dtype.kind in "iu" else np.float32)
        y = np.zeros((n, s) + y0.shape[1:], np.int32)
        mask = np.zeros((n, s), bool)
        for i, nd in enumerate(self.nodes):
            k = nd.n_samples
            x[i, :k] = nd.x
            y[i, :k] = nd.y
            mask[i, :k] = True
        return x, y, mask, np.asarray(sizes, np.int32)

    @staticmethod
    def make(
        config: DataConfig,
        n_nodes: int,
        splits: DatasetSplits | None = None,
    ) -> "FederatedDataset":
        """Build federated shards per the DataConfig partition scheme."""
        if splits is None:
            sizes = (
                (config.synthetic_train, config.synthetic_test or 4000)
                if config.synthetic_train else None
            )
            splits = get_dataset(config.dataset, seed=config.seed,
                                 synthetic_sizes=sizes,
                                 profile=getattr(config, "surrogate_profile",
                                                 "hard"))
        parts = partition_indices(
            splits.y_train, n_nodes, scheme=config.partition,
            seed=config.seed, alpha=config.dirichlet_alpha,
            groups=splits.writer_train,
        )
        nodes = []
        for node_i, idx in enumerate(parts):
            # shuffle before capping/splitting — sorted/dirichlet
            # partitions return label-ordered indices, and an unshuffled
            # head slice would be single-label
            rng = np.random.default_rng(config.seed * 100003 + node_i)
            idx = rng.permutation(idx)
            if config.samples_per_node is not None:
                idx = idx[: config.samples_per_node]
            n_val = int(len(idx) * config.val_percent)
            val_idx, train_idx = idx[:n_val], idx[n_val:]
            nodes.append(
                NodeData(
                    x=splits.x_train[train_idx],
                    y=splits.y_train[train_idx],
                    x_val=splits.x_train[val_idx],
                    y_val=splits.y_train[val_idx],
                )
            )
        return FederatedDataset(
            name=splits.name,
            num_classes=splits.num_classes,
            input_shape=splits.input_shape,
            nodes=nodes,
            x_test=splits.x_test,
            y_test=splits.y_test,
            synthetic=splits.synthetic,
        )


@dataclasses.dataclass
class CrossDeviceData:
    """Cross-device dataset view (round 13): client-state-as-index.

    At N=10k–1M virtual clients the :class:`FederatedDataset` recipe —
    N eager ``NodeData`` shards — is both the setup bottleneck and a
    memory multiplier. Here a client IS its row in a lazy
    :class:`ClientPartition`; actual arrays materialize per round, only
    for the K sampled clients, at one FIXED shard size ``shard_size``
    so every round's cohort batch has identical shapes (one compiled
    round program, zero mid-run recompiles).

    No per-client val split: sampled clients are transient, so quality
    tracking is central (the shared test set), like every cross-device
    system FedJAX models.
    """

    name: str
    num_classes: int
    input_shape: tuple[int, ...]
    x_train: np.ndarray
    y_train: np.ndarray
    part: ClientPartition
    x_test: np.ndarray
    y_test: np.ndarray
    shard_size: int  # fixed pad target for every materialized shard
    seed: int = 0
    synthetic: bool = False

    @property
    def n_clients(self) -> int:
        return self.part.n_clients

    @property
    def client_sizes(self) -> np.ndarray:
        """Effective (cap-clamped) per-client sample counts — the
        FedAvg weights and the weighted-sampling distribution."""
        return np.minimum(self.part.sizes(), self.shard_size)

    def cohort_sizes(self, client_ids: np.ndarray) -> np.ndarray:
        """``client_sizes[client_ids]`` without the O(N) full-population
        diff — O(k) per round via ``ClientPartition.take_sizes`` (the
        streamed driver's weight lookup, round 20)."""
        return np.minimum(self.part.take_sizes(client_ids),
                          self.shard_size).astype(np.int32)

    def cohort_buffers(self, k: int):
        """Preallocated host buffers for a ``k``-client
        ``cohort_batch(out=...)`` — the streamed driver's double
        buffer: two of these per run bound the host-side cohort
        residency at exactly two cohorts regardless of N or C."""
        s = self.shard_size
        return (np.zeros((k, s) + self.input_shape, np.float32),
                np.zeros((k, s), np.int32),
                np.zeros((k, s), bool),
                np.zeros((k,), np.int32))

    def cohort_batch(self, client_ids: np.ndarray, out=None):
        """Materialize the sampled clients' shards, padded to
        ``shard_size``: ``(x [k,S,...], y [k,S], mask [k,S],
        n_samples [k])``. Each client's rows are drawn through a
        per-client seeded shuffle before the cap — dirichlet partitions
        are label-grouped, and an unshuffled head slice would be
        single-label (the FederatedDataset.make guard, applied lazily).

        ``out`` (round 20): an existing ``cohort_buffers(k)`` tuple to
        fill in place instead of allocating — the values written are
        identical either way, so streaming through reused buffers
        cannot change round math.
        """
        k = len(client_ids)
        s = self.shard_size
        if out is None:
            x, y, mask, sizes = self.cohort_buffers(k)
        else:
            x, y, mask, sizes = out
            x[:k] = 0.0
            y[:k] = 0
            mask[:k] = False
            sizes[:k] = 0
        for j, cid in enumerate(client_ids):
            idx = self.part.client_indices(int(cid))
            rng = np.random.default_rng(self.seed * 100003 + int(cid))
            idx = rng.permutation(idx)[:s]
            m = len(idx)
            x[j, :m] = self.x_train[idx]
            y[j, :m] = self.y_train[idx]
            mask[j, :m] = True
            sizes[j] = m
        return x, y, mask, sizes

    @staticmethod
    def make(config: DataConfig, n_clients: int) -> "CrossDeviceData":
        """Build the lazy N-client view per the DataConfig scheme.
        ``samples_per_node`` caps (and thereby fixes) the shard size;
        without it the pad target is the largest client shard."""
        sizes = (
            (config.synthetic_train, config.synthetic_test or 4000)
            if config.synthetic_train else None
        )
        splits = get_dataset(config.dataset, seed=config.seed,
                             synthetic_sizes=sizes,
                             profile=getattr(config, "surrogate_profile",
                                             "hard"))
        part = lazy_partition_indices(
            splits.y_train, n_clients, scheme=config.partition,
            seed=config.seed, alpha=config.dirichlet_alpha,
        )
        largest = int(part.sizes().max())
        shard = (
            min(config.samples_per_node, largest)
            if config.samples_per_node is not None else largest
        )
        return CrossDeviceData(
            name=splits.name,
            num_classes=splits.num_classes,
            input_shape=splits.input_shape,
            x_train=splits.x_train,
            y_train=splits.y_train,
            part=part,
            x_test=splits.x_test,
            y_test=splits.y_test,
            shard_size=shard,
            seed=config.seed,
            synthetic=splits.synthetic,
        )
