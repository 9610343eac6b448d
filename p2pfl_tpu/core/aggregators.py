"""Aggregation algorithms over stacked parameter pytrees.

TPU-native re-design of the reference's aggregator family
(fedstellar/learning/aggregators/aggregator.py + fedavg.py): instead of
a daemon thread collecting ``{contributor-key: (state_dict, weight)}``
dicts and looping over layers, every aggregator here is a **pure
function** ``aggregate(stacked, weights, mask) -> params``:

- ``stacked``: pytree whose leaves carry a leading ``[n]`` node axis;
- ``weights``: float ``[n]`` sample counts (FedAvg weighting,
  fedavg.py:52-58);
- ``mask``: bool ``[n]`` — which rows actually arrived. Timeout-bounded
  aggregation (aggregator.py:46-76 "aggregate with whatever arrived")
  becomes "call with a partial mask"; a dead node is a False entry, not
  a special case.

Everything is fixed-shape and jit-able, so aggregation fuses into the
same XLA program as training and the gossip collectives. The robust
aggregators (Krum, trimmed mean, median) cover the reference's stretch
config "ViT-Tiny … Krum/trimmed-mean aggregator" (BASELINE.json).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from p2pfl_tpu.core.pytree import tree_weighted_mean

Params = Any


def _masked_weights(weights: jnp.ndarray, mask: jnp.ndarray | None) -> jnp.ndarray:
    w = jnp.asarray(weights, jnp.float32)
    if mask is not None:
        w = jnp.where(mask, w, 0.0)
    return w


class Aggregator:
    """Base aggregator. Subclasses implement :meth:`aggregate`.

    The reference's session bookkeeping (waiting for the train set,
    partial-aggregation gossip, contributor dedup —
    aggregator.py:106-229) lives in
    :mod:`p2pfl_tpu.p2p.session`, not here: this class is only
    the math, so it can run on-device.
    """

    name = "base"

    def aggregate(
        self,
        stacked: Params,
        weights: jnp.ndarray,
        mask: jnp.ndarray | None = None,
    ) -> Params:
        raise NotImplementedError

    def __call__(self, stacked, weights, mask=None):
        return self.aggregate(stacked, weights, mask)


class FedAvg(Aggregator):
    """Sample-count-weighted mean (fedavg.py:26-60 semantics)."""

    name = "FedAvg"

    def aggregate(self, stacked, weights, mask=None):
        return tree_weighted_mean(stacked, _masked_weights(weights, mask))


class FedMedian(Aggregator):
    """Coordinate-wise median over present rows.

    Masked rows are replaced by the masked mean so they never win the
    median; with an odd number of present rows this is the exact
    coordinate-wise median.
    """

    name = "FedMedian"

    def aggregate(self, stacked, weights, mask=None):
        w = _masked_weights(weights, mask)
        fill = tree_weighted_mean(stacked, w)
        present = w > 0

        def leaf(x, f):
            bshape = (x.shape[0],) + (1,) * (x.ndim - 1)
            xf = jnp.where(present.reshape(bshape), x.astype(jnp.float32), f)
            return jnp.median(xf, axis=0).astype(x.dtype)

        return jax.tree.map(leaf, stacked, fill)


class TrimmedMean(Aggregator):
    """Coordinate-wise trimmed mean: drop the ``beta`` largest and
    smallest values per coordinate, average the rest.

    ``beta`` is the trim count per side (Byzantine tolerance). Masked
    rows are filled with the masked mean, so they land mid-sort and are
    averaged as if they were the consensus value.
    """

    name = "TrimmedMean"

    def __init__(self, beta: int = 1):
        if beta < 0:
            raise ValueError(f"trim count beta must be >= 0, got {beta}")
        self.beta = beta

    def aggregate(self, stacked, weights, mask=None):
        w = _masked_weights(weights, mask)
        fill = tree_weighted_mean(stacked, w)
        present = w > 0
        n = w.shape[0]
        beta = min(self.beta, max((n - 1) // 2, 0))
        lo, hi = beta, n - beta

        def leaf(x, f):
            bshape = (x.shape[0],) + (1,) * (x.ndim - 1)
            xf = jnp.where(present.reshape(bshape), x.astype(jnp.float32), f)
            xs = jnp.sort(xf, axis=0)
            return jnp.mean(xs[lo:hi], axis=0).astype(x.dtype)

        return jax.tree.map(leaf, stacked, fill)


class Krum(Aggregator):
    """(Multi-)Krum: score each model by the sum of its ``n - f - 2``
    smallest squared distances to other models; return the best one
    (``m=1``) or the mean of the ``m`` best.

    Distances are computed on flattened float32 vectors — one big
    ``[n, d] @ [d, n]`` Gram matmul, which XLA tiles onto the MXU.
    Masked rows get +inf score and can never be selected.
    """

    name = "Krum"

    def __init__(self, f: int = 1, m: int = 1):
        self.f = f
        self.m = m
        self._small_cohort_warned = False

    def aggregate(self, stacked, weights, mask=None):
        w = _masked_weights(weights, mask)
        present = w > 0
        n = w.shape[0]
        # Krum's score needs n_present - f - 2 >= 1 closest neighbors;
        # below that the clip to 1 silently degrades selection to
        # nearest-single-neighbor, which tolerates NOTHING — fail loud
        # instead of returning a number that looks Byzantine-robust.
        # The static row count is checkable even under jit (and a
        # too-small n can never recover at runtime)...
        if n < self.f + 3:
            raise ValueError(
                f"Krum(f={self.f}) needs at least f+3={self.f + 3} rows "
                f"to score n_present-f-2 neighbors, got n={n}; lower f "
                "or use TrimmedMean/FedMedian for small cohorts"
            )
        # ...while a dynamic partial mask can only be checked when it
        # is concrete (eager host-path aggregation); inside a jitted
        # program the clip below still applies, documented here.
        if not isinstance(present, jax.core.Tracer):
            n_present = int(jnp.sum(present))
            if n_present < self.f + 3 and not self._small_cohort_warned:
                import warnings

                warnings.warn(
                    f"Krum(f={self.f}) aggregating only {n_present} "
                    f"present rows (< f+3={self.f + 3}): neighbor count "
                    "clipped to 1 — selection is NOT Byzantine-robust "
                    "this round",
                    RuntimeWarning,
                    stacklevel=2,
                )
                self._small_cohort_warned = True  # once per instance

        with jax.named_scope("krum.gram"):
            flat = jnp.concatenate(
                [x.reshape(n, -1).astype(jnp.float32) for x in jax.tree.leaves(stacked)],
                axis=1,
            )
            sq = jnp.sum(flat * flat, axis=1)
            gram = flat @ flat.T
            d2 = sq[:, None] + sq[None, :] - 2.0 * gram  # [n, n]
        with jax.named_scope("krum.select"):
            big = jnp.float32(jnp.finfo(jnp.float32).max / 4)
            # distances to self / to absent rows never count as "closest"
            d2 = jnp.where(jnp.eye(n, dtype=bool), big, d2)
            d2 = jnp.where(present[None, :], d2, big)

            n_present = jnp.sum(present.astype(jnp.int32))
            k = jnp.clip(n_present - self.f - 2, 1, n - 1)  # closest-count per Krum
            d2_sorted = jnp.sort(d2, axis=1)
            col_mask = jnp.arange(n - 1)[None, :] < k  # static shape, dynamic k
            scores = jnp.sum(jnp.where(col_mask, d2_sorted[:, : n - 1], 0.0), axis=1)
            scores = jnp.where(present, scores, jnp.inf)

            m = min(self.m, n)
            _, best = jax.lax.top_k(-scores, m)  # indices of m lowest scores
            sel = jnp.zeros((n,), jnp.float32).at[best].set(1.0)
            sel = jnp.where(present, sel, 0.0)
        return tree_weighted_mean(stacked, sel)


_REGISTRY: dict[str, Callable[..., Aggregator]] = {
    "fedavg": FedAvg,
    "fedmedian": FedMedian,
    "median": FedMedian,
    "trimmedmean": TrimmedMean,
    "krum": Krum,
}


def get_aggregator(name: str, **kwargs) -> Aggregator:
    """Factory by name (reference selects by ``aggregator_args.algorithm``,
    participant.json.example + node.py:134-137)."""
    key = name.lower().replace("_", "").replace("-", "")
    if key not in _REGISTRY:
        raise ValueError(f"unknown aggregator {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[key](**kwargs)
