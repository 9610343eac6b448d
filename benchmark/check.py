"""The comparison that decides ``correct``: the program's first rounds
against the plain reference's, number by number, each with a limit of
its own (``cells/<cell>.json``; how each was set: ``PERF.md``).

Norms are compared as the contract says: the gap between the program's
norm and the reference's (not the norm of a difference), per leaf and
node, measured against the reference's norm of that leaf or of the
node's median leaf, whichever is larger; the worst is the number. Beside
each worst gap stand the root mean square and the median of the same gaps
over nodes (and leaves): a worst-of-hundreds swings from seed to seed,
and one node whose first steps part from the reference's (Adam's first
updates are all but signs) carries the root mean square with it; the
median reads alike from seed to seed.
"""

import numpy as np

#: a leaf whose reference gradient is under this share of the median
#: leaf's moves by round-off alone (a key's bias under softmax): it is
#: left out of the parameters' change
NOUGHT = 1e-3


def _worst_gap(prog, ref, keep=None):
    """prog, ref: [leaves, nodes] norms. Returns the worst gap and the
    (leaf, node) that reads it."""
    floor = np.median(ref, axis=0, keepdims=True)
    gap = np.abs(prog - ref) / np.maximum(np.maximum(ref, floor), 1e-30)
    gap = np.nan_to_num(gap, nan=np.inf)
    if keep is not None:
        gap = np.where(keep[:, None], gap, -1.0)
    at = np.unravel_index(np.argmax(gap), gap.shape)
    kept = gap[keep] if keep is not None else gap
    return (float(gap[at]), at, float(np.sqrt(np.mean(np.square(kept)))),
            float(np.median(kept)), kept.max(axis=1))


def _rel(prog, ref):
    """Worst, root-mean-square and median relative gap over the nodes."""
    gap = np.abs(prog - ref) / np.maximum(np.abs(ref), 1e-30)
    gap = np.nan_to_num(gap, nan=np.inf)
    return (float(gap.max()), float(np.sqrt(np.mean(np.square(gap)))),
            float(np.median(gap)))


def gaps(seen, ref, eval_nodes, where=None):
    """Every number compared, by name. ``where``, if given, is filled
    with the leaf and node that read the worst norm gaps."""
    out = {}
    where = {} if where is None else where
    for r in range(ref["loss"].shape[0]):
        (out[f"loss{r + 1}_gap"], out[f"loss{r + 1}_rms"],
         out[f"loss{r + 1}_med"]) = _rel(seen["loss"][r], ref["loss"][r])
    (out["moment_gap"], at, out["moment_rms"], out["moment_med"],
     by_leaf) = _worst_gap(seen["moment"], ref["moment"])
    where["moment_by_leaf"] = dict(zip(ref["leaves"], by_leaf.round(5).tolist()))
    where["moment_gap"] = f"{ref['leaves'][at[0]]} node {at[1]}"
    share = ref["moment"] / np.maximum(
        np.median(ref["moment"], axis=0, keepdims=True), 1e-30)
    keep = np.median(share, axis=1) >= NOUGHT
    (out["change_gap"], at, out["change_rms"], out["change_med"],
     by_leaf) = _worst_gap(seen["change"], ref["change"], keep)
    where["change_by_leaf"] = dict(zip(
        [n for n, k in zip(ref["leaves"], keep) if k], by_leaf.round(5).tolist()))
    where["change_gap"] = f"{ref['leaves'][at[0]]} node {at[1]}"
    where["nought_leaves"] = [n for n, k in zip(ref["leaves"], keep) if not k]
    out["eval_loss_gap"] = _rel(
        seen["eval_loss"][eval_nodes], ref["eval_loss"])[0]
    out["eval0_loss_gap"] = _rel(
        np.atleast_1d(seen["eval0_loss"])[: len(eval_nodes)],
        ref["eval0_loss"])[0]
    return out


def compare(seen, ref, eval_nodes, limits, where=None):
    """{name: (value, limit)} for the numbers that have a limit."""
    got = gaps(seen, ref, eval_nodes, where)
    missing = set(limits) - set(got)
    if missing:
        raise KeyError(f"limits for numbers nobody computes: {sorted(missing)}")
    return {k: (got[k], float(limits[k])) for k in limits}
