"""Fold a ``vmap`` axis into the rows of a frozen layer.

The round ``vmap``s one node's step over the nodes: right where the
weights are per node, wrong for a layer whose weights all nodes share
and whose cost is per call, not per row. An expert layer vmapped over
8 nodes sorts, gathers and runs its grouped products 8 times over 1/8
of the tokens each; the federation's tokens of a step belong in ONE
dispatch. :func:`fold_rows` gives such a layer a batching rule: mapped
over its row inputs (and over none of its frozen ones) it is called
once, on the rows of all nodes laid end to end.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap


def fold_rows(fn: Callable, row_outputs: Callable) -> Callable:
    """``fn(rows, frozen) -> out``: ``rows`` a pytree of arrays whose
    leading axis counts rows (tokens), ``frozen`` a pytree that is never
    mapped. ``row_outputs(out)`` is a pytree of booleans like ``out``:
    true for the leaves that have one row an input row (given back per
    node), false for those computed over all rows together (given back
    unmapped: every node sees the same value)."""

    @custom_vmap
    def folded(rows, frozen):
        return fn(rows, frozen)

    @folded.def_vmap
    def rule(axis_size, in_batched, rows, frozen):
        rows_b, frozen_b = in_batched
        if any(jax.tree.leaves(frozen_b)):
            raise ValueError("fold_rows: a frozen input is mapped; the "
                             "frozen part is shared by every node")

        def lay(a, batched):
            if not batched:  # the same rows on every node: test inputs
                a = jnp.broadcast_to(a[None], (axis_size,) + a.shape)
            return a.reshape((axis_size * a.shape[1],) + a.shape[2:])

        out = folded(jax.tree.map(lay, rows, rows_b), frozen)
        per_row = row_outputs(out)
        out = jax.tree.map(
            lambda a, rowwise: a.reshape(
                (axis_size, a.shape[0] // axis_size) + a.shape[1:])
            if rowwise else a, out, per_row)
        return out, per_row

    return folded
