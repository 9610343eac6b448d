"""Device self seconds a round of the expert layer: its router
(``moe.route``), the sort and gather of the chosen pairs
(``moe.dispatch``), the grouped products (``moe.experts``), the weighted
scatter back (``moe.combine``) and the shared expert (``moe.shared``), in
the round program, recomputation included. NOT in it: the grouped
matmuls themselves, which XLA:TPU files under ``ragged-dot-none`` with no
program or scope in their name (their seconds, over the whole window,
are ``moe.experts_roofline``'s)."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "moe.route", "moe.dispatch",
                               "moe.experts", "moe.combine", "moe.shared")
