"""``moe.device_s_per_round`` in the Qwen3-Next cell: device self seconds
a round of the expert layer's scopes (``moe.route``, ``moe.dispatch``,
``moe.experts``, ``moe.combine``, ``moe.shared`` with its sigmoid gate)
in the round program; NOT the grouped matmuls themselves
(``ragged-dot-none``)."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "moe.route", "moe.dispatch",
                               "moe.experts", "moe.combine", "moe.shared")
