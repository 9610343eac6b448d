"""``moe.device_s_per_round`` in the Kimi Linear cell: device self
seconds a round of the expert layer's scopes (``moe.route``,
``moe.dispatch``, ``moe.experts``, ``moe.combine``, ``moe.shared``) in
the round program; NOT the grouped matmuls themselves
(``ragged-dot-none``)."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "moe.route", "moe.dispatch",
                               "moe.experts", "moe.combine", "moe.shared")
