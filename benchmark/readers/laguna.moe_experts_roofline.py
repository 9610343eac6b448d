"""``moe.experts_roofline`` in the Laguna cell: the held experts' grouped
products' share of their roofline over the traced window (the work from
``counts/laguna_s.py``: 2.5 expected held pairs a token, every held
expert's weights read once a pass), the ops under ``moe.experts`` and
XLA:TPU's ``ragged-dot-none``."""

import scopework


def read(ctx):
    return scopework.roofline_share(ctx, "moe.experts",
                                    also=("ragged-dot-none",))
