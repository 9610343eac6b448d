"""``moe.experts_roofline`` in the Kimi Linear cell: the held experts'
grouped products' share of their roofline over the traced window (the
work from ``counts/kimi_linear.py``: 1.0 expected held pair a token,
every one of the 32 held experts' weights read once a pass), the ops
under ``moe.experts`` and XLA:TPU's ``ragged-dot-none``."""

import scopework


def read(ctx):
    return scopework.roofline_share(ctx, "moe.experts",
                                    also=("ragged-dot-none",))
