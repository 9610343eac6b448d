"""``moe.experts_roofline`` in the Qwen3-Next cell: the held experts'
grouped products' share of their roofline over the traced window (the
work from ``counts/qwen3_next.py``: 1.25 expected held pairs a token,
every one of the 64 held experts' weights read once a pass), the ops
under ``moe.experts`` and XLA:TPU's ``ragged-dot-none``."""

import scopework


def read(ctx):
    return scopework.roofline_share(ctx, "moe.experts",
                                    also=("ragged-dot-none",))
