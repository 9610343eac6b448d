"""``moe.experts_roofline`` in the LFM2 cell: the experts' grouped
products' share of their roofline over the traced window (the work from
``counts/lfm2_moe.py``: all 4 chosen pairs a token, every one of the 32
experts' weights read once a pass), the ops under ``moe.experts`` and
XLA:TPU's ``ragged-dot-none``."""

import scopework


def read(ctx):
    return scopework.roofline_share(ctx, "moe.experts",
                                    also=("ragged-dot-none",))
