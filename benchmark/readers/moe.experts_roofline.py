"""The held experts' grouped products' share of their roofline over the
traced window (``scopework.roofline_share``; the work from
``counts/``: the expected held pairs a token, every held expert's
weights read once a pass). The seconds are those of the ops under
``moe.experts`` and of XLA:TPU's grouped matmuls themselves, which the
compiler files under ``ragged-dot-none`` with no name stack (the expert
layer is their only user)."""

import scopework


def read(ctx):
    return scopework.roofline_share(ctx, "moe.experts",
                                    also=("ragged-dot-none",))
