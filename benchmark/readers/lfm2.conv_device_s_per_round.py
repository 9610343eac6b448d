"""Device self seconds a round of the short-convolution mixers' two
products and their convolution (``lfm2.conv``: forward, recomputed and
on the way back; NOT the two projections around them), in the round
program."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "lfm2.conv")
