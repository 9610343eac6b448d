"""Device self seconds a round of the tied head and its loss
(``lm.head_loss``: ``h E^T`` over all 65,536 ids a chunk of positions at
a time, the log-sum, forward, recomputed and on the way back), in the
round program."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "lm.head_loss")
