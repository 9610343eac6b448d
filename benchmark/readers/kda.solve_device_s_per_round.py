"""Device self seconds a round of the delta rule's intra-chunk solve
(``kda.solve``, nested in ``kda.scan``): the inverse of each chunk's
unit lower-triangular matrix and its products, forward, recomputed and
on the way back. Nothing where the program has no such scope."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "kda.solve")
