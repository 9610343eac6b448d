"""Device self seconds a round of Gated DeltaNet's intra-chunk solve
(``kda.solve``, nested in ``gdn.scan``): the inverse of each chunk's unit
lower-triangular matrix and its products, forward, recomputed and on the
way back, where the hand-written backward sets both scopes itself.
Nothing where the program has no such scope."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "kda.solve")
