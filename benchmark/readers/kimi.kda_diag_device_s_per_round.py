"""Device self seconds a round of the delta rule's diagonal sub-blocks
formed from the pairwise differences of the running log-decays, each
summed inside the fusion that forms its factors (``kda.diag``, nested
in ``kda.scan``: forward, recomputed and on the hand-written way back),
in the round program. Only the form of
``kda_heads`` that is exact for an unbounded decay has the scope."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "kda.diag")
