"""Device self seconds a round of Gated DeltaNet between its projections,
in the round program, recomputation included: the causal convolution
over q, k, v with q and k's normalisation (``gdn.conv``) and the
chunk-wise delta rule in its scalar-decay form with its solve
(``gdn.scan``, the solve's hand-written way back under it too)."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "gdn.conv", "gdn.scan")
