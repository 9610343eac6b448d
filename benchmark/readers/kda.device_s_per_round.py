"""Device self seconds a round of Kimi Delta Attention: the short
convolutions with q and k's normalisation (``kda.conv``) and the chunk-wise
delta rule (``kda.scan``), in the round program, recomputation
included."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "kda.conv", "kda.scan")
