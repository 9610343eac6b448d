"""Device self seconds a round of the window layers' score and value
products (``swa.attn``: every tile a block's window touches, forward,
recomputed and on the way back), in the round program."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "swa.attn")
