"""Device self seconds a round of the full layers' grouped-query score
and value products (``gqa.attn``: the tiles at or under the diagonal,
forward, recomputed and on the way back), in the round program."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "gqa.attn")
