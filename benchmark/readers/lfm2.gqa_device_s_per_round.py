"""``gqa.device_s_per_round`` in the LFM2 cell: device self seconds a
round of the attention layers' grouped-query score and value products
(``gqa.attn``: 32 heads of 64 over 8, the tiles at or under the
diagonal, forward, recomputed and on the way back), in the round
program."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "gqa.attn")
