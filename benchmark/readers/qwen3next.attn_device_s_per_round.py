"""Device self seconds a round of the gated attention layers' causal
softmax attention (``gattn.attn``: 16 query heads of 256 over 2 key and
value heads, 8192 positions; forward, recomputed and on the way back, by
the fused kernels on the TPU), in the round program."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "gattn.attn")
