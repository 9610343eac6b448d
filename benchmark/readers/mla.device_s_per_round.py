"""Device self seconds a round of the latent-attention layer's causal
softmax attention (``mla.attn``), in the round program, recomputation
included."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "mla.attn")
