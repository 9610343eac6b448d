"""``mla.device_s_per_round`` in the Kimi Linear cell: device self
seconds a round of the NoPE latent-attention layers' causal softmax
attention (``mla.attn``: 32 heads of 192 and 128 over 16,384 positions,
forward, recomputed and on the way back), in the round program."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "mla.attn")
