"""Device self seconds a round of the adapters' side paths
(``lora.side``: ``(x A) B`` at every adapted projection, and their
gradients), in the round program."""

import scopework


def read(ctx):
    return scopework.per_round(ctx, "lora.side")
