"""Latent attention's share of its roofline over the traced window
(``scopework.roofline_share``; the work from ``counts/kimi_linear.py``:
the causal half's score and value products at heads of 192 and 128, q,
k, v read and the output written once)."""

import scopework


def read(ctx):
    return scopework.roofline_share(ctx, "mla.attn")
