"""``gqa.attn_roofline`` in the LFM2 cell: grouped-query attention's
share of its roofline over the traced window, here at heads of 64
(``scopework.roofline_share``; the work from ``counts/lfm2_moe.py``: the
causal half's score and value products of every query head, q, k, v
read and the output written once)."""

import scopework


def read(ctx):
    return scopework.roofline_share(ctx, "gqa.attn")
