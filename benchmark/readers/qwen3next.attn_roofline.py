"""The gated attention's share of its roofline over the traced window
(``scopework.roofline_share``; the work from ``counts/qwen3_next.py``:
the causal half's score and value products of every query head at heads
of 256, q, k, v read and the output written once)."""

import scopework


def read(ctx):
    return scopework.roofline_share(ctx, "gattn.attn")
