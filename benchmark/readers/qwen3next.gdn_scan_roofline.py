"""Gated DeltaNet's delta rule's share of its roofline over the traced
window (``scopework.roofline_share``; the work from
``counts/qwen3_next.py``: the recurrence's own products over the 32
value heads, q and k read once a key head, one 4-byte decay a value head
and position, whatever form computes it)."""

import scopework


def read(ctx):
    return scopework.roofline_share(ctx, "gdn.scan")
