"""The short-convolution mixers' gated convolution's share of its
roofline over the traced window (``scopework.roofline_share``; the work
from ``counts/lfm2_moe.py``: 7 FLOPs a channel against the input
projection's three thirds read and the gated output written once, 2
bytes each, a position a layer: bound by bytes)."""

import scopework


def read(ctx):
    return scopework.roofline_share(ctx, "lfm2.conv")
