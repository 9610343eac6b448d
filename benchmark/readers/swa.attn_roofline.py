"""The window layers' attention's share of its roofline over the traced
window (``scopework.roofline_share``; the work from ``counts/``: the
band's score and value products of every query head, q, k, v read and
the output written once, whatever tiles or kernel compute them)."""

import scopework


def read(ctx):
    return scopework.roofline_share(ctx, "swa.attn")
