"""The delta rule's share of its roofline over the traced window
(``scopework.roofline_share``; the work from ``counts/``: the
recurrence's own products and its operands' bytes, whatever form
computes it)."""

import scopework


def read(ctx):
    return scopework.roofline_share(ctx, "kda.scan")
