"""``kda.scan_roofline`` in the Kimi Linear cell: the delta rule's share
of its roofline over the traced window (``scopework.roofline_share``;
the work from ``counts/kimi_linear.py``: the recurrence's own products
and its operands' bytes, whatever form computes it; the pairwise
diagonal, ``kda.diag``, is inside the scope and is not required work)."""

import scopework


def read(ctx):
    return scopework.roofline_share(ctx, "kda.scan")
