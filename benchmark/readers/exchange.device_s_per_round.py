"""Device self seconds a round under the round builders' scopes: the
mixing contraction (``exchange.mix``) or Krum's Gram matrix and
selection (``krum.gram``, ``krum.select``), over the traced rounds.

A fused op carries ONE scope, the one XLA kept for the fusion, and a
copy that layout assignment put beside a scoped op carries none: this
reads what bears the scope, not all that the exchange costs (PERF.md,
section 5, says which ops it caught and which it missed). Nothing where
no op bears one of the scopes: an executable compiled from a program
without scopes and handed on by the compile cache."""

import tracereduce


def read(ctx):
    if ctx["trace"] is None:
        return None
    got = tracereduce.scope_seconds(
        ctx["trace"], "exchange.mix", "krum.gram", "krum.select")
    return None if got is None else got / ctx["rounds"]
