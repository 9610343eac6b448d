"""The round program's part of ``entry.trace_lower_s``: seconds jax
spent tracing and lowering ``parallel.transport.ROUND_PROGRAM`` (what
it traces inside itself with it) since the process started."""

import hostspans


def read(ctx):
    return hostspans.trace_lower_of(hostspans.ROUND_PROGRAM)
