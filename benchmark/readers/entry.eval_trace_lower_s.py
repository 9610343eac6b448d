"""The evaluation program's part of ``entry.trace_lower_s``: seconds
jax spent tracing and lowering ``parallel.transport.EVAL_PROGRAM``
since the process started."""

import hostspans


def read(ctx):
    return hostspans.trace_lower_of(hostspans.EVAL_PROGRAM)
