"""How many times the round program was traced anew, as an outermost
function, since the process started: 1 unless the step is traced
twice."""

import hostspans


def read(ctx):
    return hostspans.trace_lower_of(hostspans.ROUND_PROGRAM, "traces")
