"""Seconds a round that the program's stall watch (``host.stall``: a
thread that woke late, so every thread of the process stood still) saw
inside the window's ``scenario.wait`` spans: near
``driver.wait_over_median_s_per_round`` where a frozen process made the
wait long, near 0 where the host ran on time and the device sat idle."""

import hostspans
from p2pfl_tpu.obs.trace import get_tracer


def read(ctx):
    return hostspans.stall_s_in_wait_per_round(get_tracer().spans(),
                                               ctx["first_round"])
