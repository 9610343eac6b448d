"""Over the window's rounds, the mean ``scenario.wait`` less the median
one: the excess that a stall (``driver.stall_s_in_wait_per_round``) has
to explain. Nothing on a program without the stall watch."""

import hostspans
from p2pfl_tpu.obs.trace import get_tracer


def read(ctx):
    return hostspans.wait_over_median_s_per_round(get_tracer().spans(),
                                                  ctx["first_round"])
