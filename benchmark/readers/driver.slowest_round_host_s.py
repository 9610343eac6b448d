"""In the window's longest ``scenario.round``: its duration less its
``scenario.wait``, that is the host's part of the slowest round."""

import spans
from p2pfl_tpu.obs.trace import get_tracer


def read(ctx):
    return spans.slowest_round_host_s(get_tracer().spans(),
                                      ctx["first_round"])
