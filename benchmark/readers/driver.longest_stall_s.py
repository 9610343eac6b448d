"""The longest ``host.stall`` that touches the window's ``scenario.run``
or one of its evaluations (the watch runs inside ``run()``): 0 where
the process never stood still for longer than the watch's threshold."""

import hostspans
from p2pfl_tpu.obs.trace import get_tracer


def read(ctx):
    return hostspans.longest_stall_s(get_tracer().spans(), ctx["evals"])
