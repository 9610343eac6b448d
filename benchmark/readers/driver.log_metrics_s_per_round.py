"""Host seconds a round under ``scenario.log.metrics``: the per-node
``log_metrics`` loop. A part of ``driver.log_s_per_round``."""

import hostspans
from p2pfl_tpu.obs.trace import get_tracer


def read(ctx):
    return hostspans.log_part_s_per_round(
        get_tracer().spans(), ctx["first_round"], hostspans.LOG_METRICS)
