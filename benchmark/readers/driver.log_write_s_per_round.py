"""Host seconds a round under ``scenario.log.write``: the resource
record's ``log_metrics`` and ``round_marker``. A part of
``driver.log_s_per_round``."""

import hostspans
from p2pfl_tpu.obs.trace import get_tracer


def read(ctx):
    return hostspans.log_part_s_per_round(
        get_tracer().spans(), ctx["first_round"], hostspans.LOG_WRITE)
