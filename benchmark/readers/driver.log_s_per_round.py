"""Host seconds a round under ``scenario.log`` and ``scenario.status``
plus the self time of ``scenario.round`` (observers, gauges,
checkpoint), over the window's rounds."""

import spans
from p2pfl_tpu.obs.trace import get_tracer


def read(ctx):
    return spans.per_round(get_tracer().spans(), ctx["first_round"],
                           ["scenario.log", "scenario.status"],
                           with_self=True)
