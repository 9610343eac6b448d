"""Host seconds a round under ``scenario.dispatch``: the call of the
compiled round program until it returns, over the window's rounds."""

import spans
from p2pfl_tpu.obs.trace import get_tracer


def read(ctx):
    return spans.per_round(get_tracer().spans(), ctx["first_round"],
                           ["scenario.dispatch"])
