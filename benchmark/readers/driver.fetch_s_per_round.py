"""Host seconds a round under ``scenario.fetch``: the device-to-host
copy of the round's training losses (and trust observations), over the
window's rounds."""

import spans
from p2pfl_tpu.obs.trace import get_tracer


def read(ctx):
    return spans.per_round(get_tracer().spans(), ctx["first_round"],
                           ["scenario.fetch"])
