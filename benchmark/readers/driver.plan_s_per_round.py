"""Host seconds a round under the program's ``scenario.plan`` span
(membership, leader rotation, the ``alive`` placement, the vote, the
plan's arguments), over the window's rounds."""

import spans
from p2pfl_tpu.obs.trace import get_tracer


def read(ctx):
    return spans.per_round(get_tracer().spans(), ctx["first_round"],
                           ["scenario.plan"])
