"""Seconds of the window's ``run()`` that lie in no round and before
its closing evaluation (``scenario.run`` less its ``scenario.round``s,
up to the closing ``scenario.evaluate``): ``scenario.run.enter``, the
first ``scenario.run.exit`` and what lies between rounds. Paid once a
``run()``, inside ``round_s``; not per round."""

import hostspans
from p2pfl_tpu.obs.trace import get_tracer


def read(ctx):
    return hostspans.run_outside_rounds_s(get_tracer().spans())
