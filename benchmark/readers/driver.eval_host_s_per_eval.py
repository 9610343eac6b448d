"""``scenario.evaluate`` less ``scenario.evaluate.device``: what an
evaluation costs beside its device pass, mean over the window's
evaluations."""

import spans
from p2pfl_tpu.obs.trace import get_tracer


def read(ctx):
    return spans.eval_host_s_per_eval(get_tracer().spans(), ctx["evals"])
