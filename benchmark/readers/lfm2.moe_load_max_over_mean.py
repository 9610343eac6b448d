"""``moe.load_max_over_mean`` in the LFM2 cell: the largest load of an
expert over the experts' mean (all 32 held: the deployment's own
imbalance), the worst layer and step since the process started
(``obs.trace.counted``)."""

from p2pfl_tpu.obs import trace as obs_trace


def read(ctx):
    counted = getattr(obs_trace, "counted", None)
    got = counted().get("moe.load_max_over_mean") if counted else None
    return None if got is None else float(got["max"].max())
