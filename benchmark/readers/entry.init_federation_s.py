"""Seconds of the program's constructor under
``scenario.init.federation``: ``init_federation``, the placement of the
stacked state, the resume."""

from p2pfl_tpu.obs import trace as obs_trace


def read(ctx):
    # a program from before these counters has nothing to read
    seconds = getattr(obs_trace, "stage_seconds", None)
    return seconds().get("scenario.init.federation") if seconds else None
