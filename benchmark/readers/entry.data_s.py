"""Seconds of the program's constructor under ``scenario.init.data``:
data from the seed, the stacked host arrays, their placement."""

from p2pfl_tpu.obs import trace as obs_trace


def read(ctx):
    # a program from before these counters has nothing to read
    seconds = getattr(obs_trace, "stage_seconds", None)
    return seconds().get("scenario.init.data") if seconds else None
