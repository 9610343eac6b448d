"""Seconds of the program's constructor under ``scenario.init.base``:
the frozen base made on the device from the seed."""

from p2pfl_tpu.obs import trace as obs_trace


def read(ctx):
    seconds = getattr(obs_trace, "stage_seconds", None)
    return seconds().get("scenario.init.base") if seconds else None
