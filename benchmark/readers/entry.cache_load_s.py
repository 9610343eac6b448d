"""Seconds jax reported for loading executables from the persistent
compilation cache inside the program's own calls since the process
started."""

from p2pfl_tpu.obs import trace as obs_trace


def read(ctx):
    # a program from before these counters has nothing to read
    seconds = getattr(obs_trace, "cache_load_seconds", None)
    return seconds() if seconds else None
