"""Seconds inside the program's own calls (constructor, ``run``,
``evaluate``) during which jax was tracing or lowering, since the
process started: nested traces once, and what runs at trace time (the
kernel gate's measurements) with them."""

from p2pfl_tpu.obs import trace as obs_trace


def read(ctx):
    # a program from before these counters has nothing to read
    seconds = getattr(obs_trace, "trace_lower_seconds", None)
    return seconds() if seconds else None
