"""``moe.dropped_pairs`` in the LFM2 cell: chosen (token, expert) pairs
the expert layer did not compute, over every layer and step since the
process started (``obs.trace.counted``). Has to read 0."""

from p2pfl_tpu.obs import trace as obs_trace


def read(ctx):
    counted = getattr(obs_trace, "counted", None)
    got = counted().get("moe.dropped_pairs") if counted else None
    return None if got is None else float(got["sum"].sum())
