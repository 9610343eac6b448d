"""The share, in %, of (sub-chunk of 16 positions, head, channel) whose
decay over the sub-chunk is deeper than 80, the places where the delta
rule's bounded form (Ling's clip) would be wrong: the program's own
counter ``kda.deep_decay_share`` (``obs.trace.counted``, a KDA layer a
step), averaged over the layers and the steps since the process
started."""

from p2pfl_tpu.obs import trace as obs_trace


def read(ctx):
    counted = getattr(obs_trace, "counted", None)
    got = counted().get("kda.deep_decay_share") if counted else None
    if got is None or not got["steps"]:
        return None
    return float(got["sum"].sum()) / (got["steps"] * got["sum"].size)
