"""95th percentile of ``ScenarioResult.round_times_s`` in the window
(nearest rank; with under 20 rounds it is the slowest round)."""

import math


def read(ctx):
    times = sorted(ctx["round_times_s"])
    if not times:
        return None
    return times[min(len(times) - 1, math.ceil(0.95 * len(times)) - 1)]
