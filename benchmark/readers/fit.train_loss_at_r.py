"""Mean over nodes of the training loss at the fixed round the traffic
file names (counted from the federation's first round)."""

import numpy as np


def read(ctx):
    r = ctx["cell"].traffic["loss_round"] - ctx["first_round"]
    losses = ctx["window_losses"]
    if not 0 <= r < len(losses) or not np.isfinite(losses[r]).all():
        return None
    return float(np.mean(losses[r]))
