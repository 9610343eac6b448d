"""Fixture: the trained parameters' share of all the model's, from the
shapes the reference module states."""

import math


def read(ctx):
    model = ctx["cell"].reference_model()
    size = lambda shapes: sum(math.prod(s) for s in shapes.values())
    return 100.0 * size(model.SHAPES) / (
        size(model.SHAPES) + size(model.FROZEN_SHAPES))
