"""100 x the score tiles a window layer's attention forms over the tiles
of the full ``[T, T]`` square: the program's own record of its last
trace of ``causal_attention`` under ``swa.attn`` (``models/ling.py``
``score_tiles(scope)``). 93 of 1024 at 8192 positions, a window of 512
and tiles of 256 x 256."""

from p2pfl_tpu.models import ling


def read(ctx):
    try:
        tiles = ling.score_tiles("swa.attn")
    except (AttributeError, TypeError):
        # a program from before the record, or whose record is of one
        # attention only, has nothing to read
        return None
    if not tiles:  # no window layer was traced
        return None
    return 100.0 * tiles["computed"] / tiles["square"]
