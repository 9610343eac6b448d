"""100 x the score tiles the cell's latent attention forms over the
tiles of the full ``[T, T]`` square: the program's own record of its
last trace of ``causal_attention`` (``models/ling.py``
``score_tiles()``)."""

from p2pfl_tpu.models import ling


def read(ctx):
    # a program from before the record has nothing to read
    score_tiles = getattr(ling, "score_tiles", None)
    if score_tiles is None:
        return None
    tiles = score_tiles()
    if not tiles:  # no latent-attention layer was traced
        return None
    return 100.0 * tiles["computed"] / tiles["square"]
