"""100 x the score tiles the gated attention forms over the tiles of the
full ``[T, T]`` square at 8192 positions, at the block and tile its last
trace took: the program's own record of ``causal_attention`` under
``gattn.attn`` (``models/ling.py`` ``score_tiles(scope)``)."""

from p2pfl_tpu.models import ling


def read(ctx):
    score_tiles = getattr(ling, "score_tiles", None)
    tiles = score_tiles("gattn.attn") if score_tiles is not None else None
    if not tiles:  # no gated attention was traced
        return None
    return 100.0 * tiles["computed"] / tiles["square"]
