"""100 where the last trace of the gated attention (scope ``gattn.attn``)
ran the fused kernel of ``ops/flash_attention.py``, 0 where it ran the
tiled form: the program's own record of each ``causal_attention``
(``models/ling.py`` ``score_tiles(scope)``, its ``kernel``)."""

from p2pfl_tpu.models import ling


def read(ctx):
    score_tiles = getattr(ling, "score_tiles", None)
    record = score_tiles("gattn.attn") if score_tiles is not None else None
    # a program from before the record's ``kernel`` has nothing to read
    if not record or "kernel" not in record:
        return None
    return 100.0 * bool(record["kernel"])
