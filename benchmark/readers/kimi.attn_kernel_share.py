"""``attn.kernel_share`` in the Kimi Linear cell: 100 x the attention
scopes whose last trace runs the fused kernel (``ops/flash_attention.py``)
over the attention scopes traced, by the program's own record of each
``causal_attention`` (``models/ling.py`` ``score_tiles(scope)``, its
``kernel``). The cell's one attention scope is latent attention's."""

from p2pfl_tpu.models import ling


def read(ctx):
    score_tiles = getattr(ling, "score_tiles", None)
    if score_tiles is None:
        return None
    record = score_tiles("mla.attn")
    # a program from before the record's ``kernel`` has nothing to read
    if not record or "kernel" not in record:
        return None
    return 100.0 * bool(record["kernel"])
