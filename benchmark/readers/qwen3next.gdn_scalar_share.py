"""100 x the traces of the delta rule under ``gdn.scan`` that took the
scalar-decay form (one decay a value head: ``K K^T`` and ``Q K^T`` over
whole chunks, no sub-chunks, no diagonal kernel) over all its traces
since the process started: the program's own record, ``models/ling.py``
``chunk_forms()``. 100 where every Gated DeltaNet layer took it."""

from p2pfl_tpu.models import ling


def read(ctx):
    chunk_forms = getattr(ling, "chunk_forms", None)
    record = chunk_forms().get("gdn.scan") if chunk_forms is not None else None
    if not record:
        return None
    forms = record["forms"]
    return 100.0 * forms.get("scalar", 0) / sum(forms.values())
