"""``kda.diag_kernel_share`` in the Kimi Linear cell: 100 where the last
trace of the delta rule's diagonal sub-blocks (``models/ling.py``
``_diag_pairs``, the form of ``kda_heads`` exact for an unbounded decay)
formed them by the Pallas kernels of ``ops/kda_diag.py``, 0 where by the
XLA form: the program's own record, ``ling.diag_forms()``, as the
platform the run holds lowers it."""

from p2pfl_tpu.models import ling


def read(ctx):
    diag_forms = getattr(ling, "diag_forms", None)
    # a program from before the record has nothing to read
    record = diag_forms() if diag_forms is not None else None
    if not record:
        return None
    return 100.0 * bool(record["kernel"])
