"""1 where the first conv block of the cell's model traced in the
lane-dense (banded) form, else 0: the program's own record of which
lowering each conv took (``models/cnn.py`` ``lowerings()``)."""

from p2pfl_tpu.models import cnn


def read(ctx):
    # a program from before the record has nothing to read
    lowerings = getattr(cnn, "lowerings", None)
    if lowerings is None:
        return None
    return int(lowerings().get("Conv_0", {}).get("form") == "banded")
