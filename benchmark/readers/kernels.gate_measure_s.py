"""Seconds the kernel gate spent deciding: the sum of ``measure_s`` over
its decisions. ``None`` where no decision was measured (off the TPU the
gate is forced to XLA and measures nothing)."""

from p2pfl_tpu.ops import pallas_gemm


def read(ctx):
    measured = [d["measure_s"] for d in pallas_gemm.decisions().values()
                if "measure_s" in d]
    return sum(measured) if measured else None
