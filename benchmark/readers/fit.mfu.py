"""The whole step's share of the chip's peak: the forward and backward
FLOPs a round requires (``flops.py`` with the configuration's file under
``counts/``: analytic, no recompute counted) times the rounds, over the
wall seconds of the traced rounds, the chips and the bf16 peak of
``peaks.json``. In per cent."""


def read(ctx):
    if ctx["peak"] is None or ctx["trace"] is None:
        return None
    cell = ctx["cell"]
    per_round = ctx["flops"].round_flops(
        cell.config, cell.scenario, ctx["rows_per_node"], cell.home / "counts")
    wall = ctx["trace"]["part_s"].get("rounds")
    if not wall:
        return None
    return 100.0 * per_round * ctx["rounds"] / (
        wall * ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
