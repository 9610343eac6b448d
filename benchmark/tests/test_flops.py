"""A round's required FLOPs: the accepted cells' integers, and a count
found by file in a home ``flops.py`` has never seen."""

import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import flops  # noqa: E402
import run as bench  # noqa: E402

FIXTURES = HERE / "data" / "bench"


@pytest.mark.parametrize("cell, rows, per_sample, whole", [
    # 64 nodes x 2 steps x 336 rows x 91.93 MFLOP
    ("femnist-cnn.dfl64-full", 675, 91927040, 3953598136320),
    # 32 nodes x 4 steps x 115 rows x 2.154 GFLOP
    ("vit-tiny.dfl32-full-krum", 461, 2154048768, 31707597864960),
])
def test_accepted_cells_count_what_they_counted(cell, rows, per_sample, whole):
    c = bench.Cell(cell, False)
    assert flops.per_sample(c.config, c.scenario)["train"] == per_sample
    assert flops.round_flops(c.config, c.scenario, rows) == whole


def test_a_count_is_found_by_file():
    c = bench.Cell("vit-tiny-lora.dfl8-full", True,
                   bench=FIXTURES / "entries.json", home=FIXTURES)
    got = flops.per_sample(c.config, c.scenario, c.home / "counts")
    # the frozen base: forward and input gradients, none for the patch
    # embedding; the rank-4 pairs on q and v of 12 layers, trained
    whole, patch = 718409472, 2 * 64 * 4 * 4 * 3 * 192
    adapters = 2 * 12 * 2 * 64 * 4 * (192 + 192)
    assert got == {"forward": whole + adapters,
                   "train": 2 * whole - patch + 3 * adapters}
    # 8 nodes x 4 steps of batch 9 on 36 rows
    assert flops.round_flops(c.config, c.scenario, 36, c.home / "counts") \
        == 8 * 4 * 9 * got["train"]
    with pytest.raises(FileNotFoundError):
        flops.per_sample(c.config, c.scenario)  # not among the benchmark's own
