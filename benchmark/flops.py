"""Operations the forward and backward passes of one training sample
REQUIRE, by configuration: multiply-adds of the GEMMs and convolutions
counted as 2 each, from the configuration's shapes alone. The same
whatever implements the step: no recomputation (remat), no layout
copies, no optimizer or loss arithmetic, and no tap of a 'same'
convolution that falls on the zero padding (an im2col GEMM multiplies
those zeros; the convolution does not need them). Backward counts the
weight gradient and the input gradient of every layer but the first,
whose input needs none.

Each configuration's count is a file of its own, ``counts/<name>.py``,
``<name>`` being the configuration's ``"flops"`` key, with one function
``per_sample(config, scenario) -> {"forward": .., "train": ..}``: the
whole configuration file and the cell's merged scenario, so that a
sequence length, a frozen base (forward and input gradients, no weight
gradient) or a slice of experts can be counted. The helpers here are
for those files.
"""

import importlib.util
import pathlib

COUNTS = pathlib.Path(__file__).resolve().parent / "counts"


def same_taps(n, k):
    """Kernel taps that land inside an n-wide axis, summed over the n
    output positions of a stride-1 'same' convolution."""
    r = k // 2
    return n * k - r * (r + 1)


def train(layers):
    """layers: forward FLOPs per sample, first layer first, every layer
    trained."""
    return 3 * sum(layers) - layers[0]


def per_sample(cell_config, scenario, counts=COUNTS):
    spec = importlib.util.spec_from_file_location(
        "bench_count", pathlib.Path(counts) / f"{cell_config['flops']}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.per_sample(cell_config, scenario)


def round_flops(cell_config, scenario, rows_per_node, counts=COUNTS):
    """Required training FLOPs of one federated round of a cell."""
    per = per_sample(cell_config, scenario, counts)
    batch = scenario["data"]["batch_size"]
    steps = max(rows_per_node // batch, 1) * scenario["training"]["epochs_per_round"]
    return scenario["n_nodes"] * steps * min(batch, rows_per_node) * per["train"]
