"""A configuration that comes as new files only, and states a frozen
part: a fixture cell of ``vit-tiny`` with LoRA on (rank 4, the registered
query/value targets), every file of it under ``data/bench`` (its entries of
``BENCHMARK.json`` in ``entries.json``, its configuration, reference module, FLOP count, traffic,
limits, reader), driven through ``run_cell`` as any cell is. The program
trains stacked adapters over one frozen base; the harness hands that base
to the reference once, as the program's own device arrays, and the
reference merges ``W + (alpha / rank) A B`` over it."""

import argparse
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import run as bench  # noqa: E402

FIXTURES = HERE / "data" / "bench"
CELL = "vit-tiny-lora.dfl8-full"


def run(trace, sabotage=None):
    cell = bench.Cell(CELL, True, bench=FIXTURES / "entries.json",
                      home=FIXTURES)
    args = argparse.Namespace(workload=CELL, seed=2147483659, seconds=1.0,
                              trace=trace, rehearse_cpu=True)
    return bench.run_cell(args, sabotage=sabotage, cell=cell)


def test_every_file_of_the_cell_is_a_fixture():
    cell = bench.Cell(CELL, True, bench=FIXTURES / "entries.json",
                      home=FIXTURES)
    entry = cell.bench["configs"][0]
    assert (bench.ROOT / entry["file"]).is_relative_to(FIXTURES)
    assert pathlib.Path(cell.reference_model().__file__).is_relative_to(FIXTURES)
    for kind, name in (("traffic", "dfl8-full.json"), ("cells", f"{CELL}.json"),
                       ("counts", f"{cell.config['flops']}.py"),
                       ("reference", f"{cell.config['reference']['module']}.py")):
        assert (FIXTURES / kind / name).is_file()
        assert not (bench.HERE / kind / name).exists()
    for m in cell.bench["per_layer"]:
        assert (FIXTURES / "readers" / f"{m['name']}.py").is_file()


def test_frozen_base_with_stacked_adapters_is_correct():
    seen = {}

    def look(driven):
        import jax

        base = jax.tree_util.tree_leaves(driven.sc.model.base)
        handed = driven.inputs["frozen"]
        # the program's own arrays, not copies; one base, not one a node
        assert all(any(v is leaf for leaf in base) for v in handed.values())
        assert sorted(handed) == sorted(
            driven.cell.reference_model().FROZEN_SHAPES)
        seen["trained"] = sorted(
            bench.path_str(p).rsplit("/", 3)[-3] + "/" + bench.path_str(p)[-1]
            for p, _ in jax.tree_util.tree_flatten_with_path(
                driven.sc.fed.states.params)[0])

    line = run(trace=1, sabotage=look)
    assert line["correct"] is True, line["compared"]
    assert seen["trained"] == ["query/A", "query/B", "value/A", "value/B"]
    # the numbers of ``correct`` are over the trained leaves alone
    assert sorted(line["facts"]["worst_at"]["change_by_leaf"]) \
        == ["q_A", "q_B", "v_A", "v_B"]
    # the reader found in the fixtures' home: 2 targets x 12 layers x 4 x
    # (192 + 192) adapter parameters beside the 5,362,378 the program's DeiT-Ti holds
    assert line["metrics"]["fit.adapter_share"]["value"] == pytest.approx(
        100 * 36864 / (36864 + 5362378))


def test_a_zeroed_base_leaf_is_not_correct():
    def zero_one_leaf(driven):
        import jax.numpy as jnp

        frozen = driven.inputs["frozen"]
        frozen["mlp1_w"] = jnp.zeros_like(frozen["mlp1_w"])

    line = run(trace=0, sabotage=zero_one_leaf)
    assert line["correct"] is False, line["compared"]
    assert set(line["metrics"]) == {"round_s", "eval_s", "setup_s"}
