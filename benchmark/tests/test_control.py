"""``correct`` has to be able to fail. At a size a test run can hold
(the cells' ``--rehearse-cpu`` sizes, on four host devices):

- the control — the reference in the configuration's
  ``control_precision`` put in the program's place — fails at least one
  of the cell's limits;
- the rest of a run, driven past the look for a chip with the timed
  path broken underneath, prints ``correct: false``: a round that hands
  its state back unchanged, half of every batch left out (the mean taken
  over the rest), and, where the cell spans chips, the exchange between
  chips left out.
"""

import argparse
import json
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import check  # noqa: E402
import run as bench  # noqa: E402

BENCH = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
MULTI = [w["name"] for w in BENCH["workloads"] if w["chips"] > 1]


def args_for(cell):
    return argparse.Namespace(workload=cell, seed=2147483659, seconds=1.0,
                              trace=0, rehearse_cpu=True)


def state_unchanged(driven):
    import jax
    import jax.numpy as jnp

    sc = driven.sc
    inner = sc._round_fn

    def broken(fed, *a):
        new, metrics = inner(jax.tree.map(jnp.copy, fed), *a)
        return fed.replace(round=new.round), metrics

    sc._round_fn = broken


def half_batch(driven):
    sc = driven.sc
    x, y, mask, ns = sc._data_args
    keep = np.arange(mask.shape[1]) % 2 == 0
    sc._data_args = (x, y, sc.transport.put_stacked(
        np.asarray(mask) & keep[None, :]), ns)


def no_exchange_between_chips(driven):
    sc = driven.sc
    inner = sc._plan_args
    n = sc.config.n_nodes
    blk = np.arange(n) // (n // sc.transport.n_devices)
    same = (blk[:, None] == blk[None, :]).astype(np.float32)

    def cut(trains_override=None):
        mix, adopt, trains = inner(trains_override)
        return sc.transport.put_stacked(np.asarray(mix) * same), adopt, trains

    sc._plan_args = cut


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    line = bench.run_cell(args_for(cell))
    assert line["correct"] is True, line["compared"]


@pytest.mark.parametrize("fault", [state_unchanged, half_batch],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("cell", CELLS)
def test_broken_step_is_not_correct(cell, fault):
    line = bench.run_cell(args_for(cell), sabotage=fault)
    assert line["correct"] is False, line["compared"]


@pytest.mark.parametrize("cell", MULTI)
def test_exchange_left_out_is_not_correct(cell):
    line = bench.run_cell(args_for(cell), sabotage=no_exchange_between_chips)
    assert line["correct"] is False, line["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    c = bench.Cell(cell, True)
    _, inputs = bench.Driven(c, 2147483659).release()
    nodes = bench.eval_nodes_of(c, 2147483659)
    ref = bench.reference_readings(c, inputs, nodes)
    ctl = bench.reference_readings(c, inputs, nodes,
                                   q=c.config["control_precision"])
    got = check.compare(ctl, ref, np.arange(len(nodes)), c.limits)
    assert any(v > lim for v, lim in got.values()), got


def test_reference_spread_over_devices():
    """The reference spreads its nodes over a cell's chips (the float32
    state of some hundreds of nodes does not fit one). No cell asks for
    four yet, so this keeps that path honest: on four host devices it
    reads what it reads on one, and with the exchange between the
    devices left out it does not."""
    from reference.federation import Federation

    c = bench.Cell(CELLS[0], True)
    _, inputs = bench.Driven(c, 2147483659).release()
    nodes = bench.eval_nodes_of(c, 2147483659)
    rounds = c.traffic["followed_rounds"]

    def follow(chips, fault=None):
        fed = Federation(c.reference_model(), c.reference_spec(),
                         fault=fault, chips=chips)
        return fed.follow(eval_nodes=nodes, rounds=rounds, **inputs)

    one, four, cut = follow(1), follow(4), follow(4, "no_exchange")
    for k in ("loss", "moment", "change", "eval_loss"):
        np.testing.assert_allclose(four[k], one[k], rtol=2e-3, atol=1e-6)
    every = np.arange(len(nodes))
    assert check.gaps(cut, one, every)["change_gap"] > 0.1
