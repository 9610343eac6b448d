"""The reduction from trace events to busy/idle, self times, named idle
gaps and collective exposure, on a small recorded trace
(``data/small_trace.json``: the plain events ``tracereduce.load`` gives,
cut from a two-device run; times in seconds)."""

import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import tracereduce  # noqa: E402


@pytest.fixture(scope="module")
def events():
    raw = json.loads((HERE / "data" / "small_trace.json").read_text())
    return {"device": {k: [tuple(e) for e in v] for k, v in raw["device"].items()},
            "host": [tuple(e) for e in raw["host"]]}


def test_window_busy_and_idle(events):
    r = tracereduce.reduce(events)
    assert r["n_devices"] == 2
    assert r["window_s"] == pytest.approx(10.0)
    # device 0: [1,4] with a nested child, [5,6] (copy, then a start), [8,9] -> 5 s; device 1: [1,4],[5,7] -> 5 s
    assert r["busy_s"] == pytest.approx(5.0)


def test_self_time_takes_children_out(events):
    ops = dict(tracereduce.reduce(events)["device_ops"])
    # the while on device 0 spans [1,4] and holds fusion.1 [1.5,3.5]
    assert ops["while.1"] == pytest.approx((1.0 + 3.0) / 2)  # dev0 1 s self, dev1 3 s
    assert ops["fusion.1"] == pytest.approx(2.0 / 2)


def test_idle_gaps_are_named_by_host_span(events):
    r = tracereduce.reduce(events)
    gaps = dict(r["idle_gaps"])
    # host: round [0,4.5], post_round [4.5,5], round [5,7.5], evaluate [7.5,10]
    # dev0 idle: [0,1] round, [4,5] .5 round .5 post, [6,8] 1.5 round .5 eval, [9,10] eval
    # dev1 idle: [0,1] round, [4,5] .5 round .5 post, [7,10] .5 round 2.5 eval
    assert gaps["round"] == pytest.approx((3.0 + 2.0) / 2)
    assert gaps["post_round"] == pytest.approx(0.5)
    assert gaps["evaluate"] == pytest.approx((1.5 + 2.5) / 2)
    assert gaps.get("outside_any_span", 0.0) == pytest.approx(0.0, abs=1e-9)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # the stretch bench.part.rounds is [0,7.5]
    assert r["part_s"]["rounds"] == pytest.approx(7.5)
    assert r["idle_in_part_s"]["rounds"] == pytest.approx((3.5 + 2.5) / 2)


def test_collective_and_its_exposed_part(events):
    r = tracereduce.reduce(events)
    # dev0: all-gather-start.3 [5.9,6] .. all-gather-done.3 [8,9]: 3.1 s in
    # flight, 1.1 s of it on the line; dev1: all-reduce.2 [5,7] holds
    # fusion.9 [5,6]: 2 s, 1 s its own
    assert r["collective_s"] == pytest.approx((3.1 + 2.0) / 2)
    assert r["exposed_collective_s"] == pytest.approx((1.1 + 1.0) / 2)


def test_no_device_plane_gives_nothing():
    assert tracereduce.reduce({"device": {}, "host": []}) is None


def test_recorded_chip_trace():
    """Two rounds of ``femnist-cnn.dfl64-full`` cut from a traced run on
    the TPU v5e (PR 31): 874 device ops, the harness's round spans."""
    import gzip

    raw = json.loads(gzip.open(
        HERE / "data" / "recorded_trace.json.gz", "rt").read())
    events = {"device": {k: [tuple(e) for e in v] for k, v in raw["device"].items()},
              "host": [tuple(e) for e in raw["host"]]}
    r = tracereduce.reduce(events)
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(0.41447, abs=1e-4)
    # one long program a round: the device is idle ~2% of the two rounds
    assert 0.97 < r["busy_s"] / r["window_s"] < 0.99
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert gaps["round"] > gaps["outside_any_span"]
    ops = dict(r["device_ops"])
    # self times never exceed the busy time, and the layout copy of conv1's
    # patches is among the costliest ops
    assert sum(ops.values()) <= r["busy_s"] + 1e-9
    assert any(n.startswith("%copy.199") for n in ops)
    assert r["collective_s"] == 0.0
