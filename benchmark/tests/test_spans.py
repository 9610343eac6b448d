"""``spans.py`` on a hand-made ring (clipping by round, self time by
nesting, rounds that lost spans), and the readers of the program's spans
and counters in a traced rehearsal of every cell."""

import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import spans  # noqa: E402
from test_rehearsal import BENCH, rehearse  # noqa: E402

#: the metrics this file's readers fill, by what they read
SPAN_METRICS = {
    "driver.plan_s_per_round", "driver.dispatch_s_per_round",
    "driver.fetch_s_per_round", "driver.log_s_per_round",
    "driver.slowest_round_host_s", "driver.eval_host_s_per_eval"}
COUNTER_METRICS = {
    "entry.data_s", "entry.init_federation_s", "entry.trace_lower_s",
    "entry.cache_load_s", "kernels.gate_measure_s"}
#: a metric a CPU rehearsal may leave out, and why
NONE_ON_CPU = {
    "kernels.gate_measure_s":
        "off the TPU the gate is forced to XLA and measures nothing",
}


def a_round(r, t, wait=0.2, lane=None, slow=0.0):
    """One round starting at ``t``: 10 ms plan, 1 ms dispatch, the wait,
    2 ms fetch, two logs of 1 ms around a 0.5 ms status, each child
    0.1 ms after the last, so 0.8 ms of the round is its own. Children
    first, as the ring has them."""
    out, at = [], t + 0.0001
    for name, dur in (("scenario.plan", 0.010 + slow),
                      ("scenario.dispatch", 0.001), ("scenario.wait", wait),
                      ("scenario.fetch", 0.002), ("scenario.log", 0.001),
                      ("scenario.status", 0.0005), ("scenario.log", 0.001)):
        out.append((name, lane, at, dur, None))
        at += dur + 0.0001
    out.append(("scenario.round", lane, t, at - t, {"round": r}))
    return out


def an_evaluation(t, lane=None):
    return [("scenario.evaluate.device", lane, t + 0.001, 0.25, None),
            ("scenario.evaluate.fetch", lane, t + 0.2515, 0.0005, None),
            ("scenario.evaluate", lane, t, 0.2525, None)]


@pytest.fixture
def ring():
    ring = []
    for r in range(5):  # rounds 0-2 are set-up's, 3 and 4 the window's
        ring += a_round(r, 100.0 + r, slow=0.5 if r == 4 else 0.0)
    ring += an_evaluation(99.0) + an_evaluation(105.0) + an_evaluation(106.0)
    return ring


def test_rounds_are_clipped_by_the_parents_round(ring):
    assert [n.args["round"] for n in spans.window_rounds(ring, 3)] == [3, 4]
    assert spans.per_round(ring, 3, ["scenario.plan"]) == pytest.approx(
        (0.010 + 0.510) / 2)
    assert spans.per_round(ring, 0, ["scenario.plan"]) == pytest.approx(
        (4 * 0.010 + 0.510) / 5)
    assert spans.per_round(ring, 5, ["scenario.plan"]) is None
    assert spans.per_round([], 0, ["scenario.plan"]) is None


def test_self_time_is_what_the_direct_children_leave(ring):
    (r3, _) = spans.window_rounds(ring, 3)
    assert len(r3.children) == 7
    assert r3.self_s() == pytest.approx(0.0008)
    assert spans.per_round(ring, 3, ["scenario.log", "scenario.status"],
                           with_self=True) == pytest.approx(0.0025 + 0.0008)
    # a grandchild is its parent's, not the round's
    deep = ring + [("p2p.verify", None, 103.0002, 0.004, None)]
    (r3, _) = spans.window_rounds(deep, 3)
    assert len(r3.children) == 7 and r3.self_s() == pytest.approx(0.0008)
    plan = next(c for c in r3.children if c.name == "scenario.plan")
    assert plan.self_s() == pytest.approx(0.006)
    # another lane's spans are another timeline
    other = ring + [("node.fit", "node1", 103.0, 0.9, None)]
    assert spans.window_rounds(other, 3)[0].self_s() == pytest.approx(0.0008)


def test_the_slowest_rounds_host_part(ring):
    # round 4: 0.5 s more under plan; its wait is taken out
    assert spans.slowest_round_host_s(ring, 3) == pytest.approx(
        0.510 + 0.001 + 0.002 + 0.0025 + 0.0008)
    assert spans.slowest_round_host_s(ring, 9) is None


def test_evaluations_are_the_last_n(ring):
    assert [e.t0 for e in spans.last_evaluations(ring, 2)] == [105.0, 106.0]
    assert spans.eval_host_s_per_eval(ring, 2) == pytest.approx(0.0025)
    assert spans.eval_host_s_per_eval(ring, 0) is None
    assert spans.eval_host_s_per_eval(a_round(0, 1.0), 3) is None
    # one that run() made inside a round counts like any other
    inside = a_round(7, 200.0, wait=0.01) + [
        (n, lane, 200.03 + t0 - 300.0, d, a)
        for n, lane, t0, d, a in an_evaluation(300.0)]
    inside[-4] = ("scenario.round", None, 200.0, 0.5, {"round": 7})
    (ev,) = spans.last_evaluations(inside, 1)
    assert ev.dur == pytest.approx(0.2525)
    (r7,) = spans.window_rounds(inside, 7)
    assert r7.self_s() == pytest.approx(0.5 - 0.0255 - 0.2525)


def test_a_round_that_lost_spans_is_left_out(ring):
    # the ring evicts oldest first, and a round's children close before
    # it: round 3 without its first two children would read their time
    # as its own
    cut = [s for s in ring if not (
        103.0 < s[2] < 103.02 and s[0] in ("scenario.plan",
                                            "scenario.dispatch"))]
    assert [n.args["round"] for n in spans.window_rounds(cut, 3)] == [4]
    # children whose parent is gone (evicted, or still open when the
    # ring was read) belong to no round
    orphans = [s for s in ring if not (
        s[0] == "scenario.round" and s[4]["round"] == 4)]
    assert [n.args["round"] for n in spans.window_rounds(orphans, 3)] == [3]
    assert spans.per_round(orphans, 3, ["scenario.plan"]) == pytest.approx(0.010)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_traced_rehearsal_fills_the_program_metrics(cell):
    line, _ = rehearse(cell, 1)
    expected = {m["name"] for m in BENCH["per_layer"]
                if m["name"] in SPAN_METRICS | COUNTER_METRICS
                and cell["name"] in m.get("workloads", [cell["name"]])}
    assert expected >= SPAN_METRICS | COUNTER_METRICS - {
        "kernels.gate_measure_s"}
    got = line["metrics"]
    for name in sorted(expected):
        if name not in got:
            assert name in NONE_ON_CPU, f"{name} read nothing"
            continue
        assert got[name]["unit"] == "s" and got[name]["value"] >= 0.0
    for name in SPAN_METRICS | {"entry.data_s", "entry.init_federation_s",
                                "entry.trace_lower_s"}:
        assert got[name]["value"] > 0.0, name
    # the parts of a round lie within the round
    parts = sum(got[n]["value"] for n in (
        "driver.plan_s_per_round", "driver.dispatch_s_per_round",
        "driver.fetch_s_per_round", "driver.log_s_per_round"))
    assert parts < got["driver.round_p95_s"]["value"]
