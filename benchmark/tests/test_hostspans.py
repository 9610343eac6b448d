"""The readers of PR 43 on a hand-made ring, as ``test_spans.py`` has
it: a window's ``run()`` of two rounds, one of them stalled inside its
wait for the device, under a watch that saw it; and the same ring as
the parent of that PR would leave it (no ``scenario.run``, no parts of
``scenario.log``, no ``host.stall``), on which every reader is silent.
No device, no subprocess."""

import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import hostspans  # noqa: E402
from run import load_module  # noqa: E402

from p2pfl_tpu.federation import scenario  # noqa: E402
from p2pfl_tpu.obs import trace as obs_trace  # noqa: E402
from p2pfl_tpu.parallel import transport  # noqa: E402

NEW_NAMES = ("host.stall", "scenario.run", "scenario.log.")


def a_round(r, t, wait=0.2):
    """One round starting at ``t``, children first, as the ring has
    them: 10 ms plan, 1 ms dispatch, the wait, 2 ms fetch, a log of
    1.2 ms (its per-node loop 1 ms), 0.5 ms status, a log of 4 ms (the
    snapshot 3 ms, its write 0.5 ms); 0.1 ms between them."""
    out, at = [], t + 0.0001
    for name, dur, parts in (
            ("scenario.plan", 0.010, ()), ("scenario.dispatch", 0.001, ()),
            ("scenario.wait", wait, ()), ("scenario.fetch", 0.002, ()),
            ("scenario.log", 0.0012, (("scenario.log.metrics", 0.001),)),
            ("scenario.status", 0.0005, ()),
            ("scenario.log", 0.004, (("scenario.log.resources", 0.003),
                                     ("scenario.log.write", 0.0005)))):
        inner = at + 0.00005
        for part, part_dur in parts:
            out.append((part, None, inner, part_dur, None))
            inner += part_dur + 0.00005
        out.append((name, None, at, dur, None))
        at += dur + 0.0001
    out.append(("scenario.round", None, t, at - t, {"round": r}))
    return out, at


def a_run(t, first_round, waits):
    """A ``run()`` entered at ``t``: 3 ms under ``enter``, the rounds
    with 1 ms between them, 2 ms under the first ``exit``, a closing
    evaluation of 0.25 s and a second ``exit`` of 0.1 ms."""
    out = [("scenario.run.enter", None, t + 0.0001, 0.003, None)]
    at = t + 0.0032
    for i, wait in enumerate(waits):
        spans, at = a_round(first_round + i, at, wait)
        out += spans
        at += 0.001
    out.append(("scenario.run.exit", None, at, 0.002, None))
    at += 0.0021
    out += [("scenario.evaluate.device", None, at + 0.001, 0.248, None),
            ("scenario.evaluate", None, at, 0.25, None),
            ("scenario.run.exit", None, at + 0.2501, 0.0001, None)]
    end = at + 0.2503
    out.append(("scenario.run", None, t, end - t,
                {"rounds": len(waits), "start_round": first_round}))
    return out


@pytest.fixture
def ring():
    """Set-up's ``run()`` (rounds 0-2, before any profiler: spans of an
    enabled tracer here) and the window's (rounds 3 and 4): round 4
    waits 1.6 s for 0.2, and the watch saw 1.4 s of it stand still, and
    30 ms more between the two rounds."""
    ring = a_run(100.0, 0, [0.2, 0.2, 0.2]) + a_run(200.0, 3, [0.2, 1.6])
    (wait4,) = [s for s in ring if s[0] == "scenario.wait" and s[3] == 1.6]
    ring.append(("host.stall", "watch", wait4[2] + 0.1, 1.4,
                 {"run_delay_s": 0.001, "nivcsw": 1}))
    (round4,) = [s for s in ring if s[0] == "scenario.round"
                 and s[4]["round"] == 4]
    ring.append(("host.stall", "watch", round4[2] - 0.0009, 0.0008, None))
    # one that set-up's run() met: no window's
    ring.append(("host.stall", "watch", 100.5, 5.0, None))
    return ring


def parents(ring):
    """The ring as the program before PR 43 leaves it."""
    return [s for s in ring if not s[0].startswith(NEW_NAMES)]


def test_overlap_of_intervals():
    assert hostspans.overlap_s([(0.0, 1.0)], [(0.5, 1.0)]) == pytest.approx(0.5)
    assert hostspans.overlap_s([(0.0, 1.0)], [(1.0, 1.0)]) == 0.0
    assert hostspans.overlap_s([(0.0, 4.0)], [(1.0, 1.0), (3.0, 0.5)]) \
        == pytest.approx(1.5)
    assert hostspans.overlap_s([], [(1.0, 1.0)]) == 0.0


def test_the_names_are_the_programs():
    assert hostspans.STALL == obs_trace.STALL_SPAN == "host.stall"
    assert hostspans.RUN == scenario.SPAN_RUN == "scenario.run"
    assert (hostspans.LOG_METRICS, hostspans.LOG_RESOURCES,
            hostspans.LOG_WRITE) == (
        scenario.SPAN_LOG_METRICS, scenario.SPAN_LOG_RESOURCES,
        scenario.SPAN_LOG_WRITE) == (
        "scenario.log.metrics", "scenario.log.resources",
        "scenario.log.write")
    assert (hostspans.ROUND_PROGRAM, hostspans.EVAL_PROGRAM) == (
        transport.ROUND_PROGRAM, transport.EVAL_PROGRAM)


def test_a_stalled_round_reads_as_a_frozen_process(ring):
    # the stall inside round 4's wait, over the window's two rounds
    assert hostspans.stall_s_in_wait_per_round(ring, 3) == pytest.approx(0.7)
    # mean wait 0.9 less the median of the two, 0.9: nothing; over all
    # five rounds the excess is the stall's
    assert hostspans.wait_over_median_s_per_round(ring, 3) \
        == pytest.approx(0.0)
    assert hostspans.wait_over_median_s_per_round(ring, 0) \
        == pytest.approx(1.4 / 5)
    # the longest inside the window's run(): not set-up's 5 s
    assert hostspans.longest_stall_s(ring, 1) == pytest.approx(1.4)
    quiet = [s for s in ring if s[0] != "host.stall"]
    assert hostspans.stall_s_in_wait_per_round(quiet, 3) == 0.0
    assert hostspans.longest_stall_s(quiet, 1) == 0.0


def test_run_outside_its_rounds(ring):
    # enter 3.2 ms, 1 ms after each round, exit 2.1 ms up to the closing
    # evaluation; what follows that is not round_s's
    assert hostspans.run_outside_rounds_s(ring) == pytest.approx(
        0.0032 + 2 * 0.001 + 0.0021)
    # a run() whose last round evaluated has no closing evaluation
    no_closing = [s for s in ring if not s[0].startswith("scenario.eval")]
    assert hostspans.run_outside_rounds_s(no_closing) == pytest.approx(
        0.0032 + 2 * 0.001 + 0.0021 + 0.2503)


def test_the_parts_of_the_log(ring):
    part = hostspans.log_part_s_per_round
    assert part(ring, 3, hostspans.LOG_METRICS) == pytest.approx(0.001)
    assert part(ring, 3, hostspans.LOG_RESOURCES) == pytest.approx(0.003)
    assert part(ring, 3, hostspans.LOG_WRITE) == pytest.approx(0.0005)
    assert part(ring, 9, hostspans.LOG_WRITE) is None


SPAN_READERS = {
    "driver.stall_s_in_wait_per_round": 0.7,
    "driver.wait_over_median_s_per_round": 0.0,
    "driver.longest_stall_s": 1.4,
    "driver.run_outside_rounds_s": 0.0032 + 2 * 0.001 + 0.0021,
    "driver.log_metrics_s_per_round": 0.001,
    "driver.log_resources_s_per_round": 0.003,
    "driver.log_write_s_per_round": 0.0005,
}


@pytest.fixture
def planted():
    """Puts a ring into the process tracer for a reader to find."""
    tracer = obs_trace.get_tracer()
    tracer.reset()

    def plant(ring):
        tracer.reset()
        tracer._events.extend(ring)

    yield plant
    tracer.reset()


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_reader_on_a_stalled_ring_and_on_the_parents(
        metric, ring, planted):
    reader = load_module(HERE.parent / "readers" / f"{metric}.py",
                         "bench_reader")
    ctx = {"first_round": 3, "evals": 1, "rounds": 2}
    planted(ring)
    assert reader.read(ctx) == pytest.approx(SPAN_READERS[metric], abs=1e-9)
    planted(parents(ring))
    assert reader.read(ctx) is None
    planted([])
    assert reader.read(ctx) is None


def test_counter_readers_read_the_programs_record(monkeypatch):
    def read(metric):
        return load_module(HERE.parent / "readers" / f"{metric}.py",
                           "bench_reader").read({})

    record = {transport.ROUND_PROGRAM: {"s": 30.5, "traces": 1,
                                        "compile_s": 2.0},
              transport.EVAL_PROGRAM: {"s": 4.25, "traces": 1}}
    monkeypatch.setattr(obs_trace, "trace_lower_by_function",
                        lambda: record)
    assert read("entry.round_trace_lower_s") == 30.5
    assert read("entry.eval_trace_lower_s") == 4.25
    assert read("entry.round_traces") == 1
    # a process that never traced them, and a program without the record
    monkeypatch.setattr(obs_trace, "trace_lower_by_function", dict)
    assert read("entry.round_trace_lower_s") is None
    monkeypatch.delattr(obs_trace, "trace_lower_by_function")
    for metric in ("entry.round_trace_lower_s", "entry.eval_trace_lower_s",
                   "entry.round_traces"):
        assert read(metric) is None


def test_a_program_without_the_names_reads_nothing(ring, monkeypatch):
    """The parent's program has no constant to look up: whatever its
    ring holds, nothing is read."""
    for name in ("STALL", "RUN", "LOG_METRICS", "ROUND_PROGRAM"):
        monkeypatch.setattr(hostspans, name, None)
    assert hostspans.stall_s_in_wait_per_round(ring, 3) is None
    assert hostspans.wait_over_median_s_per_round(ring, 3) is None
    assert hostspans.longest_stall_s(ring, 1) is None
    assert hostspans.run_outside_rounds_s(ring) is None
    assert hostspans.log_part_s_per_round(ring, 3, hostspans.LOG_METRICS) \
        is None
    assert hostspans.trace_lower_of(hostspans.ROUND_PROGRAM) is None
