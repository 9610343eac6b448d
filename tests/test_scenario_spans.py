"""The SPMD driver's own spans: where a round's and an evaluation's host
work goes (``scenario.*``), what starts them recording (``P2PFL_TRACE``
or a live ``jax.profiler`` session), the since-process-start seconds of
set-up, and the named scopes of the device work."""

import contextlib
import glob
import os
import re
import sys
import threading
import time

import jax
import numpy as np
import pytest

from p2pfl_tpu.config.schema import DataConfig, ScenarioConfig, TrainingConfig
from p2pfl_tpu.federation import scenario as scenario_module
from p2pfl_tpu.federation.events import Events
from p2pfl_tpu.federation.scenario import Scenario
from p2pfl_tpu.obs import trace as obs_trace
from p2pfl_tpu.obs.trace import NULL_SPAN
from p2pfl_tpu.parallel import transport

ROUND_CHILDREN = {"scenario.plan", "scenario.dispatch", "scenario.wait",
                  "scenario.fetch", "scenario.log", "scenario.status"}


def toy_config(aggregator="fedavg", **kw):
    # eval_every=0: the one evaluation is run()'s closing one, outside
    # any round
    return ScenarioConfig(
        name="spans", n_nodes=4, aggregator=aggregator,
        data=DataConfig(dataset="mnist", samples_per_node=100),
        training=TrainingConfig(rounds=2, epochs_per_round=1,
                                learning_rate=0.05, eval_every=0), **kw)


@pytest.fixture(scope="module")
def toy():
    sc = Scenario(toy_config())
    sc.run(rounds=1)  # compile outside the tests
    yield sc
    sc.close()


@pytest.fixture
def tracer(monkeypatch):
    """The process tracer, off and empty, with ``P2PFL_TRACE`` unset; put
    back as it was."""
    monkeypatch.delenv(obs_trace.ENV_VAR, raising=False)
    tr = obs_trace.get_tracer()
    was = tr.enabled
    tr.configure(enabled=False)
    tr.reset()
    yield tr
    tr.configure(enabled=was)
    tr.reset()


def inside(parent, spans):
    """The driver's spans (the watch's lie on a lane of their own)
    within ``parent``'s interval, in order of start."""
    lo, hi = parent[2], parent[2] + parent[3]
    return sorted((s for s in spans if s is not parent and s[1] is None
                   and s[2] >= lo and s[2] + s[3] <= hi),
                  key=lambda s: s[2])


def children(parent, spans):
    """Those of them directly inside ``parent``: in no other."""
    kids = inside(parent, spans)
    return [k for k in kids
            if not any(k in inside(other, kids) for other in kids)]


def named(spans, name):
    return [s for s in spans if s[0] == name]


def test_round_span_is_the_parent_of_the_rounds_host_work(toy, tracer):
    tracer.configure(enabled=True)
    start = int(np.asarray(toy.fed.round))
    # run() applies the environment's convention: unset leaves the
    # tracer as this process set it
    toy.run(rounds=2)
    assert tracer.enabled
    spans = tracer.spans()
    rounds = [s for s in spans if s[0] == "scenario.round"]
    assert [s[4]["round"] for s in rounds] == [start, start + 1]
    for parent in rounds:
        kids = children(parent, spans)
        assert {k[0] for k in kids} == ROUND_CHILDREN
        assert [k[0] for k in kids][:4] == [
            "scenario.plan", "scenario.dispatch", "scenario.wait",
            "scenario.fetch"]
        for a, b in zip(kids, kids[1:]):
            assert a[2] + a[3] <= b[2], f"{a[0]} overlaps {b[0]}"
        self_s = parent[3] - sum(k[3] for k in kids)
        assert 0.0 <= self_s < parent[3]
    # nothing of the program's may pass for the benchmark's own
    assert not [s for s in spans if s[0].startswith("bench.")]


def test_evaluate_yields_its_three_spans(toy, tracer):
    tracer.configure(enabled=True)
    toy.evaluate()
    (whole,) = [s for s in tracer.spans() if s[0] == "scenario.evaluate"]
    kids = inside(whole, tracer.spans())
    assert [k[0] for k in kids] == ["scenario.evaluate.device",
                                    "scenario.evaluate.fetch"]
    assert kids[0][2] + kids[0][3] <= kids[1][2]


@pytest.mark.parametrize("prefetch", ["off", "stream"])
def test_cross_device_rounds_carry_the_same_spans(tracer, prefetch):
    from p2pfl_tpu.federation.scenario import CrossDeviceScenario

    sc = CrossDeviceScenario(ScenarioConfig.from_dict({
        "name": "cd", "n_nodes": 4,
        "data": {"dataset": "mnist", "samples_per_node": 20},
        "training": {"rounds": 2, "eval_every": 0},
        "cross_device": {"n_clients": 64, "clients_per_round": 8,
                         "cohort_size": 2, "prefetch": prefetch},
    }))
    tracer.configure(enabled=True)
    sc.run()
    sc.close()
    spans = tracer.spans()
    rounds = [s for s in spans if s[0] == "scenario.round"]
    assert [s[4]["round"] for s in rounds] == [0, 1]
    for parent in rounds:
        kids = children(parent, spans)
        assert [k[0] for k in kids] == [
            "scenario.plan", "scenario.dispatch", "scenario.wait",
            "scenario.fetch", "scenario.log"]
        assert [k[0] for k in children(kids[-1], spans)] == [
            scenario_module.SPAN_LOG_METRICS]
    assert [s[0] for s in spans].count("scenario.evaluate") == 1
    # the root, what comes before the first round and after the last
    (run,) = named(spans, scenario_module.SPAN_RUN)
    assert run[4] == {"rounds": 2, "start_round": 0}
    assert [k[0] for k in children(run, spans)] == [
        scenario_module.SPAN_RUN_ENTER, "scenario.round", "scenario.round",
        "scenario.evaluate", scenario_module.SPAN_RUN_EXIT]
    assert _watch_threads() == []


def test_run_span_is_the_root_of_what_run_does(toy, tracer):
    tracer.configure(enabled=True)
    start = int(np.asarray(toy.fed.round))
    toy.run(rounds=2)
    spans = tracer.spans()
    (run,) = named(spans, scenario_module.SPAN_RUN)
    assert run[4] == {"rounds": 2, "start_round": start}
    kids = children(run, spans)
    assert [k[0] for k in kids] == [
        scenario_module.SPAN_RUN_ENTER, "scenario.round", "scenario.round",
        scenario_module.SPAN_RUN_EXIT, "scenario.evaluate",
        scenario_module.SPAN_RUN_EXIT]
    # every round and the closing evaluation, and nothing of them outside
    assert [k for k in kids if k[0] == "scenario.round"] == named(
        spans, "scenario.round")
    assert [k for k in kids if k[0] == "scenario.evaluate"] == named(
        spans, "scenario.evaluate")
    enter, first = kids[0], kids[1]
    assert first[4] == {"round": start}
    assert 0.0 <= first[2] - (enter[2] + enter[3]) < 0.05
    assert enter[2] - run[2] < 0.05 and kids[-1][3] < 0.05


def test_the_parts_of_scenario_log_lie_inside_it(toy, tracer):
    tracer.configure(enabled=True)
    toy.run(rounds=2)
    spans = tracer.spans()
    logs = named(spans, "scenario.log")
    assert len(logs) == 4  # two a round
    parts = [[k[0] for k in children(log, spans)] for log in logs]
    assert parts == 2 * [[scenario_module.SPAN_LOG_METRICS],
                         [scenario_module.SPAN_LOG_RESOURCES,
                          scenario_module.SPAN_LOG_WRITE]]
    for log in logs:
        kids = children(log, spans)
        assert 0.0 <= log[3] - sum(k[3] for k in kids) < 0.05
    # they are the logs' and not the round's: its direct children are
    # the names they were
    for parent in named(spans, "scenario.round"):
        assert {k[0] for k in children(parent, spans)} == ROUND_CHILDREN


def _watch_threads():
    return [t for t in threading.enumerate()
            if t.name == "p2pfl-stall-watch"]


def test_off_and_no_profiler_records_nothing(toy, tracer, monkeypatch):
    """... and starts no thread and opens nothing under ``/proc``: the
    driver's untraced runs must not see the watch."""
    assert tracer.span("scenario.round", args={"round": 0}) is NULL_SPAN
    assert tracer.watch() is NULL_SPAN
    threads, opened = [], []
    real_open = os.open

    def spying_open(path, *args, **kwargs):
        opened.append(str(path))
        return real_open(path, *args, **kwargs)

    def no_counters():
        raise AssertionError("the watch's reader was made")

    monkeypatch.setattr(os, "open", spying_open)
    monkeypatch.setattr(obs_trace, "_thread_counters", no_counters)
    before = set(threading.enumerate())
    toy.add_observer(lambda event, payload: threads.append(
        set(threading.enumerate()) - before))
    try:
        toy.run(rounds=1)
    finally:
        toy._observers.pop()
    assert threads and not any(threads)
    assert not [p for p in opened if p.startswith("/proc")]
    assert tracer.spans() == []
    assert tracer.enabled is False


def test_the_watch_lives_as_long_as_run_does(toy, tracer):
    tracer.configure(enabled=True)
    seen = []
    toy.add_observer(lambda event, payload: seen.append(
        (event, len(_watch_threads()))))
    try:
        toy.run(rounds=1)
    finally:
        toy._observers.pop()
    assert {n for _, n in seen} == {1}
    assert seen[-1][0] is Events.LEARNING_FINISHED
    assert _watch_threads() == []

    def failing(event, payload):
        if event is Events.AGGREGATION_FINISHED:
            raise RuntimeError("an observer fails mid-round")

    tracer.reset()
    toy.add_observer(failing)
    try:
        with pytest.raises(RuntimeError):
            toy.run(rounds=1)
    finally:
        toy._observers.pop()
    assert _watch_threads() == []
    # the root closed over the failure, and so did what was open in it
    names = [s[0] for s in tracer.spans()]
    assert names[-3:] == ["scenario.round", scenario_module.SPAN_RUN_EXIT,
                          scenario_module.SPAN_RUN]


def test_a_stall_during_run_lands_in_the_ring_beside_the_wait(
        toy, tracer, monkeypatch):
    """Every thread of the process made to stand still inside one
    round's wait for the device (the interpreter's lock held and not
    handed over, as a pause of the machine would hold them): the ring
    has a ``host.stall`` on the watch's lane that overlaps that round's
    ``scenario.wait``."""
    tracer.configure(enabled=True)
    real = jax.block_until_ready

    def wait_after_a_pause(x):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.12:
            pass
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", wait_after_a_pause)
    handed_over = sys.getswitchinterval()
    sys.setswitchinterval(10.0)
    try:
        toy.run(rounds=1)
    finally:
        sys.setswitchinterval(handed_over)
    spans = tracer.spans()
    (wait,) = named(spans, "scenario.wait")
    stalls = named(spans, obs_trace.STALL_SPAN)
    assert stalls and {s[1] for s in stalls} == {obs_trace.STALL_LANE}
    overlap = sum(max(0.0, min(s[2] + s[3], wait[2] + wait[3])
                      - max(s[2], wait[2])) for s in stalls)
    assert overlap >= 0.05


def test_the_programs_names_are_the_transports(toy, tracer):
    """``trace_lower_by_function()`` files the two programs' tracing
    under the names ``parallel/transport.py`` states."""
    assert toy._round_fn.__name__ == transport.ROUND_PROGRAM
    assert toy._eval_fn.__name__ == transport.EVAL_PROGRAM
    by_function = obs_trace.trace_lower_by_function()
    for program in (transport.ROUND_PROGRAM, transport.EVAL_PROGRAM):
        assert by_function[program]["traces"] >= 1
        assert by_function[program]["s"] > 0.0
    assert sum(f["s"] for f in by_function.values()) == pytest.approx(
        obs_trace.trace_lower_seconds())


def test_configure_from_env_zero_still_switches_off(tracer):
    tracer.configure(enabled=True)
    assert obs_trace.configure_from_env(env={}).enabled is True
    assert obs_trace.configure_from_env(
        env={obs_trace.ENV_VAR: ""}).enabled is True
    assert obs_trace.configure_from_env(
        env={obs_trace.ENV_VAR: "0"}).enabled is False


def test_a_profiler_session_fills_the_ring_and_the_xplane(
        toy, tracer, tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        toy.run(rounds=2)
    finally:
        jax.profiler.stop_trace()
    assert tracer.enabled is False  # the session alone made it record
    in_ring = [s[0] for s in tracer.spans()]
    assert in_ring.count("scenario.round") == 2
    assert in_ring.count("scenario.evaluate") == 1
    # with the session over, spans are free again
    assert tracer.span("scenario.round") is NULL_SPAN

    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    host = [ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    for name in ROUND_CHILDREN | {"scenario.round", "scenario.evaluate",
                                  "scenario.evaluate.device"}:
        assert host.count(name) == in_ring.count(name) > 0, name
    assert not [n for n in host if n.startswith("bench.")]


def test_profile_dir_traces_a_whole_round(tracer, tmp_path):
    from jax.profiler import ProfileData

    sc = Scenario(toy_config(profile_dir=str(tmp_path)))
    sc.run()
    sc.close()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    host = [ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    # one steady round, host work and all, under its parent
    assert host.count("scenario.round") == 1
    assert host.count("scenario.log") == 2


def test_setup_seconds_outlive_the_counter_reset(tracer):
    def snapshot():
        return (obs_trace.stage_seconds(), obs_trace.trace_lower_seconds(),
                obs_trace.cache_load_seconds())

    # a second build of the same programs finds them in the persistent
    # cache (tests/conftest.py turns it on), once jax forgets the first
    Scenario(toy_config()).close()
    jax.clear_caches()
    stages0, lower0, load0 = snapshot()
    t0 = time.perf_counter()
    Scenario(toy_config()).close()
    wall = time.perf_counter() - t0
    stages, lower, load = snapshot()
    for name in ("scenario.init.data", "scenario.init.build",
                 "scenario.init.federation"):
        assert stages[name] > stages0[name] > 0.0
    # a jit traced inside a jit's trace reports twice: counted once
    assert wall > lower - lower0 > 0.0 and lower0 > 0.0
    assert load > load0

    obs_trace.reset_xla_counters()
    assert snapshot() == (stages, lower, load)
    assert obs_trace.xla_recompiles() == 0

    # tracing outside the program's calls is somebody else's
    jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
    assert obs_trace.trace_lower_seconds() == lower


@contextlib.contextmanager
def _no_scope(name):
    yield


@pytest.mark.parametrize("aggregator,expected", [
    ("fedavg", ("fit.value_and_grad", "fit.optimizer_update",
                "exchange.mix")),
    ("krum", ("fit.value_and_grad", "fit.optimizer_update",
              "krum.gram", "krum.select")),
])
def test_named_scopes_are_in_the_lowered_round(aggregator, expected):
    sc = Scenario(toy_config(aggregator))
    text = sc._round_fn.lower(
        sc.fed, *sc._data_args, *sc._plan_args()).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    for scope in expected:
        assert any(scope + "/" in n for n in names), scope
    # flax names its modules itself: the model's layers are there once,
    # under the step's scope, and no scope of ours repeats them
    assert any("fit.value_and_grad/jvp(MLP)/Dense_0/" in n for n in names)
    assert not any(n.count("Dense_0/") > 1 for n in names)
    ev = sc._eval_fn.lower(
        sc.fed, sc._x_test, sc._y_test).as_text(debug_info=True)
    assert "eval.forward/MLP/Dense_0/" in ev
    sc.close()


def test_named_scopes_change_no_bit(monkeypatch):
    def one_round():
        sc = Scenario(toy_config())
        text = sc._round_fn.lower(
            sc.fed, *sc._data_args, *sc._plan_args()).as_text(debug_info=True)
        sc.run(rounds=1)
        out = jax.tree.map(np.asarray, sc.fed.states.params)
        sc.close()
        return "exchange.mix/" in text, out

    scoped, with_scopes = one_round()
    monkeypatch.setattr(jax, "named_scope", _no_scope)
    unscoped, without = one_round()
    assert scoped and not unscoped
    jax.tree.map(np.testing.assert_array_equal, with_scopes, without)
