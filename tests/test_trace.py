"""Tracing subsystem (round 9): disabled-path freeness, span/counter
semantics, Chrome trace-event export schema, the multi-process merge,
and the XLA recompile counter."""

import json
import threading

import pytest

from p2pfl_tpu.obs import trace as obs_trace
from p2pfl_tpu.obs.trace import NULL_SPAN, Tracer
from p2pfl_tpu.obs import traceview


# ---------------------------------------------------------------------
# disabled fast path
# ---------------------------------------------------------------------

def test_disabled_span_is_shared_null_singleton():
    """The no-op fast path must not allocate per call: every disabled
    span() returns the ONE module-level NULL_SPAN instance."""
    tr = Tracer()
    assert tr.enabled is False
    a = tr.span("p2p.verify", lane="node0", args={"x": 1})
    b = tr.span("node.round")
    assert a is NULL_SPAN and b is NULL_SPAN
    with a:
        pass
    assert tr.spans() == []


def test_disabled_counters_and_gauges_record_nothing():
    tr = Tracer()
    tr.count("rx_bytes/peer0", 1024)
    tr.high_water("send_q_depth/peer0", 7)
    assert tr.counters() == {} and tr.gauges() == {}


def test_null_span_swallows_nothing():
    """NULL_SPAN is a plain CM: exceptions still propagate."""
    with pytest.raises(ValueError):
        with NULL_SPAN:
            raise ValueError("boom")


# ---------------------------------------------------------------------
# enabled semantics
# ---------------------------------------------------------------------

def test_enabled_span_counter_gauge_roundtrip():
    tr = Tracer().configure(enabled=True)
    with tr.span("node.round", lane="node0", args={"round": 2}):
        with tr.span("learner.fit", lane="node0"):
            pass
    tr.count("tx_msgs/params")
    tr.count("tx_msgs/params", 2)
    tr.high_water("send_q_depth/peer1", 3)
    tr.high_water("send_q_depth/peer1", 1)  # lower: must not regress
    names = [s[0] for s in tr.spans()]
    assert names == ["learner.fit", "node.round"]  # closed-order ring
    assert tr.counters() == {"tx_msgs/params": 3}
    assert tr.gauges() == {"send_q_depth/peer1": 3}
    summary = tr.summarize()
    assert summary["node"] is None and "ts" in summary
    assert summary["spans"]["node.round"]["count"] == 1
    assert summary["spans"]["node.round"]["total_s"] >= (
        summary["spans"]["learner.fit"]["total_s"])


def test_ring_is_bounded():
    tr = Tracer(ring_max=8).configure(enabled=True)
    for i in range(50):
        with tr.span(f"s{i}"):
            pass
    spans = tr.spans()
    assert len(spans) == 8 and spans[-1][0] == "s49"


def test_thread_safety_spans_and_counters():
    tr = Tracer().configure(enabled=True)

    def work():
        for _ in range(500):
            with tr.span("t"):
                pass
            tr.count("n")

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.spans()) == 2000
    assert tr.counters() == {"n": 2000}


def test_configure_mutates_in_place_for_cached_references():
    tr = Tracer()
    cached = tr
    tr.configure(enabled=True)
    assert cached.enabled is True
    assert cached.span("x") is not NULL_SPAN


# ---------------------------------------------------------------------
# P2PFL_TRACE convention
# ---------------------------------------------------------------------

def test_configure_from_env_convention(tmp_path):
    tr = obs_trace.get_tracer()
    orig = (tr.enabled, tr.export_dir)
    try:
        assert obs_trace.configure_from_env(env={}).enabled is False
        assert obs_trace.configure_from_env(
            env={"P2PFL_TRACE": "0"}).enabled is False
        got = obs_trace.configure_from_env(
            default_dir=tmp_path / "t", env={"P2PFL_TRACE": "1"})
        assert got is tr and got.enabled is True
        assert got.export_dir == tmp_path / "t"
        got = obs_trace.configure_from_env(
            default_dir=tmp_path / "t",
            env={"P2PFL_TRACE": str(tmp_path / "elsewhere")})
        assert got.enabled is True
        assert got.export_dir == tmp_path / "elsewhere"
    finally:
        tr.configure(enabled=orig[0], export_dir=orig[1])
        tr.reset()


# ---------------------------------------------------------------------
# export schema + merge
# ---------------------------------------------------------------------

def _traced_tracer() -> Tracer:
    tr = Tracer().configure(enabled=True)
    with tr.span("node.round", lane="node0", args={"round": 0}):
        with tr.span("learner.fit", lane="node0"):
            pass
    with tr.span("session.add_model", lane="node1"):
        pass
    tr.count("rx_bytes/peer0", 512)
    return tr


def test_export_chrome_trace_schema(tmp_path):
    tr = _traced_tracer()
    path = tr.export(tmp_path / "proc1.trace.json", process_name="test")
    doc = json.loads(path.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit", "metadata"}
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    phases = {e["ph"] for e in events}
    assert phases == {"M", "X", "C"}
    metas = [e for e in events if e["ph"] == "M"]
    assert {"process_name", "thread_name"} <= {e["name"] for e in metas}
    lane_names = {e["args"]["name"] for e in metas
                  if e["name"] == "thread_name"}
    assert {"main", "node0", "node1"} <= lane_names
    for ev in events:
        assert {"name", "ph", "pid", "tid"} <= set(ev)
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
    counters = [e for e in events if e["ph"] == "C"]
    assert counters[0]["name"] == "rx_bytes/peer0"
    assert counters[0]["args"]["value"] == 512
    meta = doc["metadata"]
    assert {"wall_t0", "perf_t0", "pid", "counters", "gauges"} <= set(meta)


def test_export_default_dir_and_disabled_export(tmp_path):
    tr = Tracer()
    assert tr.export() is None  # no dir known
    tr.configure(enabled=True, export_dir=tmp_path / "trace")
    with tr.span("x"):
        pass
    path = tr.export(process_name="p")
    assert path is not None and path.parent == tmp_path / "trace"
    assert path.name.endswith(".trace.json")
    assert json.loads(path.read_text())["traceEvents"]


def test_traceview_merge_anchors_on_earliest_wall_clock(tmp_path):
    tr = _traced_tracer()
    p1 = tr.export(tmp_path / "proc1.trace.json", process_name="a")
    # second process: same events, but its tracer reset 5 s later on
    # the wall clock and under a different pid
    doc = json.loads(p1.read_text())
    doc["metadata"]["wall_t0"] += 5.0
    doc["metadata"]["pid"] = 99999
    doc["metadata"]["counters"] = {"rx_bytes/peer0": 99}
    for ev in doc["traceEvents"]:
        ev["pid"] = 99999
    p2 = tmp_path / "proc2.trace.json"
    p2.write_text(json.dumps(doc))

    merged = traceview.merge([p1, p2])
    assert merged["metadata"]["files"] == 2
    by_pid = merged["metadata"]["counters_by_pid"]
    assert by_pid["99999"] == {"rx_bytes/peer0": 99}

    def first_x(pid):
        return min(e["ts"] for e in merged["traceEvents"]
                   if e["ph"] == "X" and e["pid"] == pid)

    real_pid = json.loads(p1.read_text())["metadata"]["pid"]
    shift = first_x(99999) - first_x(real_pid)
    assert abs(shift - 5e6) < 1.0  # µs
    # merged output is itself valid trace JSON: sorted ts, M events first
    ts = [e["ts"] for e in merged["traceEvents"] if "ts" in e]
    assert ts == sorted(ts)
    assert merged["traceEvents"][0]["ph"] == "M"


def test_traceview_cli(tmp_path, capsys):
    tr = _traced_tracer()
    tr.export(tmp_path / "in" / "proc1.trace.json")
    out = tmp_path / "merged.trace.json"
    rc = traceview.main([str(tmp_path / "in"), "-o", str(out)])
    assert rc == 0
    assert json.loads(out.read_text())["metadata"]["files"] == 1
    assert "merged 1 file(s)" in capsys.readouterr().out
    assert traceview.main([str(tmp_path / "empty"), "-o", str(out)]) == 1


# ---------------------------------------------------------------------
# XLA recompile counter
# ---------------------------------------------------------------------

def test_xla_recompile_counter_fixed_vs_varying_shapes():
    """Fixed-shape re-execution hits the jit cache → 0 new compiles;
    a fresh shape forces a backend compile → counter > 0."""
    import jax
    import jax.numpy as jnp

    assert obs_trace.install_xla_listener() is True
    assert obs_trace.install_xla_listener() is True  # idempotent

    @jax.jit
    def f(x):
        return (x * 2.0).sum()

    a = jnp.ones((4,))
    b = jnp.ones((5,))
    f(a).block_until_ready()  # warm: compiles once (not asserted on)

    obs_trace.reset_xla_counters()
    f(a).block_until_ready()  # cache hit
    assert obs_trace.xla_recompiles() == 0
    assert obs_trace.xla_compile_seconds() == 0.0

    f(b).block_until_ready()  # new shape: real backend compile
    assert obs_trace.xla_recompiles() > 0
    assert obs_trace.xla_compile_seconds() > 0.0
    obs_trace.reset_xla_counters()


def test_xla_counter_mirrors_into_enabled_tracer():
    import jax
    import jax.numpy as jnp

    assert obs_trace.install_xla_listener() is True
    tr = obs_trace.get_tracer()
    orig = tr.enabled
    tr.reset()
    tr.configure(enabled=True)
    try:
        obs_trace.reset_xla_counters()

        @jax.jit
        def g(x):
            return x + 3.0

        g(jnp.ones((7,))).block_until_ready()
        assert obs_trace.xla_recompiles() > 0
        c = tr.counters()
        assert c.get("xla/backend_compiles", 0) > 0
        assert c.get("xla/backend_compile_s", 0) > 0
    finally:
        tr.configure(enabled=orig)
        tr.reset()
        obs_trace.reset_xla_counters()


# ---------------------------------------------------------------------
# the stall watch
# ---------------------------------------------------------------------

def test_watch_tick_records_a_late_wake_up_and_no_other():
    """On an injected clock: a wake-up within the threshold of the one
    meant leaves nothing; a later one leaves one ``host.stall`` dated
    back to the wake-up meant, its duration the lateness, its args the
    thread counters' difference over that sleep alone."""
    tr = Tracer()
    now = [100.0]
    seen = [{"run_delay_s": 1.0, "nivcsw": 3}]
    watch = obs_trace._StallWatch(tr, clock=lambda: now[0])
    watch._counters = lambda: dict(seen[0])
    watch.arm()
    now[0] += obs_trace.STALL_PERIOD_S + obs_trace.STALL_THRESHOLD_S * 0.9
    seen[0] = {"run_delay_s": 1.5, "nivcsw": 4}  # an on-time sleep's
    watch.tick()
    assert tr.spans() == []
    due = now[0] + obs_trace.STALL_PERIOD_S
    now[0] = due + 0.3
    seen[0] = {"run_delay_s": 1.75, "nivcsw": 6}
    watch.tick()
    ((name, lane, t0, dur, args),) = tr.spans()
    assert (name, lane) == (obs_trace.STALL_SPAN, obs_trace.STALL_LANE)
    assert not name.startswith("scenario.")  # tracereduce names gaps by those
    assert t0 == pytest.approx(due) and dur == pytest.approx(0.3)
    assert args == {"run_delay_s": pytest.approx(0.25), "nivcsw": 2}
    # a platform that counts nothing leaves the args out
    watch._counters = dict
    watch.arm()
    now[0] += 1.0
    watch.tick()
    assert tr.spans()[-1][3] == pytest.approx(1.0 - obs_trace.STALL_PERIOD_S)
    assert tr.spans()[-1][4] is None
    # summarize() and the Chrome export show it like any span, on its lane
    assert tr.summarize()["spans"][obs_trace.STALL_SPAN]["count"] == 2
    events = tr.chrome_events(pid=1)
    (tid,) = {e["tid"] for e in events if e["name"] == obs_trace.STALL_SPAN}
    assert {"name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
            "args": {"name": obs_trace.STALL_LANE}} in events


def _watch_threads():
    return [t for t in threading.enumerate()
            if t.name == "p2pfl-stall-watch"]


def test_watch_is_a_thread_only_while_the_tracer_records():
    tr = Tracer()
    assert tr.watch() is NULL_SPAN  # off, no profiler: the span() rule
    with tr.watch():
        assert _watch_threads() == []
    tr.configure(enabled=True)
    with tr.watch():
        (thread,) = _watch_threads()
        assert thread.daemon
    assert _watch_threads() == [] and not thread.is_alive()
    with pytest.raises(RuntimeError):
        with tr.watch():
            raise RuntimeError("the watched code fails")
    assert _watch_threads() == []


def test_watch_sees_a_planted_stop_of_the_process():
    """A helper stops this process (``SIGSTOP``) for 0.3 s during a
    sleep: one ``host.stall`` of 0.25 s or more overlaps the sleep, and
    where the platform counts, the watch thread was hardly runnable
    meanwhile (a stopped process, not a busy host)."""
    import os
    import signal
    import subprocess
    import sys
    import time

    if not (hasattr(signal, "SIGSTOP") and hasattr(signal, "SIGCONT")):
        pytest.skip("no SIGSTOP/SIGCONT here")
    helper = (
        "import os, signal, sys, time\n"
        "pid = int(sys.argv[1])\n"
        "time.sleep(0.3)\n"
        "os.kill(pid, signal.SIGSTOP)\n"
        "try:\n"
        "    time.sleep(0.3)\n"
        "finally:\n"
        "    os.kill(pid, signal.SIGCONT)\n")
    tr = Tracer()
    tr.configure(enabled=True)
    with tr.watch():
        proc = subprocess.Popen([sys.executable, "-c", helper,
                                 str(os.getpid())])
        t0 = time.perf_counter()
        try:
            while proc.poll() is None:  # the sleep the stop falls into
                time.sleep(0.05)
        finally:
            proc.wait(timeout=30)
        t1 = time.perf_counter()
    assert proc.returncode == 0
    long = [s for s in tr.spans()
            if s[0] == obs_trace.STALL_SPAN and s[3] >= 0.25]
    assert len(long) == 1, tr.spans()
    _, lane, at, dur, args = long[0]
    assert lane == obs_trace.STALL_LANE
    assert t0 < at and at + dur < t1 and dur < 1.0
    # stopped, the process used no CPU to speak of
    assert 0.0 <= args["cpu_s"] < 0.5 * dur
    if os.path.exists("/proc/thread-self/schedstat"):
        assert set(args) == {"cpu_s", "run_delay_s", "nivcsw"}
        assert 0.0 <= args["run_delay_s"] < dur


# ---------------------------------------------------------------------
# set-up's tracing by program
# ---------------------------------------------------------------------

def test_trace_lower_by_function_files_inner_traces_under_the_outer():
    """Two jitted functions under ``program_scope()``, one calling the
    other: the inner one's seconds lie under the outer's name, the
    records sum to ``trace_lower_seconds()``, and ``traces`` moves by 1
    each time the outer is traced anew and not on a call that finds its
    jaxpr again. Outside the scope nothing is counted."""
    import time

    import jax
    import jax.numpy as jnp

    def by_function():
        return obs_trace.trace_lower_by_function()

    def seconds(name):
        return by_function().get(name, {"s": 0.0})["s"]

    @jax.jit
    def watch_inner_fn(x):
        time.sleep(0.05)  # trace time, the inner function's own
        return x * 2.0

    @jax.jit
    def watch_outer_fn(x):
        return watch_inner_fn(x) + 1.0

    # made outside the scope: building an array traces too
    x3, x4, x5, x6 = (jnp.ones((n,)) for n in (3, 4, 5, 6))
    total0 = obs_trace.trace_lower_seconds()
    assert "watch_outer_fn" not in by_function()
    with obs_trace.program_scope():
        watch_outer_fn(x3).block_until_ready()
    assert "watch_inner_fn" not in by_function()
    outer = by_function()["watch_outer_fn"]
    assert outer["traces"] == 1 and outer["s"] >= 0.05
    assert outer["compile_s"] > 0.0  # jax 0.9 names the compile too
    assert sum(f["s"] for f in by_function().values()) == pytest.approx(
        obs_trace.trace_lower_seconds())
    assert obs_trace.trace_lower_seconds() - total0 == pytest.approx(
        outer["s"], abs=1e-6)

    with obs_trace.program_scope():
        watch_outer_fn(x3).block_until_ready()  # a cache hit
        assert by_function()["watch_outer_fn"]["traces"] == 1
        watch_outer_fn(x4).block_until_ready()  # a new shape
    again = by_function()["watch_outer_fn"]
    assert again["traces"] == 2 and again["s"] >= outer["s"] + 0.05
    assert "watch_inner_fn" not in by_function()
    assert sum(f["s"] for f in by_function().values()) == pytest.approx(
        obs_trace.trace_lower_seconds())

    # the inner one alone, as somebody's outermost function
    with obs_trace.program_scope():
        watch_inner_fn(x5).block_until_ready()
    assert by_function()["watch_inner_fn"]["traces"] == 1
    before = seconds("watch_inner_fn")
    watch_inner_fn(x6).block_until_ready()  # not the program's
    assert seconds("watch_inner_fn") == before
    obs_trace.reset_xla_counters()  # kept since the process started
    assert seconds("watch_inner_fn") == before


def test_a_lowering_that_touches_its_trace_keeps_the_trace(monkeypatch):
    """Should the two clocks' last digits make a function's lowering
    start inside its own trace, the merged stretch still counts that
    trace; another function's swallowed stretch gives its seconds and
    not its trace."""
    monkeypatch.setattr(obs_trace, "_trace_lower_spans", [])
    clock = iter([10.0, 10.5, 20.0, 20.5, 30.0, 40.0])
    monkeypatch.setattr(obs_trace.time, "perf_counter", lambda: next(clock))
    obs_trace._add_trace_lower(1.0, "f", 1)     # [9.0, 10.0]
    obs_trace._add_trace_lower(0.6, "f", 0)     # [9.9, 10.5] runs into it
    assert obs_trace._trace_lower_spans == [(9.0, 10.5, "f", 1)]
    obs_trace._add_trace_lower(1.0, "g", 1)     # [19.0, 20.0]
    obs_trace._add_trace_lower(2.0, "f", 0)     # [18.5, 20.5] holds g's
    assert obs_trace._trace_lower_spans[-1] == (18.5, 20.5, "f", 0)
    # jax reports a jaxpr found in its cache as a trace of microseconds
    # (a call that missed the dispatch fast path): seconds, no trace
    trace_event = "/jax/core/compile/jaxpr_trace_duration"
    with obs_trace.program_scope():
        obs_trace._on_xla_event(trace_event, 4.5e-5, fun_name="f")
        obs_trace._on_xla_event(trace_event, 0.5, fun_name="f")
    assert [s[3] for s in obs_trace._trace_lower_spans[-2:]] == [0, 1]
    assert obs_trace.trace_lower_by_function()["f"]["traces"] == 2
    assert obs_trace._function_of("jit(round_fn)") == "round_fn"
    assert obs_trace._function_of("round_fn") == "round_fn"
