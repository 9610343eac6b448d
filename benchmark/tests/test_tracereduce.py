"""The reduction from trace events to busy/idle, self times, time by
scope, named idle gaps and collective exposure, on a small recorded
trace (``data/small_trace.json``: the plain events ``tracereduce.load``
gives, cut from a two-device run; times in seconds), on recorded chip
traces, and the reading of an ``.xplane.pb`` itself."""

import gzip
import importlib.util
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import tracereduce  # noqa: E402


def events_of(raw):
    return {"device": {k: [tuple(e) for e in v] for k, v in raw["device"].items()},
            "host": [tuple(e) for e in raw["host"]]}


def recorded(name):
    return events_of(json.loads(gzip.open(HERE / "data" / name, "rt").read()))


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_reader", HERE.parent / "readers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def events():
    return events_of(json.loads((HERE / "data" / "small_trace.json").read_text()))


@pytest.fixture(scope="module")
def harness_only(events):
    """The same trace as a program without spans of its own leaves it."""
    return {"device": events["device"],
            "host": [e for e in events["host"] if e[0].startswith("bench.")]}


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


def test_idle_gaps_are_named_by_host_span(harness_only):
    r = tracereduce.reduce(harness_only)
    gaps = dict(r["idle_gaps"])
    # host: round [0,4.5], post_round [4.5,5], round [5,7.5], evaluate [7.5,10]
    # dev0 idle: [0,1] round, [4,5] .5 round .5 post, [6,8] 1.5 round .5 eval, [9,10] eval
    # dev1 idle: [0,1] round, [4,5] .5 round .5 post, [7,10] .5 round 2.5 eval
    assert gaps["bench.round"] == pytest.approx((3.0 + 2.0) / 2)
    assert gaps["bench.post_round"] == pytest.approx(0.5)
    assert gaps["bench.evaluate"] == pytest.approx((1.5 + 2.5) / 2)
    assert gaps.get("outside_any_span", 0.0) == pytest.approx(0.0, abs=1e-9)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # the stretch bench.part.rounds is [0,7.5]
    assert r["part_s"]["rounds"] == pytest.approx(7.5)
    assert r["idle_in_part_s"]["rounds"] == pytest.approx((3.5 + 2.5) / 2)


def test_idle_gaps_go_to_the_innermost_program_span(events):
    r = tracereduce.reduce(events)
    gaps = dict(r["idle_gaps"])
    # scenario.wait [0.5,4.2] lies in the first bench.round, scenario.log
    # [4.6,4.9] in bench.post_round, scenario.evaluate.device [7.5,9.5] in
    # bench.evaluate; each piece of the window goes to the span that
    # began last among those that cover it
    # dev0: [0,1] .5 round .5 wait; [4,5] .2 wait .3 round .2 post .3 log;
    #       [6,8] 1.5 round .5 eval.device; [9,10] .5 eval.device .5 evaluate
    # dev1: [0,1], [4,5] the same; [7,10] .5 round, 2 eval.device, .5 evaluate
    assert gaps["scenario.wait"] == pytest.approx(0.7)
    assert gaps["scenario.log"] == pytest.approx(0.3)
    assert gaps["scenario.evaluate.device"] == pytest.approx((1.0 + 2.0) / 2)
    assert gaps["bench.round"] == pytest.approx((2.3 + 1.3) / 2)
    assert gaps["bench.post_round"] == pytest.approx(0.2)
    assert gaps["bench.evaluate"] == pytest.approx(0.5)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # the stretches are the harness's and read what they read without the spans
    assert r["idle_in_part_s"]["rounds"] == pytest.approx((3.5 + 2.5) / 2)


def test_idle_gaps_beyond_the_tenth_are_summed(events):
    r = tracereduce.reduce(events, top=3)
    names = [n for n, _ in r["idle_gaps"]]
    assert len(names) == 3 and names[-1] == tracereduce.OTHER
    assert sum(t for _, t in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_self_time_by_scope(events):
    r = tracereduce.reduce(events)
    by = r["scope_s"]
    assert by["jit(round_fn)/while"] == pytest.approx((1.0 + 3.0) / 2)
    assert by["jit(round_fn)/while/body/fit.value_and_grad/dot_general"] \
        == pytest.approx(1.0)
    # the layout copy carries no scope: a row of its own
    assert by[tracereduce.UNSCOPED] == pytest.approx(0.9 / 2)
    # all-reduce.2 [5,7] holds fusion.9 [5,6]: 1 s its own
    assert by["jit(round_fn)/exchange.mix/psum"] == pytest.approx(1.0 / 2)
    # every op once: the rows sum to the busy time
    assert sum(by.values()) == pytest.approx(r["busy_s"])
    # a name is matched as a whole component: exchange.mixer is another
    assert tracereduce.scope_seconds(r, "exchange.mix") == pytest.approx(1.05)
    assert tracereduce.scope_seconds(
        r, "exchange.mix", "fit.value_and_grad") == pytest.approx(2.05)
    assert tracereduce.scope_seconds(r, "krum.gram") is None


def test_events_without_scope_are_unscoped(harness_only):
    """Events recorded before scopes were kept are triples; executables
    from a cache that an unscoped program filled read the same way."""
    bare = {"device": {k: [e[:3] for e in v]
                       for k, v in harness_only["device"].items()},
            "host": harness_only["host"]}
    r = tracereduce.reduce(bare)
    assert r["scope_s"] == {tracereduce.UNSCOPED: pytest.approx(r["busy_s"])}
    ctx = {"trace": r, "rounds": 2}
    assert reader("exchange.device_s_per_round").read(ctx) is None


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
    r = tracereduce.reduce(recorded("recorded_trace.json.gz"))
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(0.41447, abs=1e-4)
    # one long program a round: the device is idle ~2% of the two rounds
    assert 0.97 < r["busy_s"] / r["window_s"] < 0.99
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert gaps["bench.round"] > gaps["outside_any_span"]
    ops = dict(r["device_ops"])
    # self times never exceed the busy time, and the layout copy of conv1's
    # patches is among the costliest ops
    assert sum(ops.values()) <= r["busy_s"] + 1e-9
    assert any(n.startswith("%copy.199") for n in ops)
    assert r["collective_s"] == 0.0
    # recorded before ops kept their scope
    assert set(r["scope_s"]) == {tracereduce.UNSCOPED}


def test_recorded_scoped_cnn_trace():
    """Two rounds and the closing evaluation of ``femnist-cnn.dfl64-full``
    cut from a traced run on the TPU v5e from an empty cache directory
    (PR 34): 2196 device ops with the scope XLA kept for each, the
    program's ``scenario.*`` spans beside the harness's."""
    r = tracereduce.reduce(recorded("recorded_scoped_cnn.json.gz"))
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(0.37206, abs=1e-4)
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # the host's work shows under the program's own spans
    assert gaps["scenario.log"] > 0 and gaps["scenario.wait"] > 0
    assert sum(t for n, t in gaps.items() if n.startswith("scenario.")) \
        > 0.9 * sum(gaps.values())
    by = r["scope_s"]
    assert sum(by.values()) == pytest.approx(r["busy_s"])
    # XLA's own layout copies carry no scope, and are a few per cent
    assert 0.01 < by[tracereduce.UNSCOPED] / r["busy_s"] < 0.10
    fit = tracereduce.scope_seconds(r, "fit.value_and_grad")
    mix = tracereduce.scope_seconds(r, "exchange.mix")
    # two rounds of 117 ms beside one evaluation of 136 ms
    assert fit > 0.4 * r["busy_s"]
    assert tracereduce.scope_seconds(r, "eval.forward") > 0.1
    assert tracereduce.scope_seconds(r, "krum.gram") is None
    # the mixing contraction: 4.2 ms of each 117 ms round
    per_round = reader("exchange.device_s_per_round").read(
        {"trace": r, "rounds": 2})
    assert per_round == pytest.approx(mix / 2)
    assert 0.003 < per_round < 0.006


def test_recorded_scoped_vit_trace():
    """One round of ``vit-tiny.dfl32-full-krum`` from the same kind of
    run (PR 34): 13064 device ops; Krum's Gram matrix over 32 flattened
    models under ``krum.gram``, the pick under ``krum.select``."""
    r = tracereduce.reduce(recorded("recorded_scoped_vit.json.gz"))
    assert r["busy_s"] / r["window_s"] > 0.99
    by = r["scope_s"]
    assert sum(by.values()) == pytest.approx(r["busy_s"])
    assert tracereduce.scope_seconds(r, "exchange.mix") is None
    gram = tracereduce.scope_seconds(r, "krum.gram")
    pick = tracereduce.scope_seconds(r, "krum.select")
    assert gram > 100 * pick > 0
    per_round = reader("exchange.device_s_per_round").read(
        {"trace": r, "rounds": 1})
    assert per_round == pytest.approx(gram + pick)
    assert 0.005 < per_round < 0.015  # 9 ms of a 1.19 s round


# --------------------------------------------------------------------------
# the .xplane.pb itself


def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    """One protobuf field: an int as a varint, bytes length-delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def metadata_entry(ident, name, stats=b""):
    return field(1, ident) + field(2, field(1, ident) + field(2, name) + stats)


def test_load_reads_scopes_and_program_spans(tmp_path):
    """A hand-made XSpace: a TPU plane whose ops carry ``tf_op`` as a
    string and as a reference to a stat's name, a fixed-width stat to
    step over, an op with none; a host plane with spans of the harness,
    of the program and of neither."""
    stat_names = (field(5, metadata_entry(1, "tf_op"))
                  + field(5, metadata_entry(2, "flops"))
                  + field(5, metadata_entry(3, "jit(f)/krum.gram/dot_general:")))
    ops = (field(4, metadata_entry(
               1, "%fusion.1", field(5, field(1, 2) + b"\x11" + b"\0" * 8)
               + field(5, field(1, 1) + field(5, "jit(f)/exchange.mix/dot_general:MatMul"))))
           + field(4, metadata_entry(2, "%fusion.2", field(5, field(1, 1) + field(7, 3))))
           + field(4, metadata_entry(3, "%copy.3")))
    event = lambda ident, offset_ps, dur_ps: field(
        4, field(1, ident) + field(2, offset_ps) + field(3, dur_ps))
    line = field(3, field(2, "XLA Ops") + field(3, 2_000_000_000)
                 + event(1, 0, 500_000_000) + event(2, 10**12, 250_000_000)
                 + event(3, 2 * 10**12, 10**9))
    other = field(3, field(2, "Steps") + field(3, 0) + event(1, 0, 10**12))
    device = field(1, field(2, "/device:TPU:0") + stat_names + ops + line + other)
    spans = (field(4, metadata_entry(1, "bench.window"))
             + field(4, metadata_entry(2, "scenario.wait"))
             + field(4, metadata_entry(3, "PjitFunction(round_fn)")))
    host = field(1, field(2, "/host:CPU") + spans + field(3, (
        field(2, "python3") + field(3, 1_000_000_000)
        + event(1, 0, 5 * 10**12) + event(2, 10**12, 10**12)
        + event(3, 0, 10**9))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(device + host + field(1, field(2, "/host:metadata")))
    got = tracereduce.load(path)
    assert got["device"] == {"/device:TPU:0": [
        ("%fusion.1", pytest.approx(2.0), pytest.approx(5e-4),
         "jit(f)/exchange.mix/dot_general"),
        ("%fusion.2", pytest.approx(3.0), pytest.approx(2.5e-4),
         "jit(f)/krum.gram/dot_general"),
        ("%copy.3", pytest.approx(4.0), pytest.approx(1e-3), "")]}
    assert got["host"] == [
        ("bench.window", pytest.approx(1.0), pytest.approx(5.0)),
        ("scenario.wait", pytest.approx(2.0), pytest.approx(1.0))]
    r = tracereduce.reduce(got)
    assert tracereduce.scope_seconds(r, "exchange.mix", "krum.gram") \
        == pytest.approx(7.5e-4)
    # fusion.1 runs for 0.5 ms inside the wait
    assert dict(r["idle_gaps"])["scenario.wait"] == pytest.approx(1.0 - 5e-4)


def test_load_agrees_with_the_profiler_own_reader(tmp_path):
    """The same file through ``jax.profiler.ProfileData``: every host
    event's name, start and duration."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("scenario.round", round=3):
            jax.jit(lambda a: (a @ a).sum())(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    path = tracereduce.find_xplane(tmp_path)
    want = sorted(
        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith(tracereduce.SPAN_PREFIXES))
    got = sorted(tracereduce.load(path)["host"])
    assert [n for n, _, _ in got] == ["bench.window", "scenario.round"]
    assert len(got) == len(want)
    for (n, s, d), (wn, ws, wd) in zip(got, want):
        assert n == wn and s == pytest.approx(ws, abs=2e-9) \
            and d == pytest.approx(wd, abs=2e-9)
