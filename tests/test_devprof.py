"""Device-level step profiling (round 22): obs.devprof's mode gate and
phase-split fit, obs.cost_model's honest-FLOP/MFU/watermark arithmetic,
and obs.perf_report's automated "where the round went" attribution.

The phase-split parity test is the load-bearing one: step mode swaps
the fused train_epochs program for per-phase jits, so it must produce
the same parameters (same math, different fusion) AND its spans must
sum to the wrapping learner.fit span — the same <=10% closure gate
critpath pins for its components-vs-wall decomposition."""

import json

import jax
import numpy as np
import pytest

from p2pfl_tpu.config.schema import DataConfig
from p2pfl_tpu.datasets import FederatedDataset
from p2pfl_tpu.learning import JaxLearner
from p2pfl_tpu.models import get_model
from p2pfl_tpu.obs import cost_model, devprof, perf_report
from p2pfl_tpu.obs.trace import NULL_SPAN, get_tracer

US = 1_000_000  # µs per second (Chrome trace timestamps)


def _make_learner(seed=0, samples=64, batch=16):
    fed = FederatedDataset.make(
        DataConfig(dataset="mnist", samples_per_node=samples), 1)
    ln = JaxLearner(model=get_model("mnist-mlp"), data=fed.nodes[0],
                    learning_rate=0.05, seed=seed, batch_size=batch)
    ln.init()
    return ln


# ---------------------------------------------------------------------------
# mode gate + off-path cost
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("raw,expect", [
    ("", "off"), ("0", "off"), ("off", "off"),
    ("step", "step"),
    ("1", "gauges"), ("yes", "gauges"),  # any other truthy -> gauges
])
def test_mode_env_parsing(monkeypatch, raw, expect):
    monkeypatch.setenv(devprof.ENV_VAR, raw)
    assert devprof.mode() == expect
    assert devprof.enabled() == (expect != "off")
    assert devprof.step_enabled() == (expect == "step")


def test_off_path_no_allocation_and_no_gauges(monkeypatch):
    """Devprof off: the fit must leave devprof_last untouched, and a
    disabled tracer's span() must return the shared NULL_SPAN — the
    profiling plane costs one env read when nobody asked for it."""
    monkeypatch.delenv(devprof.ENV_VAR, raising=False)
    tr = get_tracer()
    assert not tr.enabled  # tier-1 default: tracing off
    assert tr.span("devprof.forward") is NULL_SPAN
    assert tr.span("devprof.backward") is tr.span("devprof.update")
    ln = _make_learner()
    ln.set_epochs(1)
    ln.fit()
    assert ln.devprof_last == {}


# ---------------------------------------------------------------------------
# step mode: phase-split parity + the phase-sum closure gate
# ---------------------------------------------------------------------------


def test_step_profiled_fit_matches_fused_and_phases_sum(monkeypatch):
    """P2PFL_DEVPROF=step runs separate per-phase jits instead of the
    fused scan. Same seed + same data must give the same trained
    parameters (the split is jax.vjp's own forward/backward, not a
    re-derivation), and the devprof.* spans must sum to the wrapping
    learner.fit span within 10% — the module's closure contract."""
    fused = _make_learner(seed=0)
    split = _make_learner(seed=0)
    for ln in (fused, split):
        ln.set_epochs(2)
    monkeypatch.delenv(devprof.ENV_VAR, raising=False)
    fused.fit()

    monkeypatch.setenv(devprof.ENV_VAR, "step")
    tr = get_tracer()
    tr.configure(enabled=True)
    try:
        split.fit()
        spans = tr.spans()
    finally:
        tr.configure(enabled=False)
        tr.reset()

    # identical math: phase boundaries change fusion, never results
    for a, b in zip(jax.tree.leaves(fused.state.params),
                    jax.tree.leaves(split.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)

    phase_s: dict[str, float] = {}
    fit_s = 0.0
    for name, _lane, _t0, dur, _args in spans:
        if name in devprof.PHASE_SPANS:
            phase_s[name] = phase_s.get(name, 0.0) + dur
        elif name == "learner.fit":
            fit_s += dur
    assert set(phase_s) == set(devprof.PHASE_SPANS)
    assert fit_s > 0
    phase_sum = sum(phase_s.values())
    assert abs(phase_sum - fit_s) / fit_s <= 0.10, (phase_s, fit_s)
    # step mode also feeds the gauges level
    assert split.devprof_last["devprof_fit_s"] > 0


# ---------------------------------------------------------------------------
# gauges: honest-FLOP MFU arithmetic + watermarks
# ---------------------------------------------------------------------------


def test_mfu_arithmetic_and_peak_table(monkeypatch):
    monkeypatch.delenv(cost_model.ENV_PEAK, raising=False)
    # explicit peak: 1e12 FLOPs over 2 s across 2 chips of 1e12 peak
    assert cost_model.mfu(1e12, 2.0, n_devices=2,
                          peak=1e12) == pytest.approx(0.25)
    assert cost_model.mfu(None, 1.0) is None
    assert cost_model.mfu(1e12, 0.0) is None
    # the device table keys on device_kind substrings
    from types import SimpleNamespace
    assert cost_model.peak_flops(
        SimpleNamespace(device_kind="TPU v4")) == 275e12
    # a TPU that is not in the table is an error, not a default
    with pytest.raises(ValueError, match="TPU v99"):
        cost_model.peak_flops(
            SimpleNamespace(device_kind="TPU v99", platform="tpu"))
    # CPU dev box: no table entry -> no denominator -> no MFU
    assert cost_model.peak_flops() is None
    # the env override is how tests/odd parts get a denominator
    monkeypatch.setenv(cost_model.ENV_PEAK, "2e12")
    assert cost_model.peak_flops() == 2e12
    assert cost_model.mfu(1e12, 1.0) == pytest.approx(0.5)
    monkeypatch.setenv(cost_model.ENV_PEAK, "not-a-number")
    assert cost_model.peak_flops() is None  # bad override never raises


def test_fit_gauges_live_mfu_and_flops_cache(monkeypatch):
    """P2PFL_DEVPROF=1 (gauges): after a fit, devprof_last carries the
    measured wall, achieved TFLOPs, MFU against the (env-pinned) peak,
    and the RSS watermark; the per-shape FLOP probe is memoized on the
    learner so fit #2 pays zero extra compiles."""
    monkeypatch.setenv(devprof.ENV_VAR, "1")
    monkeypatch.setenv(cost_model.ENV_PEAK, "1e12")
    ln = _make_learner()
    ln.set_epochs(1)
    ln.fit()
    g = ln.devprof_last
    assert g["devprof_fit_s"] > 0
    assert g["devprof_tflops"] > 0
    assert 0 < g["devprof_mfu"] < 1.5  # sane, not a unit slip
    assert g["devprof_rss_peak_mb"] > 0
    # the probe memo: a second read is the cached float, same value
    f1 = devprof.fit_flops(ln)
    assert f1 and ln._devprof_flops == f1
    assert devprof.fit_flops(ln) == f1
    # live MFU agrees with cost_model's arithmetic over the same
    # wall (the gauge is rounded to 4 decimals, hence the abs band)
    expect = cost_model.mfu(f1 * 1, g["devprof_fit_s"], n_devices=1)
    assert g["devprof_mfu"] == pytest.approx(expect, abs=5.1e-5)


def test_memory_watermark_rss_fallback():
    """CPU backends publish no device memory_stats — the watermark
    must still return the host RSS peak, never an empty surrender."""
    wm = cost_model.memory_watermark()
    assert wm.get("devprof_rss_peak_mb", 0) > 0


def test_round_gauges_federation_plane(monkeypatch):
    monkeypatch.setenv(cost_model.ENV_PEAK, "1e12")
    g = devprof.round_gauges(4e12, 2.0, n_devices=2)
    assert g["devprof_fit_s"] == 2.0
    assert g["devprof_tflops"] == pytest.approx(2.0)
    assert g["devprof_mfu"] == pytest.approx(1.0)
    # no FLOP count (CPU probe failed): wall + watermarks only
    g = devprof.round_gauges(None, 2.0, n_devices=2)
    assert g["devprof_fit_s"] == 2.0 and "devprof_mfu" not in g


# ---------------------------------------------------------------------------
# perf_report: the automated attribution
# ---------------------------------------------------------------------------


def _meta(pid, lane="node0"):
    return [
        {"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
         "args": {"name": f"proc{pid}"}},
        {"ph": "M", "name": "thread_name", "pid": pid, "tid": 0,
         "args": {"name": lane}},
    ]


def _x(name, pid, t0_s, dur_s, args=None):
    ev = {"ph": "X", "name": name, "pid": pid, "tid": 0,
          "ts": t0_s * US, "dur": dur_s * US}
    if args is not None:
        ev["args"] = args
    return ev


def _doc(events, counters=None):
    md = {"files": 1}
    if counters:
        md["counters_by_pid"] = counters
    return {"traceEvents": events, "metadata": md}


def test_attribute_ranks_components_and_names_top():
    events = _meta(1) + [
        _x("node.round", 1, 0, 10, {"round": 0}),
        _x("node.fit", 1, 0, 2),
        _x("node.wait", 1, 2, 7, {"round": 0, "kind": "gossip"}),
    ]
    attr = perf_report.attribute(_doc(events))
    assert attr["rounds"] == [0]
    assert attr["components"]["wait"] == pytest.approx(7.0)
    assert attr["components"]["fit"] == pytest.approx(2.0)
    assert attr["top"] == "wait"
    assert attr["recompiles"] == 0


def test_attribute_devprof_split_reaches_inside_fit():
    """With devprof.* spans in the trace, a fit-topped round names the
    dominant PHASE (fit.forward), not just the opaque bucket — the
    report the tentpole exists to produce."""
    events = _meta(1) + [
        _x("node.round", 1, 0, 10, {"round": 0}),
        _x("node.fit", 1, 0, 8),
        _x("devprof.data", 1, 0.0, 0.5),
        _x("devprof.forward", 1, 0.5, 4.0),
        _x("devprof.backward", 1, 4.5, 2.5),
        _x("devprof.update", 1, 7.0, 0.7),
        _x("devprof.accum", 1, 7.7, 0.3),
    ]
    attr = perf_report.attribute(
        _doc(events, {"1": {"xla/backend_compiles": 5}}))
    assert attr["top"] == "fit.forward"
    assert attr["recompiles"] == 5
    fwd = attr["fit_phases"]["devprof.forward"]
    assert fwd["share_of_fit"] == pytest.approx(0.5, abs=0.01)
    assert fwd["fit_s_est"] == pytest.approx(4.0, abs=0.1)
    # phases are proportions of the REAL fit bucket, so the estimates
    # re-sum to it
    est = sum(p["fit_s_est"] for p in attr["fit_phases"].values())
    assert est == pytest.approx(attr["components"]["fit"], rel=0.01)


def test_attribute_without_devprof_keeps_bucket_verdict():
    events = _meta(1) + [
        _x("node.round", 1, 0, 10, {"round": 0}),
        _x("node.fit", 1, 0, 8),
    ]
    doc = _doc(events)
    assert perf_report.devprof_phases(doc) == {}
    attr = perf_report.attribute(doc)
    assert attr["top"] == "fit" and "fit_phases" not in attr


def _write_trace(dirpath, pid, events, counters=None):
    md = {"wall_t0": 100.0, "pid": pid}
    if counters:
        md["counters"] = counters
    (dirpath / f"proc{pid}.trace.json").write_text(
        json.dumps({"traceEvents": events, "metadata": md}))


def test_cli_report_and_exit_codes(tmp_path, capsys):
    # 1: no readable trace files
    assert perf_report.main([str(tmp_path)]) == 1
    assert "no readable trace files" in capsys.readouterr().err
    # 1: traces but no node.round spans (tracing was off)
    _write_trace(tmp_path, 1, _meta(1) + [_x("learner.fit", 1, 0, 2)])
    assert perf_report.main([str(tmp_path)]) == 1
    assert "node.round" in capsys.readouterr().err
    # 0: a real round -> the human report names the top component
    _write_trace(tmp_path, 2,
                 _meta(2, "node1") + [
                     _x("node.round", 2, 0, 6, {"round": 0}),
                     _x("node.fit", 2, 0, 5),
                 ],
                 counters={"xla/backend_compiles": 2})
    assert perf_report.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "where the round went" in out
    assert "top component: fit" in out
    assert "recompiles: 2" in out


def test_cli_json_mode(tmp_path, capsys):
    _write_trace(tmp_path, 1, _meta(1) + [
        _x("node.round", 1, 0, 4, {"round": 0}),
        _x("node.wait", 1, 1, 3, {"round": 0, "kind": "gossip"}),
    ])
    assert perf_report.main([str(tmp_path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["top"] == "wait"
    assert set(doc["components"]) == {"fit", "wire", "wait", "agg",
                                      "other"}
