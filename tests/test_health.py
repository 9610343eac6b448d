"""Health plane (round 12): the rule engine's firing/clear semantics,
the healthcheck CLI's exit-code contract and the flight recorder's
dump-on-crash postmortem.

Engine tests drive ``HealthEngine.evaluate`` with synthetic status
records and explicit clocks — the engine is read-only over published
artifacts by design, so no federation needs to run. The dump-on-crash
test uses the real P2PNode crash path (shared trainer from test_p2p,
same recompile-amortising reason as test_elastic)."""

import asyncio
import json
import os
import time

import pytest

from p2pfl_tpu.obs import flight
from p2pfl_tpu.obs.flight import FlightRecorder
from p2pfl_tpu.obs.health import (
    HealthConfig,
    HealthEngine,
    evaluate_dir,
    tail_jsonl,
    worse,
)
from p2pfl_tpu.obs.healthcheck import main as healthcheck_main
from p2pfl_tpu.utils.monitor import publish_status

from test_p2p import _make_learners


def _status(node, ts, **fields):
    return {"node": node, "ts": ts, **fields}


# ---------------------------------------------------------------------------
# rule engine: firing/clear semantics
# ---------------------------------------------------------------------------


class TestHealthEngine:
    def test_round_stall_fires_then_clears(self):
        eng = HealthEngine(config=HealthConfig(stall_rounds=2))
        t = 1000.0
        lagging = [_status(i, t, round=5) for i in range(3)]
        lagging.append(_status(3, t, round=2))
        alerts = eng.evaluate(lagging, now=t)
        assert [(a.rule, a.node, a.severity) for a in alerts] == [
            ("round-stall", 3, "warn")
        ]
        assert eng.worst() == "warn"
        # still firing: same alert object identity semantics — ``since``
        # keeps the original fire time while the message refreshes
        alerts = eng.evaluate(lagging, now=t + 1)
        assert alerts[0].since == t
        # the straggler catches up: the alert must CLEAR, not linger
        caught_up = [_status(i, t + 2, round=5) for i in range(4)]
        alerts = eng.evaluate(caught_up, now=t + 2)
        assert alerts == [] and eng.worst() == "ok"
        events = [(tr["event"], tr["rule"], tr["node"])
                  for tr in eng.transitions]
        assert events == [("fire", "round-stall", 3),
                          ("clear", "round-stall", 3)]

    def test_stall_clock_judged_against_previous_evaluation(self):
        # time-based stall (no cohort to lag): the no-advance clock must
        # be anchored at the PREVIOUS eval's sighting, or a stalled node
        # would reset it every tick
        eng = HealthEngine(config=HealthConfig(stall_s=5.0))
        t = 1000.0
        rec = [_status(0, t, round=3)]
        assert eng.evaluate(rec, now=t) == []
        rec = [_status(0, t + 6, round=3)]  # fresh publish, same round
        alerts = eng.evaluate(rec, now=t + 6)
        assert [(a.rule, a.node) for a in alerts] == [("round-stall", 0)]
        # advancing the round clears it
        rec = [_status(0, t + 7, round=4)]
        assert eng.evaluate(rec, now=t + 7) == []

    def test_node_dead_escalates_to_crit_beyond_quorum(self):
        eng = HealthEngine(config=HealthConfig(liveness_s=10.0))
        t = 1000.0
        # one of four silent: warn, per-node only
        recs = [_status(i, t, round=1) for i in range(3)]
        recs.append(_status(3, t - 60, round=1))
        alerts = eng.evaluate(recs, now=t)
        assert [(a.rule, a.node, a.severity) for a in alerts] == [
            ("node-dead", 3, "warn")
        ]
        # three of four silent: below quorum_frac=0.5 — every dead node
        # escalates to crit and a federation-level alert (node=None)
        # names the quorum loss
        recs = [_status(0, t, round=1)] + [
            _status(i, t - 60, round=1) for i in (1, 2, 3)
        ]
        alerts = eng.evaluate(recs, now=t)
        assert eng.worst() == "crit"
        assert {a.node for a in alerts if a.severity == "crit"} \
            == {None, 1, 2, 3}

    def test_trust_collapse_is_crit(self):
        eng = HealthEngine()
        t = 1000.0
        recs = [_status(0, t, trust=0.9), _status(1, t, trust=0.05)]
        alerts = eng.evaluate(recs, now=t)
        assert [(a.rule, a.node, a.severity) for a in alerts] == [
            ("trust-collapse", 1, "crit")
        ]

    def test_epsilon_budget_warn_crit_and_clear(self):
        """Round 21: DP spend vs budget — warn at 80%, crit at/over
        100%, inert without a positive budget, and the alert clears
        when the spend drops back (a fresh run re-publishing)."""
        eng = HealthEngine(config=HealthConfig(eps_warn_frac=0.8))
        t = 1000.0
        recs = [_status(0, t, dp_epsilon=2.0, dp_epsilon_budget=10.0),
                _status(1, t, dp_epsilon=8.5, dp_epsilon_budget=10.0),
                _status(2, t, dp_epsilon=11.0, dp_epsilon_budget=10.0),
                # no budget configured: rule must stay silent
                _status(3, t, dp_epsilon=99.0, dp_epsilon_budget=0.0),
                _status(4, t)]  # non-DP run
        alerts = eng.evaluate(recs, now=t)
        assert [(a.rule, a.node, a.severity) for a in alerts] == [
            ("epsilon-budget", 2, "crit"),
            ("epsilon-budget", 1, "warn"),
        ]
        assert eng.worst() == "crit"
        # a fresh run's records under budget: both alerts clear
        fresh = [_status(i, t + 1, dp_epsilon=0.5,
                         dp_epsilon_budget=10.0) for i in range(3)]
        assert eng.evaluate(fresh, now=t + 1) == []
        clears = [tr for tr in eng.transitions if tr["event"] == "clear"]
        assert {c["node"] for c in clears} == {1, 2}

    def test_mfu_collapse_fires_against_own_peak_then_clears(self):
        """Round 22: live MFU halving against the node's own best-seen
        fires; recovery clears. The peak folds in AFTER rules run, so
        the first sighting can never fire against itself."""
        eng = HealthEngine()
        t = 1000.0
        # eval 1 arms the peak (0.4); nothing can fire yet
        assert eng.evaluate([_status(0, t, devprof_mfu=0.4)], now=t) == []
        # eval 2: 0.1 < 0.5 * 0.4 -> collapse
        alerts = eng.evaluate([_status(0, t + 1, devprof_mfu=0.1)],
                              now=t + 1)
        assert [(a.rule, a.node, a.severity) for a in alerts] == [
            ("mfu-collapse", 0, "warn")
        ]
        assert "MFU collapsed" in alerts[0].message
        # recovery clears the alert
        assert eng.evaluate([_status(0, t + 2, devprof_mfu=0.38)],
                            now=t + 2) == []
        events = [(tr["event"], tr["rule"]) for tr in eng.transitions]
        assert events == [("fire", "mfu-collapse"),
                          ("clear", "mfu-collapse")]

    def test_mfu_collapse_floor_keeps_cpu_noise_silent(self):
        """Peaks below mfu_floor never arm the rule: CPU smoke runs
        report sub-percent MFU whose halving is measurement noise."""
        eng = HealthEngine()
        t = 1000.0
        assert eng.evaluate([_status(0, t, devprof_mfu=0.01)], now=t) == []
        assert eng.evaluate([_status(0, t + 1, devprof_mfu=0.001)],
                            now=t + 1) == []
        # records without the gauge (devprof off) are always inert
        assert eng.evaluate([_status(0, t + 2, round=3)], now=t + 2) == []

    def test_hbm_watermark_warn_crit_and_inert_without_limit(self):
        eng = HealthEngine()
        t = 1000.0
        recs = [
            # 90% of limit: warn
            _status(0, t, devprof_hbm_peak_mb=900.0,
                    devprof_hbm_limit_mb=1000.0),
            # 98% of limit: crit
            _status(1, t, devprof_hbm_peak_mb=980.0,
                    devprof_hbm_limit_mb=1000.0),
            # comfortable headroom: silent
            _status(2, t, devprof_hbm_peak_mb=500.0,
                    devprof_hbm_limit_mb=1000.0),
            # RSS-only host (no limit gauge): inert by design
            _status(3, t, devprof_rss_peak_mb=99999.0),
        ]
        alerts = eng.evaluate(recs, now=t)
        assert [(a.rule, a.node, a.severity) for a in alerts] == [
            ("hbm-watermark", 1, "crit"),
            ("hbm-watermark", 0, "warn"),
        ]
        assert "HBM high-water" in alerts[0].message
        # the allocator drains: both clear
        fresh = [_status(i, t + 1, devprof_hbm_peak_mb=400.0,
                         devprof_hbm_limit_mb=1000.0) for i in range(2)]
        assert eng.evaluate(fresh, now=t + 1) == []
        clears = [tr for tr in eng.transitions if tr["event"] == "clear"]
        assert {c["node"] for c in clears} == {0, 1}

    def test_byte_rate_anomaly_needs_cohort_and_floor(self):
        cfg = HealthConfig(byte_ratio=8.0, byte_floor=1e6, min_cohort=3)
        t = 1000.0
        # 10x the median but only 9 KB over it: below the absolute
        # floor, so early-round noise must not fire
        small = [_status(i, t, bytes_out=1e3) for i in range(3)]
        small.append(_status(3, t, bytes_out=1e4))
        assert HealthEngine(config=cfg).evaluate(small, now=t) == []
        big = [_status(i, t, bytes_out=1e6) for i in range(3)]
        big.append(_status(3, t, bytes_out=2e7))
        alerts = HealthEngine(config=cfg).evaluate(big, now=t)
        assert [(a.rule, a.node) for a in alerts] == [("byte-rate", 3)]

    def test_recompile_storm(self):
        eng = HealthEngine(config=HealthConfig(recompile_storm=32))
        t = 1000.0
        recs = [_status(0, t, recompiles=0), _status(1, t, recompiles=40)]
        alerts = eng.evaluate(recs, now=t)
        assert [(a.rule, a.node) for a in alerts] \
            == [("recompile-storm", 1)]

    def test_accuracy_divergence_reads_metrics_fallback(self):
        eng = HealthEngine(config=HealthConfig(divergence=0.15,
                                               min_cohort=3))
        t = 1000.0
        recs = [_status(i, t, round=1) for i in range(3)]
        metrics = [
            {"node": 0, "Test/accuracy": 0.91},
            {"node": 1, "Test/accuracy": 0.90},
            {"node": 2, "Test/accuracy": 0.40},  # the poisoned node
            {"node": 2, "Train/loss": 2.0},  # later non-accuracy row
        ]
        alerts = eng.evaluate(recs, metrics, now=t)
        assert [(a.rule, a.node) for a in alerts] \
            == [("accuracy-divergence", 2)]

    def test_severity_ordering_helpers(self):
        assert worse("ok", "warn") == "warn"
        assert worse("crit", "warn") == "crit"
        # alerts() sorts crit first, federation-level before nodes
        eng = HealthEngine(config=HealthConfig(liveness_s=10.0))
        t = 1000.0
        recs = [_status(0, t, round=1, trust=0.9)] + [
            _status(i, t - 60, round=1) for i in (1, 2, 3)
        ]
        alerts = eng.evaluate(recs, now=t)
        assert alerts[0].severity == "crit" and alerts[0].node is None


# ---------------------------------------------------------------------------
# filesystem plumbing + healthcheck CLI
# ---------------------------------------------------------------------------


def test_tail_jsonl_skips_torn_and_clipped_rows(tmp_path):
    p = tmp_path / "metrics.jsonl"
    rows = [{"node": i, "Test/accuracy": 0.5} for i in range(5)]
    with open(p, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
        f.write('{"node": 9, "Test/acc')  # a writer mid-append
    out = tail_jsonl(p)
    assert out == rows  # torn trailing row skipped, never raised
    # a clipped window must also drop its (possibly partial) first line
    out = tail_jsonl(p, max_bytes=len(json.dumps(rows[0])) + 30)
    assert out and all(r in rows for r in out)
    assert tail_jsonl(tmp_path / "missing.jsonl") == []


def test_healthcheck_cli_round_stall_fire_and_clear(tmp_path, capsys):
    # synthetic scenario dir: status/ subdir + metrics.jsonl, the shape
    # resolve_dirs() must navigate
    status = tmp_path / "status"
    for i in range(3):
        publish_status(status, i, {"round": 6})
    publish_status(status, 3, {"round": 1})
    rc = healthcheck_main([str(tmp_path), "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1 and doc["severity"] == "warn"
    assert [(a["rule"], a["node"]) for a in doc["alerts"]] \
        == [("round-stall", 3)]
    # the straggler catches up -> healthy, exit 0
    publish_status(status, 3, {"round": 6})
    rc = healthcheck_main([str(tmp_path)])
    assert rc == 0
    assert "healthy" in capsys.readouterr().out


def test_healthcheck_cli_epsilon_budget_crit_exit_code(tmp_path, capsys):
    """Round 21: an exhausted DP budget is an operator-stop condition —
    the healthcheck CLI must exit 2 (crit) on it, so a watchdog can
    halt the run before it spends privacy it never provisioned."""
    status = tmp_path / "status"
    publish_status(status, 0, {"round": 4, "dp_epsilon": 3.0,
                               "dp_epsilon_budget": 10.0})
    publish_status(status, 1, {"round": 4, "dp_epsilon": 12.5,
                               "dp_epsilon_budget": 10.0})
    rc = healthcheck_main([str(tmp_path), "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 2 and doc["severity"] == "crit"
    assert [(a["rule"], a["node"]) for a in doc["alerts"]] \
        == [("epsilon-budget", 1)]


def test_healthcheck_cli_hbm_and_mfu_exit_codes(tmp_path, capsys):
    """Round 22: the devprof gauges drive the watchdog contract — an
    HBM watermark at crit must exit 2; an MFU collapse (a perf
    regression, not an outage) exits 1."""
    status = tmp_path / "status"
    publish_status(status, 0, {"round": 2, "devprof_hbm_peak_mb": 990.0,
                               "devprof_hbm_limit_mb": 1000.0})
    rc = healthcheck_main([str(tmp_path), "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 2 and doc["severity"] == "crit"
    assert [(a["rule"], a["node"]) for a in doc["alerts"]] \
        == [("hbm-watermark", 0)]

    # mfu collapse needs engine state across evals — drive evaluate_dir
    # with a shared engine the way the healthcheck daemon loop does
    mfu_dir = tmp_path / "mfu" / "status"
    publish_status(mfu_dir, 0, {"round": 1, "devprof_mfu": 0.4})
    alerts, eng = evaluate_dir(mfu_dir.parent, HealthEngine())
    assert alerts == []
    publish_status(mfu_dir, 0, {"round": 2, "devprof_mfu": 0.05})
    alerts, _ = evaluate_dir(mfu_dir.parent, engine=eng)
    assert [(a.rule, a.severity) for a in alerts] \
        == [("mfu-collapse", "warn")]
    assert eng.worst() == "warn"  # the CLI maps warn -> exit 1


def test_healthcheck_cli_dead_node_exit_codes(tmp_path, capsys):
    t = time.time()
    for i in range(4):
        ts = t - (100 if i == 3 else 0)
        (tmp_path / f"node_{i}.status.json").write_text(
            json.dumps({"node": i, "ts": ts, "round": 2}))
    assert healthcheck_main([str(tmp_path), "--liveness-s", "10"]) == 1
    capsys.readouterr()
    # kill two more: quorum lost, crit, exit 2
    for i in (1, 2):
        (tmp_path / f"node_{i}.status.json").write_text(
            json.dumps({"node": i, "ts": t - 100, "round": 2}))
    rc = healthcheck_main([str(tmp_path), "--liveness-s", "10", "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 2 and doc["severity"] == "crit"
    assert any(a["node"] is None for a in doc["alerts"])  # quorum alert


def test_evaluate_dir_shares_engine_state(tmp_path):
    publish_status(tmp_path, 0, {"round": 4})
    publish_status(tmp_path, 1, {"round": 1})
    alerts, eng = evaluate_dir(tmp_path,
                               HealthEngine(config=HealthConfig()))
    assert [(a.rule, a.node) for a in alerts] == [("round-stall", 1)]
    publish_status(tmp_path, 1, {"round": 4})
    alerts, _ = evaluate_dir(tmp_path, engine=eng)
    assert alerts == []
    assert [tr["event"] for tr in eng.transitions] == ["fire", "clear"]


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded_and_disable_is_total(self, tmp_path):
        rec = FlightRecorder(ring_max=8)
        for i in range(20):
            rec.record("evt", i=i)
        assert len(rec) == 8
        assert [e["i"] for e in rec.events("evt")] == list(range(12, 20))
        rec.configure(enabled=False)
        rec.record("evt", i=99)
        assert len(rec) == 8  # record() is a no-op when disabled
        assert rec.dump("why", path=tmp_path / "f.json") is None

    def test_dump_accumulates_reasons(self, tmp_path):
        rec = FlightRecorder()
        rec.record("membership.evict", node=2)
        p = tmp_path / "flight.json"
        rec.dump("crash", path=p)
        rec.record("session.close", lane=0)
        rec.dump("evicted", path=p)
        doc = json.loads(p.read_text())
        assert doc["reasons"] == ["crash", "evicted"]
        kinds = [e["kind"] for e in doc["events"]]
        assert kinds == ["membership.evict", "session.close"]

    def test_node_crash_dumps_postmortem_with_evict_transition(
            self, tmp_path):
        """node.crash() must leave flight_<pid>.json behind, and a
        membership eviction recorded before the crash must be in it —
        the postmortem that explains churn without a traced re-run."""
        from p2pfl_tpu.p2p import P2PNode

        rec = flight.get_recorder()
        old_dir, old_enabled = rec.dump_dir, rec.enabled
        rec.clear()
        flight.configure(enabled=True, dump_dir=tmp_path)
        try:
            async def main():
                _, learners = _make_learners(2, samples=40)
                node = P2PNode(0, learners[0], role="aggregator",
                               n_nodes=2)
                node.membership.evict(1)
                await node.crash()
                return node

            node = asyncio.run(main())
            assert node.finished.is_set()
            dump = tmp_path / f"flight_{os.getpid()}.json"
            assert dump.exists()
            doc = json.loads(dump.read_text())
            assert "node0.crash" in doc["reasons"]
            kinds = [e["kind"] for e in doc["events"]]
            assert "membership.evict" in kinds
            assert "node.crash" in kinds
            evict = next(e for e in doc["events"]
                         if e["kind"] == "membership.evict")
            assert evict["node"] == 1
        finally:
            rec.dump_dir, rec.enabled = old_dir, old_enabled
            rec.clear()

    def test_crash_dump_stamps_active_trace_id(self, tmp_path):
        """Round 18: with tracing in scope, flight events carry the
        process trace_id, so a postmortem's control events can be
        joined against the span timeline. The id must round-trip
        through a real node.crash() dump; untraced events stay
        unstamped (the always-on recorder adds no id noise)."""
        from p2pfl_tpu.obs.trace import get_tracer
        from p2pfl_tpu.p2p import P2PNode

        tr = get_tracer()
        rec = flight.get_recorder()
        old_dir, old_enabled = rec.dump_dir, rec.enabled
        old_traced = tr.enabled
        rec.clear()
        flight.configure(enabled=True, dump_dir=tmp_path)
        try:
            tr.configure(enabled=False)
            rec.record("membership.suspect", node=1)  # untraced era
            tr.configure(enabled=True)

            async def main():
                _, learners = _make_learners(2, samples=40)
                node = P2PNode(0, learners[0], role="aggregator",
                               n_nodes=2)
                await node.crash()

            asyncio.run(main())
            dump = tmp_path / f"flight_{os.getpid()}.json"
            assert dump.exists()
            doc = json.loads(dump.read_text())
            crash = next(e for e in doc["events"]
                         if e["kind"] == "node.crash")
            assert crash["trace"] == tr.trace_id
            suspect = next(e for e in doc["events"]
                           if e["kind"] == "membership.suspect")
            assert "trace" not in suspect
        finally:
            tr.configure(enabled=old_traced)
            rec.dump_dir, rec.enabled = old_dir, old_enabled
            rec.clear()
