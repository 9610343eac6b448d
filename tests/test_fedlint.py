"""fedlint: the static-analysis pass (round 15).

Covers each rule class with one positive and one negative fixture
(tests/fedlint_fixtures/ — parse-only files, never imported), the
pragma and baseline workflows, the CLI exit-code/JSON contracts, and
the tier-1 repo gate: zero unsuppressed findings over ``p2pfl_tpu/``.
"""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys

import pytest

from p2pfl_tpu.analysis import core, fedlint
from p2pfl_tpu.analysis.rules import ALL_RULES, RULES_BY_NAME

REPO = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = pathlib.Path(__file__).resolve().parent / "fedlint_fixtures"


def _run(path, rules=ALL_RULES, baseline=None):
    return core.run_paths([path], rules, root=REPO,
                          baseline_entries=baseline)


# ---------------------------------------------------------------------
# rule classes: positive + negative fixture per rule
# ---------------------------------------------------------------------

_CASES = [
    ("donation-safety", "donation_pos.py", "donation_neg.py", 3),
    ("recompile-hazard", "recompile_pos.py", "recompile_neg.py", 4),
    ("async-hygiene", "async_pos.py", "async_neg.py", 3),
    ("jit-purity", "jit_purity_pos.py", "jit_purity_neg.py", 6),
    ("atomic-artifact", "artifact_pos.py", "artifact_neg.py", 2),
]


@pytest.mark.parametrize("rule,pos,neg,n_pos", _CASES,
                         ids=[c[0] for c in _CASES])
def test_rule_positive_and_negative(rule, pos, neg, n_pos):
    res = _run(FIXTURES / pos)
    assert len(res.findings) == n_pos, [f.render() for f in res.findings]
    assert all(f.rule == rule for f in res.findings), \
        [f.render() for f in res.findings]
    # the negative twin is clean under EVERY rule, not just its own —
    # a fixed idiom must not trade one finding for another
    res = _run(FIXTURES / neg)
    assert res.findings == [], [f.render() for f in res.findings]


def test_all_five_rule_classes_registered():
    assert len(ALL_RULES) >= 5
    assert set(RULES_BY_NAME) >= {c[0] for c in _CASES}
    for r in ALL_RULES:
        assert r.incident  # every rule names the incident it encodes


# ---------------------------------------------------------------------
# pragmas
# ---------------------------------------------------------------------

def test_pragma_suppresses_single_line():
    res = _run(FIXTURES / "pragma_case.py")
    assert res.findings == []
    assert [f.rule for f in res.pragma_suppressed] == ["async-hygiene"]


def test_pragma_is_rule_scoped(tmp_path):
    # a pragma naming a DIFFERENT rule must not suppress this one
    f = tmp_path / "scoped.py"
    f.write_text(
        "import asyncio\n\n\n"
        "def kick(node):\n"
        "    asyncio.create_task(node.p())  "
        "# fedlint: disable=jit-purity\n")
    res = core.run_paths([f], ALL_RULES, root=tmp_path)
    assert [x.rule for x in res.findings] == ["async-hygiene"]


def test_bare_pragma_suppresses_all_rules(tmp_path):
    f = tmp_path / "bare.py"
    f.write_text(
        "import asyncio\n\n\n"
        "def kick(node):\n"
        "    asyncio.create_task(node.p())  # fedlint: disable\n")
    res = core.run_paths([f], ALL_RULES, root=tmp_path)
    assert res.findings == [] and len(res.pragma_suppressed) == 1


# ---------------------------------------------------------------------
# baseline round-trip
# ---------------------------------------------------------------------

def test_baseline_round_trip(tmp_path):
    pos = FIXTURES / "async_pos.py"
    res = _run(pos)
    assert res.findings
    bl = tmp_path / "BASELINE.json"
    core.write_baseline(bl, res.findings,
                        justification="fixture positive, kept on purpose")
    entries = core.load_baseline(bl)
    assert len(entries) == len(res.findings)
    # with the baseline loaded, the same findings are grandfathered
    res2 = _run(pos, baseline=entries)
    assert res2.findings == [] and res2.exit_code == 0
    assert len(res2.baselined) == len(entries)
    assert res2.stale_baseline == []
    # over a clean file the entries match nothing and read as stale
    res3 = _run(FIXTURES / "async_neg.py", baseline=entries)
    assert len(res3.stale_baseline) == len(entries)
    assert res3.exit_code == 0  # stale entries report, never gate


def test_baseline_requires_justification(tmp_path):
    bl = tmp_path / "BASELINE.json"
    bl.write_text(json.dumps({"version": 1, "entries": [
        {"rule": "async-hygiene", "path": "x.py", "code": "y()",
         "justification": "  "}]}))
    with pytest.raises(ValueError, match="justification"):
        core.load_baseline(bl)
    bl.write_text(json.dumps({"version": 1, "entries": [
        {"rule": "async-hygiene", "path": "x.py"}]}))
    with pytest.raises(ValueError, match="lacks"):
        core.load_baseline(bl)


def test_baseline_survives_line_drift(tmp_path):
    # fingerprints anchor on (rule, path, stripped line) — inserting
    # lines above the finding must not invalidate the baseline
    f = tmp_path / "drift.py"
    body = ("import asyncio\n\n\n"
            "def kick(node):\n"
            "    asyncio.create_task(node.p())\n")
    f.write_text(body)
    res = core.run_paths([f], ALL_RULES, root=tmp_path)
    bl = tmp_path / "BASELINE.json"
    core.write_baseline(bl, res.findings,
                        justification="drift fixture, kept on purpose")
    f.write_text("# a new header comment\n# another\n" + body)
    res2 = core.run_paths([f], ALL_RULES, root=tmp_path,
                          baseline_entries=core.load_baseline(bl))
    assert res2.findings == [] and len(res2.baselined) == 1


# ---------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------

def test_cli_exit_codes(tmp_path, capsys):
    # 1: findings
    rc = fedlint.main([str(FIXTURES / "async_pos.py"), "--no-baseline"])
    assert rc == 1
    assert "async-hygiene" in capsys.readouterr().out
    # 0: clean
    rc = fedlint.main([str(FIXTURES / "async_neg.py"), "--no-baseline"])
    assert rc == 0
    capsys.readouterr()
    # 2: unparseable file (operational error, not a silent skip)
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    rc = fedlint.main([str(bad), "--no-baseline"])
    assert rc == 2
    assert "cannot parse" in capsys.readouterr().err
    # 2: unknown rule
    rc = fedlint.main([str(FIXTURES / "async_neg.py"), "--rules", "nope"])
    assert rc == 2
    assert "unknown rule" in capsys.readouterr().err
    # 2: nonexistent path must be loud, never a 0-file clean pass
    rc = fedlint.main([str(tmp_path / "no_such_dir"), "--no-baseline"])
    assert rc == 2
    assert "no such path" in capsys.readouterr().err


def test_cli_default_path_resolves_against_root(tmp_path, monkeypatch,
                                                capsys):
    """`python -m p2pfl_tpu.analysis` from any cwd lints the repo's
    p2pfl_tpu/ (relative paths fall back to --root), not 0 files."""
    monkeypatch.chdir(tmp_path)
    rc = fedlint.main([])
    out = capsys.readouterr().out
    assert rc == 0
    assert " 0 file(s)" not in out  # it actually saw the package


def test_cli_json_output(capsys):
    rc = fedlint.main([str(FIXTURES / "artifact_pos.py"),
                       "--no-baseline", "--json"])
    assert rc == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["exit_code"] == 1 and doc["files"] == 1
    assert {"rule", "path", "line", "col", "message", "code"} <= set(
        doc["findings"][0])
    assert all(f["rule"] == "atomic-artifact" for f in doc["findings"])


def test_cli_rules_subset(capsys):
    # only the selected rule runs: async_pos is clean under jit-purity
    rc = fedlint.main([str(FIXTURES / "async_pos.py"),
                       "--no-baseline", "--rules", "jit-purity"])
    capsys.readouterr()
    assert rc == 0


def test_cli_write_baseline(tmp_path, capsys):
    bl = tmp_path / "BL.json"
    rc = fedlint.main([str(FIXTURES / "async_pos.py"),
                       "--baseline", str(bl), "--write-baseline"])
    capsys.readouterr()
    assert rc == 0
    # the fresh scaffold is NOT loadable as-is: every entry still
    # carries the TODO marker a reviewer must replace
    with pytest.raises(ValueError, match="scaffold"):
        core.load_baseline(bl)
    doc = json.loads(bl.read_text())
    assert doc["entries"]
    for e in doc["entries"]:
        assert e["justification"] == core.SCAFFOLD_JUSTIFICATION
        e["justification"] = "fixture exercises the positive case"
    bl.write_text(json.dumps(doc))
    entries = core.load_baseline(bl)
    assert entries and all(e["justification"] for e in entries)
    rc = fedlint.main([str(FIXTURES / "async_pos.py"),
                       "--baseline", str(bl)])
    capsys.readouterr()
    assert rc == 0


def test_load_baseline_rejects_untouched_scaffold(tmp_path):
    """Regression: the loader used to accept the --write-baseline
    default text as a 'non-empty' justification, so a regenerated
    baseline could merge with zero human words on any entry."""
    bl = tmp_path / "BL.json"
    bl.write_text(json.dumps({"version": 1, "entries": [
        {"rule": "jit-purity", "path": "x.py", "code": "abc",
         "justification": "  TODO: justify or fix  "}]}))
    with pytest.raises(ValueError, match="scaffold"):
        core.load_baseline(bl)
    # a real justification on the same entry loads fine
    bl.write_text(json.dumps({"version": 1, "entries": [
        {"rule": "jit-purity", "path": "x.py", "code": "abc",
         "justification": "measured: counter is outside the jit"}]}))
    assert len(core.load_baseline(bl)) == 1


# ---------------------------------------------------------------------
# the tier-1 repo gate + single entry point
# ---------------------------------------------------------------------

def test_fedlint_repo_gate():
    """Zero unsuppressed findings over all of p2pfl_tpu/ — the gate
    every future PR runs through. Also the regression test for this
    round's fixes: the fire-and-forget create_task sites in p2p/node.py
    and the non-atomic topology_3d.json write in federation/scenario.py
    would each re-introduce a finding here."""
    res = core.run_paths([REPO / "p2pfl_tpu"], ALL_RULES, root=REPO,
                         baseline_entries=core.load_baseline(
                             REPO / core.BASELINE_NAME))
    assert res.findings == [], "\n".join(f.render() for f in res.findings)
    assert res.files > 50  # the walk actually covered the package


def test_fedlint_cli_over_repo_subprocess():
    """The documented CI invocation exits 0 from a clean checkout."""
    res = subprocess.run(
        [sys.executable, "-m", "p2pfl_tpu.analysis.fedlint",
         "p2pfl_tpu/", "--json"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    doc = json.loads(res.stdout)
    assert doc["exit_code"] == 0 and doc["findings"] == []


def test_analysis_single_entry_point_runs_all_passes():
    """``python -m p2pfl_tpu.analysis``: fedlint + status-keys under
    one command, combined exit code."""
    res = subprocess.run(
        [sys.executable, "-m", "p2pfl_tpu.analysis", "p2pfl_tpu/"],
        capture_output=True, text=True, timeout=180, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "== fedlint ==" in res.stdout
    assert "== status-keys ==" in res.stdout
    assert "ok:" in res.stdout  # status-keys keeps its text contract
