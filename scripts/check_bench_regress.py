#!/usr/bin/env python
"""Bench regression gate: judge a BENCH json against the trajectory.

The checked-in ``BENCH_rNN.json`` files (``r04`` on; the older
records were taken on a machine that no longer exists and are gone)
record every round's bench envelope (``{"n", "cmd", "rc", "tail", "parsed": {...}}``, plus
``parsed.meta`` run stamps since round 12). This script turns that
history from archaeology into a gate:

    python scripts/check_bench_regress.py                 # newest vs rest
    python scripts/check_bench_regress.py --candidate BENCH_new.json

For each HEADLINE perf key the baseline is the trajectory's best-ever
value (min for time-like keys, max for rate-like keys) over rounds
that actually ran (``rc == 0`` with a non-empty ``parsed``; a round
that timed out is skipped automatically). A candidate worse than
baseline by more than the per-key tolerance band (default 15%) fails
with a nonzero exit.

Deliberately perf-keys-only: accuracy-flavored keys (final_accuracy,
rounds_to_80pct) moved with benchmark-harness changes across rounds
(r05 switched the headline run to a surrogate profile), so gating on
them would false-positive on the checked-in history itself. The
``value`` headline is compared only against history rows measuring the
SAME ``metric`` string — an 8-node headline must not serve as the
baseline for the 64-node metric it was replaced by.

A missing headline key in the candidate is reported but does not fail
the gate: token/time budgets legitimately skip phases
(``skipped_phases``), and absence of evidence is not a regression.
"""

from __future__ import annotations

import argparse
import glob
import json
import pathlib
import sys

# key -> "lower" (time-like: smaller is better) | "higher" (rate-like)
HEADLINE: dict[str, str] = {
    "value": "lower",  # headline s/round (metric-string matched)
    "mfu": "higher",
    # round 22: device-slope MFU (pacing sleeps subtracted) — the
    # utilization number the live devprof gauge is validated against
    "mfu_device": "higher",
    "round_s_8node": "lower",
    "socket_round_s_24node": "lower",
    "vit32_krum_round_s": "lower",
    "cifar16_dirichlet_round_s": "lower",
    "cpu8_ring_dense_round_s": "lower",
    "crossdev_round_s_10k": "lower",
    "crossdev_clients_per_s": "higher",
    # round 20: the sharded-scan mechanism gate — even where sharding
    # is an honest negative (fake host devices), a regression here
    # means the shard_map path itself got slower
    "crossdev_sharded_round_s": "lower",
    "chaos_recovery_s": "lower",
    "chaos_final_accuracy": "higher",
    "aggd_round_s_24node_uncapped": "lower",
    "lora_payload_reduction": "higher",
    # round 21: the secagg masking/quantization tax on socket round
    # wall time — the privacy plane's only perf headline
    "private_secagg_overhead_pct": "lower",
}
DEFAULT_TOL = 0.15


def _provenance(parsed: dict) -> tuple[str, int]:
    """``(backend, device_count)`` of one parsed envelope. Rows
    predating the round-20 stamps default to ``("cpu", 1)`` — every
    checked-in trajectory row before the stamps existed was a 1-device
    CPU dev-box run, so the default matches reality instead of
    vacuuming legacy history out of the baseline."""
    meta = parsed.get("meta")
    meta = meta if isinstance(meta, dict) else {}
    backend = meta.get("backend") or "cpu"
    try:
        devices = int(meta.get("device_count") or 1)
    except (TypeError, ValueError):
        devices = 1
    return (str(backend), devices)


def load_parsed(path: pathlib.Path) -> dict | None:
    """The parsed key dict of one BENCH envelope (or a bare key dict —
    what a synthetic test candidate looks like); None when the round
    didn't complete (rc != 0 / empty parsed) and must not anchor
    baselines."""
    try:
        doc = json.loads(path.read_text())
    except (OSError, ValueError) as e:
        print(f"error: cannot read {path}: {e}", file=sys.stderr)
        return None
    if not isinstance(doc, dict):
        return None
    if "parsed" in doc or "rc" in doc:
        if doc.get("rc") not in (0, None):
            return None
        parsed = doc.get("parsed")
        return parsed if isinstance(parsed, dict) and parsed else None
    return doc or None


def baseline_over(history: list[tuple[str, dict]], key: str,
                  direction: str, metric: str | None,
                  provenance: tuple[str, int] | None = None
                  ) -> tuple[float, str] | None:
    """(best value, which file it came from) for one headline key.

    ``provenance``: the candidate's ``(backend, device_count)`` — rows
    measured on different hardware are skipped (a 1-device history must
    not gate an 8-device run, or vice versa), the same matched-rows
    discipline the ``value`` key applies via ``metric``."""
    best: tuple[float, str] | None = None
    for name, parsed in history:
        v = parsed.get(key)
        if not isinstance(v, (int, float)):
            continue
        if key == "value" and metric is not None \
                and parsed.get("metric") != metric:
            continue
        if provenance is not None and _provenance(parsed) != provenance:
            continue
        v = float(v)
        if (best is None
                or (direction == "lower" and v < best[0])
                or (direction == "higher" and v > best[0])):
            best = (v, name)
    return best


def check(candidate: dict, history: list[tuple[str, dict]],
          tol: float) -> int:
    metric = candidate.get("metric")
    prov = _provenance(candidate)
    rows = []
    failures = 0
    for key, direction in HEADLINE.items():
        base = baseline_over(history, key, direction, metric, prov)
        cand = candidate.get(key)
        if base is None:
            rows.append((key, "-", "-", "no-baseline"))
            continue
        if not isinstance(cand, (int, float)):
            rows.append((key, f"{base[0]:.4f}", "-", "missing"))
            continue
        cand = float(cand)
        if direction == "lower":
            limit = base[0] * (1.0 + tol)
            bad = cand > limit
            delta = (cand - base[0]) / base[0] if base[0] else 0.0
        else:
            limit = base[0] * (1.0 - tol)
            bad = cand < limit
            delta = (base[0] - cand) / base[0] if base[0] else 0.0
        verdict = "REGRESSION" if bad else "ok"
        failures += bad
        rows.append((key, f"{base[0]:.4f} ({base[1]})",
                     f"{cand:.4f}", f"{verdict} ({delta:+.1%})"))
    print(f"provenance filter: backend={prov[0]} devices={prov[1]} "
          f"(unstamped history rows count as cpu/1)")
    w0 = max(len(r[0]) for r in rows)
    w1 = max(len(r[1]) for r in rows)
    w2 = max(len(r[2]) for r in rows)
    print(f"{'KEY'.ljust(w0)}  {'BASELINE(best)'.ljust(w1)}  "
          f"{'CANDIDATE'.ljust(w2)}  VERDICT")
    for r in rows:
        print(f"{r[0].ljust(w0)}  {r[1].ljust(w1)}  {r[2].ljust(w2)}  "
              f"{r[3]}")
    meta = candidate.get("meta")
    if isinstance(meta, dict):
        print("candidate meta: " + ", ".join(
            f"{k}={meta[k]}" for k in sorted(meta)))
    if failures:
        print(f"FAIL: {failures} headline key(s) regressed beyond "
              f"{tol:.0%} of the trajectory best", file=sys.stderr)
        return 1
    print(f"clean: no headline key regressed beyond {tol:.0%}")
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--candidate", default=None,
                    help="BENCH json to judge (default: the newest "
                         "BENCH_r*.json; the rest become the baseline)")
    ap.add_argument("--history", default=None,
                    help="glob of trajectory files "
                         "(default: BENCH_r*.json next to the repo root)")
    ap.add_argument("--tol", type=float, default=DEFAULT_TOL,
                    help=f"per-key tolerance band (default "
                         f"{DEFAULT_TOL:.0%})")
    args = ap.parse_args(argv)

    root = pathlib.Path(__file__).resolve().parent.parent
    pattern = args.history or str(root / "BENCH_r*.json")
    files = sorted(pathlib.Path(p) for p in glob.glob(pattern))
    if args.candidate:
        cand_path = pathlib.Path(args.candidate)
        files = [f for f in files if f.resolve() != cand_path.resolve()]
    else:
        if len(files) < 2:
            print("error: need >= 2 trajectory files when no "
                  "--candidate given", file=sys.stderr)
            return 2
        cand_path, files = files[-1], files[:-1]
    candidate = load_parsed(cand_path)
    if candidate is None:
        print(f"error: candidate {cand_path} has no parsed results",
              file=sys.stderr)
        return 2
    history = []
    for f in files:
        parsed = load_parsed(f)
        if parsed is None:
            print(f"note: skipping {f.name} (rc != 0 or empty parsed)")
            continue
        history.append((f.name, parsed))
    if not history:
        print("error: no usable trajectory files", file=sys.stderr)
        return 2
    print(f"candidate: {cand_path.name}  vs  "
          f"{', '.join(n for n, _ in history)}")
    return check(candidate, history, args.tol)


if __name__ == "__main__":
    sys.exit(main())
