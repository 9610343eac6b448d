"""Automated perf attribution: *where did the round go*, as a report.

docs/perf.md §6 reaches its verdicts by hand: merge the traces, stare
at the critpath table, divide FLOPs by walls. This module mechanizes
that loop over the artifacts the stack already writes —

1. **critical-path components** (obs.critpath): per-round
   fit/wire/wait/agg/other over every ``node.round`` span, averaged
   across nodes and rounds into one ranked "where the round went"
   table;
2. **device-level step phases** (obs.devprof): when the trace carries
   ``devprof.*`` spans, the fit bucket is subdivided into
   data/forward/backward/update/accum so the verdict reaches *inside*
   the jitted program;
3. **recompile counters**: the per-process ``xla/backend_compiles``
   totals the tracer exports — a fat ``other``/``fit`` bucket with a
   nonzero steady-state compile count is a recompile storm, not a
   compute floor.

Usage::

    python -m p2pfl_tpu.obs.perf_report <trace-dir> [--round N] [--json]

Exit code 1 when there is nothing to attribute (no readable trace
files, or no ``node.round`` spans — tracing was off).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any

from p2pfl_tpu.obs import critpath
from p2pfl_tpu.obs.devprof import PHASE_SPANS

_COMPONENTS = ("fit", "wire", "wait", "agg", "other")
_RECOMPILE_KEY = "xla/backend_compiles"


def devprof_phases(doc: dict) -> dict[str, dict[str, float]]:
    """Per-phase totals of the ``devprof.*`` spans across the whole
    merged trace: ``{phase: {total_s, count}}``. Empty when the run was
    not step-profiled (P2PFL_DEVPROF=step)."""
    out: dict[str, dict[str, float]] = {}
    for ev in doc.get("traceEvents", ()):
        if ev.get("ph") != "X" or ev.get("name") not in PHASE_SPANS:
            continue
        rec = out.setdefault(ev["name"], {"total_s": 0.0, "count": 0})
        rec["total_s"] += float(ev.get("dur", 0.0)) / 1e6
        rec["count"] += 1
    for rec in out.values():
        rec["total_s"] = round(rec["total_s"], 6)
    return out


def recompile_total(doc: dict) -> int:
    """Summed post-warm-up backend-compile count across every traced
    process (the tracer's exported counters)."""
    total = 0
    by_pid = doc.get("metadata", {}).get("counters_by_pid", {}) or {}
    for counters in by_pid.values():
        total += int((counters or {}).get(_RECOMPILE_KEY, 0))
    return total


def attribute(doc: dict, round_no: int | None = None) -> dict[str, Any]:
    """The full attribution over one merged trace document.

    Components are the per-round means of the per-node critpath
    decomposition, then averaged across the analyzed rounds — the
    steady-state shape of a round, not one outlier's. The devprof fit
    split reports both the raw phase seconds and each phase's share of
    the fit bucket (phases are proportions: the step-profiled pipeline
    is not the fused production program, so its absolute seconds only
    bound, never equal, the production fit)."""
    result = critpath.analyze(doc, round_no=round_no)
    per_round: list[dict[str, float]] = []
    rounds_used: list[int] = []
    for rn, rec in sorted(result["rounds"].items()):
        nodes = rec["nodes"]
        if not nodes:
            continue
        rounds_used.append(rn)
        mean = {c: sum(n[f"{c}_s"] for n in nodes.values()) / len(nodes)
                for c in _COMPONENTS}
        mean["round"] = sum(n["round_s"] for n in nodes.values()) / len(nodes)
        per_round.append(mean)
    if not per_round:
        return {"rounds": [], "components": {}, "top": None}
    comps = {
        c: round(sum(r[c] for r in per_round) / len(per_round), 6)
        for c in _COMPONENTS
    }
    round_s = sum(r["round"] for r in per_round) / len(per_round)
    top = max(comps, key=comps.get)
    out: dict[str, Any] = {
        "rounds": rounds_used,
        "round_s": round(round_s, 6),
        "components": comps,
        "top": top,
        "recompiles": recompile_total(doc),
    }
    phases = devprof_phases(doc)
    if phases:
        phase_sum = sum(p["total_s"] for p in phases.values())
        split = {}
        for name, p in sorted(phases.items()):
            share = p["total_s"] / phase_sum if phase_sum else 0.0
            split[name] = {
                "total_s": p["total_s"], "count": p["count"],
                "share_of_fit": round(share, 4),
                "fit_s_est": round(share * comps["fit"], 6),
            }
        out["fit_phases"] = split
        if top == "fit" and split:
            top_phase = max(split, key=lambda k: split[k]["total_s"])
            out["top"] = f"fit.{top_phase.split('.', 1)[1]}"
    return out


# ---------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------

def _fmt_report(attr: dict) -> str:
    lines = []
    rounds = attr["rounds"]
    span = (f"round {rounds[0]}" if len(rounds) == 1
            else f"rounds {rounds[0]}-{rounds[-1]}")
    lines.append(f"where the round went (mean over {span}, "
                 f"{attr['round_s']:.3f}s/round)")
    lines.append(f"  {'COMPONENT':<12}{'S/ROUND':>10}{'SHARE':>8}")
    total = sum(attr["components"].values()) or 1.0
    ranked = sorted(attr["components"].items(), key=lambda kv: -kv[1])
    for name, v in ranked:
        lines.append(f"  {name:<12}{v:>10.3f}{100 * v / total:>7.1f}%")
    phases = attr.get("fit_phases")
    if phases:
        lines.append("  fit phases (devprof step profile):")
        lines.append(f"    {'PHASE':<12}{'SPAN_S':>10}{'OF FIT':>8}"
                     f"{'EST S/ROUND':>13}")
        for name, p in sorted(phases.items(),
                              key=lambda kv: -kv[1]["total_s"]):
            short = name.split(".", 1)[1]
            lines.append(
                f"    {short:<12}{p['total_s']:>10.3f}"
                f"{100 * p['share_of_fit']:>7.1f}%"
                f"{p['fit_s_est']:>13.3f}")
    lines.append(f"recompiles: {attr['recompiles']} post-warm-up backend "
                 "compiles across traced processes")
    lines.append(f"top component: {attr['top']}")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="p2pfl_tpu.obs.perf_report")
    ap.add_argument("inputs", nargs="+",
                    help="trace directory (searched recursively for "
                         "*.trace.json) or individual trace files")
    ap.add_argument("--round", type=int, default=None,
                    help="restrict attribution to one round")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable JSON instead of the report")
    args = ap.parse_args(argv)
    doc = critpath.load_merged(args.inputs)
    if doc["metadata"]["files"] == 0:
        print(f"no readable trace files under {args.inputs}",
              file=sys.stderr)
        return 1
    attr = attribute(doc, round_no=args.round)
    if not attr["rounds"]:
        print("no node.round spans found (was tracing enabled?)",
              file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(attr, sort_keys=True))
    else:
        print(_fmt_report(attr))
    return 0


if __name__ == "__main__":
    sys.exit(main())
