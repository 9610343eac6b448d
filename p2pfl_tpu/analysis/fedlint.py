"""fedlint CLI.

Usage::

    python -m p2pfl_tpu.analysis.fedlint [paths...] [--json]
        [--baseline PATH | --no-baseline] [--write-baseline]
        [--rules rule1,rule2] [--root DIR]

Exit codes (healthcheck-style, for CI alongside ``healthcheck``):
0 = no unsuppressed findings, 1 = findings, 2 = operational error
(unparseable file, unknown rule).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

from p2pfl_tpu.analysis.core import (
    BASELINE_NAME,
    load_baseline,
    run_paths,
    write_baseline,
)
from p2pfl_tpu.analysis.rules import ALL_RULES, RULES_BY_NAME

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m p2pfl_tpu.analysis.fedlint",
        description="AST lint for the federation's learned invariants")
    p.add_argument("paths", nargs="*", default=["p2pfl_tpu"],
                   help="files or directories to lint "
                        "(default: p2pfl_tpu)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit the full result as JSON on stdout")
    p.add_argument("--baseline", default=None, metavar="PATH",
                   help=f"baseline file (default: <repo>/{BASELINE_NAME})")
    p.add_argument("--no-baseline", action="store_true",
                   help="ignore the baseline file entirely")
    p.add_argument("--write-baseline", action="store_true",
                   help="regenerate the baseline from current findings "
                        "and exit 0")
    p.add_argument("--rules", default=None,
                   help="comma-separated rule subset (default: all)")
    p.add_argument("--root", default=None, metavar="DIR",
                   help="directory findings paths are relative to "
                        "(default: the repo root)")
    return p


def main(argv: list[str] | None = None) -> int:
    args = _build_parser().parse_args(argv)
    root = pathlib.Path(args.root) if args.root else _REPO_ROOT

    rules = ALL_RULES
    if args.rules:
        try:
            rules = tuple(RULES_BY_NAME[r.strip()]
                          for r in args.rules.split(","))
        except KeyError as e:
            print(f"fedlint: unknown rule {e.args[0]!r} "
                  f"(have: {', '.join(sorted(RULES_BY_NAME))})",
                  file=sys.stderr)
            return 2

    baseline_path = pathlib.Path(args.baseline) if args.baseline \
        else root / BASELINE_NAME
    try:
        entries = [] if (args.no_baseline or args.write_baseline) \
            else load_baseline(baseline_path)
    except (ValueError, json.JSONDecodeError) as e:
        print(f"fedlint: bad baseline: {e}", file=sys.stderr)
        return 2

    # relative paths that don't exist in the cwd (e.g. the default
    # "p2pfl_tpu" when invoked from elsewhere) resolve against --root
    paths = []
    for s in args.paths:
        p = pathlib.Path(s)
        if not p.exists() and not p.is_absolute() and (root / p).exists():
            p = root / p
        paths.append(p)

    try:
        res = run_paths(paths, rules, root=root,
                        baseline_entries=entries)
    except FileNotFoundError as e:
        print(f"fedlint: no such path: {e}", file=sys.stderr)
        return 2
    except SyntaxError as e:
        print(f"fedlint: cannot parse {e.filename}:{e.lineno}: {e.msg}",
              file=sys.stderr)
        return 2

    if args.write_baseline:
        write_baseline(baseline_path, res.findings)
        print(f"fedlint: wrote {len(res.findings)} entr"
              f"{'y' if len(res.findings) == 1 else 'ies'} to "
              f"{baseline_path}")
        return 0

    if args.as_json:
        print(json.dumps(res.as_dict(), indent=1))
        return res.exit_code

    for f in res.findings:
        print(f.render())
    for e in res.stale_baseline:
        print(f"fedlint: note: stale baseline entry "
              f"{e['path']} ({e['rule']}): {e['code']!r} no longer "
              "matches — remove it")
    print(f"fedlint: {len(res.findings)} finding(s), "
          f"{len(res.pragma_suppressed)} pragma-suppressed, "
          f"{len(res.baselined)} baselined, "
          f"{len(res.stale_baseline)} stale baseline entr"
          f"{'y' if len(res.stale_baseline) == 1 else 'ies'}, "
          f"{res.files} file(s)")
    return res.exit_code


if __name__ == "__main__":
    sys.exit(main())
