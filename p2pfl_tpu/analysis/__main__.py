"""``python -m p2pfl_tpu.analysis`` — run every static pass.

Two passes, run in order with the combined exit code being the max
(healthcheck-style: 0 clean, 1 findings, 2 operational error):

1. **fedlint** over the given paths (default ``p2pfl_tpu/``);
2. **status-keys** three-way sync (monitor.STATUS_KEYS vs the
   publishers' emitted keys vs the renderer/health-rule reads).

Extra CLI flags are forwarded to fedlint (``--json`` etc. apply to the
lint pass only; the key pass keeps its one-line text contract).
"""

from __future__ import annotations

import sys

from p2pfl_tpu.analysis import fedlint, statuskeys


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    print("== fedlint ==")
    lint_rc = fedlint.main(argv)
    print("== status-keys ==")
    status_rc = statuskeys.main()
    return max(lint_rc, status_rc)


if __name__ == "__main__":
    sys.exit(main())
