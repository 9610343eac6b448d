"""fedlint — repo-native static analysis for the federation's
hard-won invariants.

Every rule in :mod:`p2pfl_tpu.analysis.rules` mechanizes an invariant
this codebase learned the expensive way:

- **donation-safety** — the round-9 resume bug: msgpack-restored
  leaves are non-owning views of the blob bytes, and handing them to a
  ``jit(..., donate_argnums=...)`` callee is a heap-layout-dependent
  garbage read; a binding passed to a donating callee must also never
  be read after the call.
- **recompile-hazard** — the §7b storm: ~450 mid-round XLA compiles
  (~32% of wall) from varying stack shapes in the socket hot path,
  plus f-string counter keys allocated per frame when tracing is off.
- **async-hygiene** — the round-11 prober incident: blocking calls on
  the event loop starve heartbeats and get healthy peers evicted, and
  a bare ``asyncio.create_task`` can be garbage-collected mid-flight
  with its exception reported only at interpreter exit.
- **jit-purity** — host side effects (prints, ``np.asarray``, tracer
  counters, attr/dict mutation) inside functions passed to
  ``jax.jit``/``lax.scan``/``shard_map`` either fail at trace time or
  silently run once at trace and never again.
- **atomic-artifact** — the round-12/14 torn-read contracts: every
  published status/checkpoint/flight/metrics artifact must be written
  via tmp+``os.replace`` (or appended one complete ``write()`` per
  line) so a live tailer never sees a torn file.

Entry points::

    python -m p2pfl_tpu.analysis.fedlint <paths>   # lint only
    python -m p2pfl_tpu.analysis [<paths>]         # all passes
                                                   # (fedlint + status-keys sync)

Exit codes are healthcheck-style: 0 = clean, 1 = findings,
2 = operational error (unparseable file, bad arguments). Suppress a
single line with ``# fedlint: disable=<rule>[,<rule>...]``; grandfather
a true-but-deferred finding in ``FEDLINT_BASELINE.json`` (see
docs/analysis.md for the workflow).
"""

from p2pfl_tpu.analysis.core import (  # noqa: F401
    BASELINE_NAME,
    Finding,
    load_baseline,
    run_paths,
    write_baseline,
)
from p2pfl_tpu.analysis.rules import ALL_RULES  # noqa: F401
