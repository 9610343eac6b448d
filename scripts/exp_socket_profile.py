"""Socket-path round-time attribution (VERDICT r4 #6).

The 24-node socket federation records ~3.8 s/round with no story of
where the time goes. This profiles that scenario (``_cfg`` below)
under cProfile and buckets cumulative time into the candidate sinks
the verdict names:

  serialization (core.serialize msgpack+CRC), signing (p2p.tls),
  learner compute (fit/evaluate), socket IO, and event-loop idle
  (wall - CPU: the gossip_period_s-quantized polling waits).

Also sweeps the cheapest candidate knobs (gossip tick, fanout) to
find a win or document the floor.

Round 7 additions, matching the v2 two-segment wire format (header +
raw payload segment, docs/architecture.md):

- ``--train-set-size N`` profiles the uncapped payload-bound round
  (N=24: every node trains and gossips full models — the config the
  zero-copy data plane was A/B'd on, docs/perf.md §7);
- ``--multiproc K`` runs the scenario through ``p2p.launch`` with K
  nodes per child process (K=1 -> 24 processes, K=4 -> 6) instead of
  the in-process simulation, reporting the per-layout round time.
  cProfile cannot cross process boundaries, so this mode reports timing only —
  profile a single child by running it under ``python -m cProfile``.

Usage: python scripts/exp_socket_profile.py [--rounds 3] [--sweep]
         [--train-set-size 8] [--multiproc K]
"""

from __future__ import annotations

import argparse
import cProfile
import io
import os
import pstats
import re
import sys
import time
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO))

# CPU backend: 24 asyncio nodes must not fight for the chip, and the
# socket path's cost is control-plane, not compute
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
               os.environ.get("XLA_FLAGS", "")).strip()
os.environ["XLA_FLAGS"] = flags
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def _cfg(rounds=3, gossip_period_s=0.05, gossip_fanout=6,
         train_set_size=8, aggregation_plane="inline"):
    from p2pfl_tpu.config.schema import (
        DataConfig,
        ProtocolConfig,
        ScenarioConfig,
        TrainingConfig,
    )
    return ScenarioConfig(
        name="sockprof", n_nodes=24, topology="fully",
        data=DataConfig(dataset="mnist", samples_per_node=60),
        training=TrainingConfig(rounds=rounds, epochs_per_round=1,
                                learning_rate=0.05),
        protocol=ProtocolConfig(heartbeat_period_s=0.5,
                                aggregation_timeout_s=60.0,
                                vote_timeout_s=10.0,
                                train_set_size=train_set_size,
                                gossip_fanout=gossip_fanout,
                                gossip_period_s=gossip_period_s),
        aggregation_plane=aggregation_plane,
    )


def run_once(**kw):
    from p2pfl_tpu.p2p.launch import run_simulation
    t0 = time.monotonic()
    out = run_simulation(_cfg(**kw), timeout=280)
    wall = time.monotonic() - t0
    return out, wall


def run_multiproc(nodes_per_proc: int, **kw) -> None:
    """The scenario through real OS processes (p2p.launch), timing only
    — round time = the slowest node's post-warm-up round-loop wall
    (learn_wall_s) / rounds."""
    import tempfile

    from p2pfl_tpu.p2p.launch import launch

    cfg = _cfg(**kw)
    rounds = cfg.training.rounds
    with tempfile.TemporaryDirectory() as td:
        path = Path(td) / "sockprof.json"
        cfg.save(path)
        t0 = time.monotonic()
        results = launch(cfg, path, platform="cpu",
                         nodes_per_proc=nodes_per_proc)
        wall = time.monotonic() - t0
    walls = [r["learn_wall_s"] for r in results if r.get("learn_wall_s")]
    layout = (f"{len(range(0, cfg.n_nodes, nodes_per_proc))}x"
              f"{nodes_per_proc}")
    print(f"multiproc {layout}: nodes_done="
          f"{sum(r.get('round') == rounds for r in results)}"
          f"/{cfg.n_nodes} round_s="
          f"{round(max(walls) / rounds, 3) if walls else None} "
          f"total_wall={wall:.1f}s", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--train-set-size", type=int, default=8)
    ap.add_argument("--multiproc", type=int, default=None, metavar="K",
                    help="run via p2p.launch with K nodes/process "
                         "instead of in-process simulation (no profile)")
    ap.add_argument("--aggregator", choices=("inline", "sidecar"),
                    default="inline",
                    help="aggregation plane: 'sidecar' routes payloads "
                         "through the shared-memory aggd process "
                         "(docs/perf.md §16)")
    args = ap.parse_args()

    if args.multiproc:
        run_multiproc(args.multiproc, rounds=args.rounds,
                      train_set_size=args.train_set_size,
                      aggregation_plane=args.aggregator)
        return

    # ---- attribution run under cProfile ------------------------------
    prof = cProfile.Profile()
    t_cpu0 = time.process_time()
    prof.enable()
    out, wall = run_once(rounds=args.rounds,
                         train_set_size=args.train_set_size,
                         aggregation_plane=args.aggregator)
    prof.disable()
    cpu = time.process_time() - t_cpu0
    print(f"baseline[{args.aggregator}]: round_s={out.get('round_s')} "
          f"wall={wall:.1f}s process_cpu={cpu:.1f}s "
          f"loop_payload_touch_bytes={out.get('loop_payload_touch_bytes')} "
          f"aggd_bytes_ingested={out.get('aggd_bytes_ingested')}",
          flush=True)

    stats = pstats.Stats(prof)
    buckets = {
        "serialize (msgpack+crc)": ("core/serialize", "msgpack"),
        "tls/signing": ("p2p/tls", "hmac", "cryptography", "ssl"),
        "learner compute": ("learning/learner", "jax/_src"),
        "socket io": ("asyncio/selector", "asyncio/sslproto",
                      "streams.py"),
        "protocol/dispatch": ("p2p/node", "p2p/protocol"),
    }
    agg = {k: 0.0 for k in buckets}
    total_tt = 0.0
    for (filename, _, name), (cc, nc, tt, ct, callers) in \
            stats.stats.items():
        total_tt += tt
        for bucket, pats in buckets.items():
            if any(p in filename for p in pats):
                agg[bucket] += tt
                break
    print(f"profiled CPU total {total_tt:.2f}s over wall {wall:.1f}s "
          f"(idle/waiting = {wall - cpu:.1f}s)", flush=True)
    for k, v in sorted(agg.items(), key=lambda kv: -kv[1]):
        print(f"  {k:28s} {v:6.2f}s CPU", flush=True)

    s = io.StringIO()
    pstats.Stats(prof, stream=s).sort_stats("tottime").print_stats(15)
    print(s.getvalue(), flush=True)

    if not args.sweep:
        return

    # ---- knob sweep ---------------------------------------------------
    for kw in (
        {"gossip_period_s": 0.02},
        {"gossip_period_s": 0.01},
        {"gossip_fanout": 12},
        {"gossip_period_s": 0.02, "gossip_fanout": 12},
        {"train_set_size": 24},
    ):
        try:
            out, wall = run_once(rounds=args.rounds, **kw)
            print(f"sweep {kw}: round_s={out.get('round_s')} "
                  f"wall={wall:.1f}", flush=True)
        except Exception as e:
            print(f"sweep {kw}: FAILED {e!r}"[:160], flush=True)


if __name__ == "__main__":
    main()
