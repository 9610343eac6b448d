"""Why does vit32/Krum stall at ~50%? (VERDICT r4 #3)

Runs the IDENTICAL vit32 configuration (32 nodes, ViT-tiny, fully
connected, XLA attention, adam 1e-3, batch 115, seed 4 — bench._vit32)
under four aggregators on the same shards:

  fedavg, trimmedmean, krum (m=1), multi-krum (f=1, m=3 — the bench's)

If FedAvg converges where Krum stalls, the stall is a property of
single/multi-candidate selection under these non-IID-free conditions
(literature-consistent); if FedAvg stalls too, the ViT fine-tune
config itself is the bug. ``--profile easy`` reproduces the round-4
recorded numbers' data; default runs both profiles.

Usage: python scripts/exp_vit32_aggr.py [--rounds 20] [--profile easy|hard]
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO))

from p2pfl_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--profile", default=None,
                    choices=[None, "easy", "hard"])
    ap.add_argument("--aggrs", default=None,
                    help="comma list to restrict (fedavg,trimmedmean,"
                         "krum_m1,multikrum_m3)")
    ap.add_argument("--fused", action="store_true",
                    help="fused fori trajectory (one big compile); "
                         "default is per-round dispatches — for a "
                         "4-aggregator comparison the fused program's "
                         "~6 min cold compile per aggregator dwarfs "
                         "the 20-round run")
    args = ap.parse_args()

    import gc

    import jax

    import bench
    from p2pfl_tpu.core.aggregators import Krum, TrimmedMean

    aggrs = [
        ("fedavg", None, False),
        ("trimmedmean", TrimmedMean(2), True),  # trim COUNT per side
        ("krum_m1", Krum(f=1, m=1), True),
        ("multikrum_m3", Krum(f=1, m=3), True),
    ]
    if args.aggrs:
        want = set(args.aggrs.split(","))
        unknown = want - {a[0] for a in aggrs}
        if unknown:
            raise SystemExit(
                f"unknown aggregators {sorted(unknown)}; "
                f"have {[a[0] for a in aggrs]}"
            )
        aggrs = [a for a in aggrs if a[0] in want]
    profiles = [args.profile] if args.profile else ["easy", "hard"]
    for profile in profiles:
        for tag, aggr, shared in aggrs:
            jax.clear_caches()
            gc.collect()
            run = bench._build(
                32, dataset="cifar10", model="vit-tiny",
                topology="fully", aggregator=aggr,
                partition="iid", samples_per_node=512,
                batch_size=115, learning_rate=1e-3,
                optimizer="adam", seed=4,
                shared_aggregate=shared,
                surrogate_profile=profile,
                model_kwargs={"remat": True,
                              "scan_layers": True})
            try:
                _, _, final, accs = bench._accuracy_run(
                    run, max_rounds=args.rounds, measure_seconds=False,
                    fused=args.fused)
            except Exception as e:
                print(f"{profile}/{tag}: FAILED {e!r}"[:200], flush=True)
                continue
            curve = [round(float(a), 4) for a in accs]
            print(f"{profile}/{tag}: acc_{args.rounds}r={curve[-1]:.4f} "
                  f"final={final:.4f}", flush=True)
            print(f"  curve={curve}", flush=True)
            run.clear()


if __name__ == "__main__":
    main()
