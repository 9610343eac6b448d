"""Round-5 experiment: bf16 training-state streaming (VERDICT r4 #1).

Regime 1 of the north-star round is HBM-bound on per-step weight-state
traffic (docs/perf.md §2): params read + grads write/read + momentum
read/write. Round 4 moved momentum to bf16 (~5%); params and grads
still stream at f32. This experiment measures the remaining lever:
store the WHOLE training state in bf16 (param_dtype=bf16 -> bf16
params, bf16 grads, bf16 momentum), halving every stream.

Risk: SGD updates below bf16's ~2^-8 relative quantum round away on
the param add. The convergence check (rounds-to-80 + final acc on the
same surrogate/seed) decides whether the speed win is free or needs
stochastic rounding.

Usage: python scripts/exp_bf16_state.py [--quick]
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO))

from p2pfl_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="timing only, skip convergence")
    ap.add_argument("--rounds", type=int, default=30)
    args = ap.parse_args()

    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench

    variants = [
        ("f32_params", {}),
        ("bf16_params", {"model_kwargs": {"param_dtype": jnp.bfloat16}}),
    ]
    results = {}
    for tag, extra in variants:
        jax.clear_caches()
        gc.collect()
        run = bench._build(64, momentum_dtype="bf16", **extra)
        t0 = time.monotonic()
        round_s = bench._time_chained(run)
        print(f"{tag}: round_s={round_s:.4f}  "
              f"(timing took {time.monotonic() - t0:.0f}s)", flush=True)
        res = {"round_s": round_s}
        if not args.quick:
            r80, s80, final, accs = bench._accuracy_run(
                run, max_rounds=args.rounds, measure_seconds=True,
                fused=True)
            res.update(r80=r80, s80=s80, final=round(final, 4),
                       acc_curve=[round(float(a), 4) for a in accs])
            print(f"{tag}: rounds_to_80={r80} seconds_to_80={s80} "
                  f"final={final:.4f}", flush=True)
            print(f"{tag}: curve={res['acc_curve']}", flush=True)
        results[tag] = res
        run.clear()

    a, b = results["f32_params"], results["bf16_params"]
    print(f"\nspeedup: {a['round_s'] / b['round_s']:.3f}x "
          f"({a['round_s']:.4f} -> {b['round_s']:.4f} s/round)", flush=True)
    if not args.quick and a.get("r80") and b.get("r80"):
        print(f"rounds-to-80: {a['r80']} -> {b['r80']}; "
              f"final acc {a['final']} -> {b['final']}", flush=True)


if __name__ == "__main__":
    main()
