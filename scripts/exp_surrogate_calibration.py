"""Calibrate the hard surrogate (VERDICT r4 #5): sweep the writer-style
strength (and optionally label noise) so the 64-node north-star
federation plateaus ~0.85-0.92 — high enough that training works,
low enough that 80% is a threshold the federation must fight for.

Each point runs the REAL headline config (bf16 state, batch 336,
lr 0.05) for a 30-round fused trajectory on the bench chip and prints
the accuracy curve.

Usage: python scripts/exp_surrogate_calibration.py [gamma ...]
"""

from __future__ import annotations

import sys
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO))

from p2pfl_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()


def main() -> None:
    import gc

    import jax
    import jax.numpy as jnp

    import bench
    from p2pfl_tpu.datasets import sources

    gammas = [float(g) for g in sys.argv[1:]] or [0.4, 0.55, 0.7]
    for gamma in gammas:
        sources._HARD["style_gamma"] = gamma
        jax.clear_caches()
        gc.collect()
        run = bench._build(64, momentum_dtype="bf16",
                           model_kwargs={"param_dtype": jnp.bfloat16})
        r80, _, final, accs = bench._accuracy_run(
            run, max_rounds=30, measure_seconds=False, fused=True)
        curve = [round(float(a), 4) for a in accs]
        print(f"gamma={gamma}: r80={r80} final={final:.4f}", flush=True)
        print(f"  curve={curve}", flush=True)
        run.clear()


if __name__ == "__main__":
    main()
