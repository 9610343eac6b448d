"""Fixture: rank-4 adapters on the query and value projections of the
DeiT-Ti reference (``benchmark/reference/vit_tiny.py``, loaded by file),
over a FROZEN base that the harness hands over: per target
``W + (alpha / rank) * A @ B``. Only ``init``'s leaves are trained.
Both factors start non-zero, so that both have a gradient at step 1.
"""

import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "bench_ref_vit_tiny",
    pathlib.Path(__file__).resolve().parents[4] / "reference" / "vit_tiny.py")
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

RANK, ALPHA = 4, 8.0
L, D, H, K = base.L, base.D, base.H, base.K
SHAPES = {"q_A": (L, D, RANK), "q_B": (L, RANK, H * K),
          "v_A": (L, D, RANK), "v_B": (L, RANK, H * K)}
FROZEN_SHAPES = base.SHAPES


def init(key):
    out = {}
    for i, (name, shape) in enumerate(sorted(SHAPES.items())):
        fan_in = D if name.endswith("_A") else 4 * RANK
        out[name] = jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32) / math.sqrt(fan_in)
    return out


def forward(p, x, q=lambda a: a, frozen=None):
    w = {k: v.astype(jnp.float32) for k, v in frozen.items()}
    for t in ("q", "v"):
        delta = jnp.matmul(p[t + "_A"], p[t + "_B"],
                           precision=base.HI) * (ALPHA / RANK)
        w[t + "_w"] = w[t + "_w"] + delta.reshape(L, D, H, K)
    return base.forward(w, x, q)
