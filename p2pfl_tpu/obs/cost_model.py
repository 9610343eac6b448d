"""Honest-FLOP accounting behind the live gauges.

One cost model for ``obs.devprof`` (the live per-node MFU gauge),
``parallel.federated.round_flops`` and ``chip_smoke.py``: they must
agree on what a FLOP is, or the dashboard number silently diverges
from the audited one. Two corrections make the raw ``cost_analysis()``
read honest:

1. **Count only what XLA counts correctly** (docs/perf.md §4): the
   grouped-conv lowering used before round 4 made ``cost_analysis``
   bill conv1 as if it contracted all 64 groups' channels — a ~64x
   per-op inflation (7.2 TF counted vs the analytic 4.2 TF). The fix
   was upstream (the PatchConv model lowers to ops XLA counts right);
   this module keeps the contract by reading the compiled program's
   own cost analysis rather than re-deriving analytic counts that
   would drift from the model zoo.
2. **Un-count the scan body collapse** (docs/perf.md §6.3):
   ``cost_analysis`` counts a ``lax.scan`` body ONCE regardless of
   trip count, so a batched epoch program under-reports by ~#steps.
   :func:`learner_fit_flops` probes with a mathematically equivalent
   trip-count-1 program (batch = the samples the real program uses
   per epoch) — same matmul/conv FLOPs over the same sample count,
   accurately counted — and takes the max of probe and direct read.

The peak table and the watermark reader live here too so every MFU /
HBM number in the repo shares one denominator. Module-level imports
stay jax-free: a parent process can import this without touching the
accelerator.
"""

from __future__ import annotations

import os
from typing import Any

# bf16 peak FLOP/s per chip, by device_kind substring
PEAKS = {
    "v5 lite": 197e12,  # v5e
    "v5litepod": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,  # Trillium
    "v6e": 918e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}

ENV_PEAK = "P2PFL_PEAK_FLOPS"  # per-chip override (tests, odd parts)


def peak_flops(device: Any | None = None) -> float | None:
    """Per-chip bf16 peak FLOP/s; None on a CPU dev box, which has no
    table entry and no MFU. A TPU whose ``device_kind`` is not in
    ``PEAKS`` raises: a utilization against a guessed or missing peak
    would be reported as a device number. ``P2PFL_PEAK_FLOPS``
    overrides — how tests exercise the MFU arithmetic without a TPU,
    and how an unlisted part gets a denominator without a code
    change."""
    env = os.environ.get(ENV_PEAK)
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    if device is None:
        try:
            import jax

            device = jax.local_devices()[0]
        except Exception:
            return None
    kind = getattr(device, "device_kind", "").lower()
    for key, peak in PEAKS.items():
        if key in kind:
            return peak
    if getattr(device, "platform", None) == "tpu":
        raise ValueError(
            f"no peak FLOP/s for TPU device_kind {device.device_kind!r}: "
            f"add it to cost_model.PEAKS with its source, or set "
            f"{ENV_PEAK}")
    return None


def compiled_flops(compiled: Any) -> float | None:
    """The ``flops`` entry of one compiled program's cost analysis;
    None when the backend publishes no analysis (some CPU builds)."""
    try:
        cost = compiled.cost_analysis()
        flops = cost.get("flops") if isinstance(cost, dict) else None
        return float(flops) if flops else None
    except Exception:
        return None


def program_flops(jitfn: Any, *args: Any, **kwargs: Any) -> float | None:
    """Lower + compile ``jitfn`` at the given (aval or concrete)
    arguments and read its counted FLOPs. Compile cost is paid once
    per shape signature (jit/persistent caches apply)."""
    try:
        return compiled_flops(jitfn.lower(*args, **kwargs).compile())
    except Exception:
        return None


def avals(tree: Any) -> Any:
    """Shape/dtype skeleton of a pytree — ``.lower()`` needs only
    shapes, and materializing real arrays just to read their avals
    would double host->device traffic (learner.warm_up's trick)."""
    import jax
    import numpy as np

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
        if not hasattr(a, "aval")
        else jax.ShapeDtypeStruct(a.shape, a.dtype),
        tree,
    )


def learner_fit_flops(learner: Any) -> float | None:
    """Honest FLOPs of ONE epoch of a ``JaxLearner`` fit.

    ``max(direct, probe)``: the direct read of the real scan program
    under-counts by ~#steps (correction 2 above); the probe rebuilds
    the step functions at batch = used-samples so the epoch scan's
    trip count is 1 and every op is counted once per sample actually
    trained. The probe compiles one extra program per (model, shape)
    signature — callers cache (obs.devprof does)."""
    import jax
    import numpy as np

    from p2pfl_tpu.learning.learner import make_step_fns

    if learner.state is None or learner.data is None:
        return None
    x = np.asarray(learner.data.x)
    y = np.asarray(learner.data.y)
    s = len(x)
    bsz = min(learner.batch_size, s)
    if bsz <= 0:
        return None
    used = (s // bsz) * bsz
    state_avals = avals(learner.state)
    xa = jax.ShapeDtypeStruct(x.shape, x.dtype)
    ya = jax.ShapeDtypeStruct(y.shape, y.dtype)
    ma = jax.ShapeDtypeStruct((s,), np.dtype(bool))
    direct = None
    if getattr(learner, "_train_jit", None) is not None:
        direct = program_flops(learner._train_jit, state_avals,
                               xa, ya, ma, epochs=1)
    probe = None
    try:
        fns = make_step_fns(
            learner.model, objective=learner.objective,
            optimizer=learner.optimizer_name,
            learning_rate=learner.learning_rate,
            momentum=learner.momentum,
            weight_decay=learner.weight_decay,
            momentum_dtype=learner.momentum_dtype,
            batch_size=used,
        )
        probe = program_flops(
            jax.jit(fns.train_epochs, static_argnames=("epochs",)),
            state_avals, xa, ya, ma, epochs=1,
        )
    except Exception:
        probe = None
    counted = [f for f in (direct, probe) if f]
    return max(counted) if counted else None


def mfu(flops: float | None, wall_s: float | None,
        n_devices: int = 1, peak: float | None = None) -> float | None:
    """Model-FLOP utilization: achieved FLOP/s over the aggregate peak
    of the devices the program spans. None without a peak (CPU)."""
    if not flops or not wall_s or wall_s <= 0:
        return None
    peak = peak if peak is not None else peak_flops()
    if not peak:
        return None
    return flops / wall_s / (peak * max(int(n_devices), 1))


def memory_watermark() -> dict[str, float]:
    """Peak-memory gauges for a status record: device HBM high-water
    (and its limit) via ``memory_stats()`` where the backend publishes
    them, host RSS peak as the always-available fallback — CPU
    backends publish no device stats, and an OOM-bound socket
    federation is host-memory-bound anyway."""
    out: dict[str, float] = {}
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        limit = stats.get("bytes_limit")
        if peak:
            out["devprof_hbm_peak_mb"] = round(float(peak) / 1e6, 1)
        if limit:
            out["devprof_hbm_limit_mb"] = round(float(limit) / 1e6, 1)
    except Exception:
        pass
    try:
        import resource

        ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # linux reports KiB; darwin reports bytes
        scale = 1024.0 if os.uname().sysname == "Linux" else 1.0
        out["devprof_rss_peak_mb"] = round(ru * scale / 1e6, 1)
    except Exception:
        pass
    return out
