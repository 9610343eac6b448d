"""Device-level profiling below ``node.fit`` — phases, MFU, HBM.

The critical-path plane (obs.critpath) attributes round wall to
fit/wire/wait/agg, but ``fit`` stays a black box: one jitted
``train_epochs`` program whose internals no host clock can see. This
module opens that box two ways, both gated on ``P2PFL_DEVPROF``:

**gauges** (``P2PFL_DEVPROF=1``) — the cheap, always-safe level. After
every fit the learner computes a live MFU / achieved-TFLOPs gauge
(honest FLOPs from obs.cost_model over the measured fit wall) and the
peak-HBM / RSS watermarks, and stows them in ``devprof_last`` for the
status publisher. Nothing touches the training program; the only
added work is a once-per-shape FLOP probe (cached) and two gauge
reads per fit (measured at <= 2% of the fit on the dev box,
docs/perf.md §10).

**step** (``P2PFL_DEVPROF=step``) — explicit opt-in step profiling.
The fit runs a *phase-split* pipeline instead of the fused scan:
separate jitted sub-programs per phase, each drained with
``block_until_ready`` inside its Tracer span —

- ``devprof.data``: per-epoch shuffle + batch layout (host-gather),
- ``devprof.forward``: the forward pass (``jax.vjp`` primal, residuals
  included — a TRUE forward/backward split, no recompute),
- ``devprof.backward``: the vjp cotangent pass alone,
- ``devprof.update``: optimizer update (decay/gate/fused-SGD path),
- ``devprof.accum``: the accumulate-epilogue (metric assembly + final
  drain; federated cross-device runs fold their aggregate here).

Because every span measures work the profiled fit actually executes
exactly once, the phases sum to the wrapping ``learner.fit`` span by
construction — pinned under the same <=10% gate as critpath's
components-vs-wall check. The caveat is the converse: the phase-split
pipeline is NOT the production program (XLA cannot fuse across the
phase boundaries), so step mode measures *where the step's work
lives*, not the fused program's exact wall. Leave it off for timing
runs; the gauges level exists so the dashboard number comes from the
real program.

Spans ride the existing Tracer: disabled tracing keeps the shared
NULL_SPAN no-allocation path, and devprof itself is one env read per
fit when off.
"""

from __future__ import annotations

import functools
import os
from types import SimpleNamespace
from typing import Any

from p2pfl_tpu.obs import cost_model
from p2pfl_tpu.obs.trace import get_tracer

ENV_VAR = "P2PFL_DEVPROF"

# span names the step level records (perf_report joins on them)
PHASE_SPANS = ("devprof.data", "devprof.forward", "devprof.backward",
               "devprof.update", "devprof.accum")


def mode() -> str:
    """``off`` / ``gauges`` / ``step`` from ``P2PFL_DEVPROF``. Read
    per call — fits happen at round cadence, not frame cadence, so an
    env read is free and keeps child processes config-less."""
    raw = os.environ.get(ENV_VAR, "")
    if raw in ("", "0", "off"):
        return "off"
    return "step" if raw == "step" else "gauges"


def enabled() -> bool:
    return mode() != "off"


def step_enabled() -> bool:
    return mode() == "step"


# ---------------------------------------------------------------------
# phase-split fit (step level)
# ---------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _phase_jits(fns) -> SimpleNamespace:
    """Jitted phase programs for one StepFns. Cached on the (frozen,
    hashable) StepFns itself so SharedTrainer federations compile the
    split once, like the production programs."""
    import jax

    return SimpleNamespace(
        prep=jax.jit(fns.prepare_epoch),
        fwd=jax.jit(fns.forward),
        bwd=jax.jit(fns.backward),
        upd=jax.jit(fns.apply_update),
    )


def profiled_epoch(learner, x, y, mask):
    """One epoch of ``learner``'s fit through the phase-split pipeline,
    each phase drained inside its span. Returns ``(state, metrics)``
    with the same ``{"loss": ...}`` contract as ``train_epochs`` —
    the learner adopts the state exactly as on the fused path."""
    import jax

    tracer = get_tracer()
    jits = _phase_jits(learner.fns)
    state = learner.state
    with tracer.span("devprof.data"):
        rng, (bx, by, bm) = jits.prep(state, x, y, mask)
        jax.block_until_ready((bx, by, bm))
    state = state.replace(rng=rng)
    steps = int(bx.shape[0])
    loss_sum = 0.0
    for i in range(steps):
        with tracer.span("devprof.forward"):
            loss, vjp_fn = jits.fwd(state.params, bx[i], by[i], bm[i])
            # drain residuals too: an unblocked residual producer
            # would bill its device time to the backward span
            jax.block_until_ready((loss, vjp_fn))
        with tracer.span("devprof.backward"):
            grads = jits.bwd(vjp_fn, loss)
            jax.block_until_ready(grads)
        with tracer.span("devprof.update"):
            state = jits.upd(state, grads)
            jax.block_until_ready(state.params)
        loss_sum += float(loss)
    with tracer.span("devprof.accum"):
        metrics = {"loss": loss_sum / max(steps, 1)}
        jax.block_until_ready(state)
    return state, metrics


# ---------------------------------------------------------------------
# live gauges (gauges + step levels)
# ---------------------------------------------------------------------

# (id(fns), data shape) -> per-epoch honest FLOPs; learners sharing a
# SharedTrainer hit the same entry, so the probe compiles once
_FLOPS_CACHE: dict[tuple, float | None] = {}


def fit_flops(learner) -> float | None:
    """Cached per-epoch honest FLOPs for one learner (cost_model's
    trip-1 probe; see its docstring for the two corrections)."""
    memo = getattr(learner, "_devprof_flops", None)
    if memo is not None:
        return memo or None  # 0.0 sentinel = probed, unknown
    try:
        shape = tuple(getattr(learner.data.x, "shape", (len(learner.data.x),)))
    except Exception:
        shape = ()
    key = (id(learner.fns), shape, learner.batch_size)
    if key not in _FLOPS_CACHE:
        _FLOPS_CACHE[key] = cost_model.learner_fit_flops(learner)
    flops = _FLOPS_CACHE[key]
    learner._devprof_flops = flops or 0.0
    return flops


def fit_gauges(learner, wall_s: float, epochs: int) -> dict[str, Any]:
    """The ``devprof_*`` status gauges for one completed fit: measured
    wall, achieved TFLOPs and MFU (against one chip — a JaxLearner fit
    runs on one device), and the memory watermarks."""
    out: dict[str, Any] = {"devprof_fit_s": round(wall_s, 4)}
    flops = fit_flops(learner)
    if flops and wall_s > 0:
        achieved = flops * max(epochs, 1) / wall_s
        out["devprof_tflops"] = round(achieved / 1e12, 4)
        util = cost_model.mfu(flops * max(epochs, 1), wall_s, n_devices=1)
        if util is not None:
            out["devprof_mfu"] = round(util, 4)
    out.update(cost_model.memory_watermark())
    return out


def round_gauges(flops: float | None, wall_s: float,
                 n_devices: int) -> dict[str, Any]:
    """Federation-plane gauges: one SPMD round program spanning
    ``n_devices`` (the scenario drivers publish the same number for
    every node — utilization is a property of the shared program)."""
    out: dict[str, Any] = {"devprof_fit_s": round(wall_s, 4)}
    if flops and wall_s > 0:
        out["devprof_tflops"] = round(flops / wall_s / 1e12, 4)
        util = cost_model.mfu(flops, wall_s, n_devices=n_devices)
        if util is not None:
            out["devprof_mfu"] = round(util, 4)
    out.update(cost_model.memory_watermark())
    return out
