"""Hand-tiled Pallas GEMM kernels for the ops furthest over their
derived floors (docs/perf.md §6.2): conv1's patches GEMM and dense1's
backward.

Why these two: the §6.2 ceiling table puts the headline FEMNIST round
at 17.9% device-true MFU against a 31% achievable ceiling, and the
overage is concentrated in (a) conv1's `[M≈263k, 25] @ [25, 32]`
patches matmul (13.3 ms measured vs a 2.8 ms floor — XLA's grouped /
small-tile lowering, not the MXU tile fill, is what loses the 4.7x)
and (b) dense1's backward (7.5 ms vs 2.9 ms — two separate XLA GEMMs
re-streaming the [3136, 2048] weight and both activations through
HBM). Neither kernel can beat the MXU's 128-lane tile fill — the
floors already price that in — so the target is XLA's overhead above
the floor, not the floor itself.

Kernel shapes (per federated node; the federation's `vmap` over the
node axis batches `pallas_call` by prepending a grid dimension, so
kernels are written 2-D):

- ``stream_gemm``: ``[M, K] @ [K, N]`` with K, N small (≤128 each,
  i.e. one MXU tile). The weight stays VMEM-stationary across the
  whole grid; M streams through in ``block_m`` row tiles. Covers
  conv1 fwd (``patches @ wf``) and conv1 dgrad (``g @ wf^T`` — same
  shape class with K and N swapped).
- ``stream_wgrad``: ``[M, K]^T @ [M, N] -> [K, N]`` — M-streamed
  accumulation into a stationary f32 output block. Covers conv1
  wgrad. Ragged-edge M tiles mask BOTH operands: an out-of-bounds
  block row may read garbage (even NaN), and ``NaN * 0 = NaN`` would
  poison the accumulator if only one side were zeroed.
- ``_dense_bwd_kernel``: fused dgrad+wgrad for ``y = x @ w`` — grid
  over the contraction-free ``d_in`` axis with the cotangent
  VMEM-stationary, producing ``dx`` and ``dw`` tiles from one pass
  over ``x`` and ``w`` (one HBM read of each instead of XLA's two
  independent GEMMs).
- ``sgd_accum`` (round 17): fused SGD(+momentum) update — and
  optionally a weighted FedAvg accumulate — as one M-streamed
  elementwise pass: params, momentum and grads are read once and the
  new params/momentum (plus ``acc + w * p_new``) written back,
  attacking the §6.4 "SGD state stream" overage (6.3 ms measured vs a
  5.0 ms floor). Arithmetic replicates ``optax.sgd`` bit-for-bit
  (same promotion order, accumulator-dtype cast last).

Selection: every call site asks :func:`choose`, which measures the
Pallas and XLA variants at the actual per-node shape, vmapped as wide
as the federation or as a 1 GiB operand budget allows
(:func:`_measure_width`), on the real backend — scan-slope timing
(:func:`_slope_ms`: a longer scan less a shorter one, net of dispatch
and sync) — caches the verdict per shape, and takes
XLA whenever Pallas does not win. "XLA measured faster" is a decision;
"the kernel broke" is not: on a TPU a kernel that fails to lower,
compile or launch raises out of the gate. ``P2PFL_PALLAS_GEMM``
(auto|on|off) forces either path; non-TPU backends always take XLA
(interpret-mode Pallas is a correctness tool, not a fast path). The
decision table (:func:`decisions`) is what ``benchmark/run.py`` and
its readers count as ``kernels.pallas_picked`` and
``kernels.gate_measure_s``.

Block shapes are what Mosaic accepts on a v5e (compiled at the
north-star shapes, PERF.md "Bring-up"): a block's last dimension is the
whole axis or a multiple of the 128-lane tile, and every streamed tile
is sized against the 16 MiB scoped-VMEM default, double buffering
included.
"""

from __future__ import annotations

import functools
import math
import os
import time

import jax
import jax.numpy as jnp

__all__ = [
    "patches_matmul",
    "dense_matmul",
    "sgd_accum",
    "fedavg_accum",
    "stream_gemm",
    "stream_wgrad",
    "dense_bwd",
    "choose",
    "decisions",
    "set_nodes_hint",
    "clear_cache",
]

#: env knob: "auto" (measure, default), "on"/"pallas" (force kernels),
#: "off"/"xla" (force XLA). Documented in README + docs/perf.md §6.4.
ENV_KNOB = "P2PFL_PALLAS_GEMM"

_BLOCK_M = 2048  # M rows per grid step of 16-bit operands (conv1:
# 129 tiles of 263424); wider dtypes stream proportionally fewer rows
_BLOCK_D = 256   # d_in columns per dense-bwd grid step of 16-bit
# operands: a multiple of the 128-lane tile (3136 = 12 x 256 + 64, the
# ragged edge is masked on write)
_SGD_TILE = 128 * 1024  # elements per streamed sgd tile (512 KiB f32)
_SGD_COLS = 2048        # widest sgd tile; wider leaves tile columns too


def _interp(interpret):
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _rows(block: int, total: int, dtype) -> int:
    """Rows per grid step. ``block`` counts rows of 16-bit operands; a
    32-bit operand streams half as many, so the tile's VMEM footprint
    stays the one that compiled ([2048, 800] f32 tiles overflow the
    scoped limit, [1024, 800] fit)."""
    return min(max(block * 2 // jnp.dtype(dtype).itemsize, 1), total)


# ---------------------------------------------------------------------------
# stream_gemm: [M, K] @ [K, N], weight stationary, M streamed
# ---------------------------------------------------------------------------


def _gemm_kernel(x_ref, w_ref, o_ref):
    o_ref[:] = _dot(x_ref[:], w_ref[:], ((1,), (0,))).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _stream_gemm(x, w, block_m, interpret):
    import jax.experimental.pallas as pl

    m, k = x.shape
    n = w.shape[1]
    bm = _rows(block_m, m, x.dtype)
    out = pl.pallas_call(
        _gemm_kernel,
        grid=(pl.cdiv(m, bm),),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),  # stationary
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(x, w)
    return out


def stream_gemm(x, w, *, block_m: int = _BLOCK_M,
                interpret: bool | None = None):
    """``x [M, K] @ w [K, N]`` with w VMEM-stationary, f32 accumulate.

    Raw kernel (no custom VJP) — the building block for
    :func:`patches_matmul`'s forward and dgrad.
    """
    return _stream_gemm(x, w, int(block_m), _interp(interpret))


# ---------------------------------------------------------------------------
# stream_wgrad: x^T @ g accumulated over M tiles into a stationary block
# ---------------------------------------------------------------------------


def _wgrad_kernel(x_ref, g_ref, o_ref, *, m_total, block_m):
    import jax.experimental.pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)

    # ragged edge: mask BOTH operands — out-of-bounds block rows are
    # unspecified (possibly NaN) and NaN * 0 = NaN would poison the
    # accumulator through either side of the dot
    rows = jax.lax.broadcasted_iota(jnp.int32, (x_ref.shape[0], 1), 0)
    ok = rows + i * block_m < m_total
    x = jnp.where(ok, x_ref[:], 0)
    g = jnp.where(ok, g_ref[:], 0)
    o_ref[:] += _dot(x, g, ((0,), (0,))).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _stream_wgrad(x, g, block_m, interpret):
    import jax.experimental.pallas as pl

    m, k = x.shape
    n = g.shape[1]
    bm = _rows(block_m, m, x.dtype)
    out = pl.pallas_call(
        functools.partial(_wgrad_kernel, m_total=m, block_m=bm),
        grid=(pl.cdiv(m, bm),),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((k, n), lambda i: (0, 0)),  # stationary
        out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
        interpret=interpret,
    )(x, g)
    return out


def stream_wgrad(x, g, *, block_m: int = _BLOCK_M,
                 interpret: bool | None = None):
    """``x [M, K]^T @ g [M, N] -> [K, N]`` f32, M-streamed accumulate."""
    return _stream_wgrad(x, g, int(block_m), _interp(interpret))


# ---------------------------------------------------------------------------
# patches_matmul: stream_gemm with a Pallas VJP (conv1 fwd + dgrad + wgrad)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _patches_mm(x, w, block_m, interpret):
    return _stream_gemm(x, w, block_m, interpret)


def _patches_mm_fwd(x, w, block_m, interpret):
    return _patches_mm(x, w, block_m, interpret), (x, w)


def _patches_mm_bwd(block_m, interpret, res, g):
    x, w = res
    # dgrad is the same small-tile shape class ([M, N] @ [N, K]);
    # dead-code eliminated when x is a non-differentiated input
    # (conv1: the image layer needs no dx)
    dx = _stream_gemm(g, w.T, block_m, interpret).astype(x.dtype)
    dw = _stream_wgrad(x, g, block_m, interpret).astype(w.dtype)
    return dx, dw


_patches_mm.defvjp(_patches_mm_fwd, _patches_mm_bwd)


def patches_matmul(x, w, *, block_m: int = _BLOCK_M,
                   interpret: bool | None = None):
    """``x [M, K] @ w [K, N]`` (K, N ≤ 128) — Pallas fwd, dgrad and
    wgrad. The conv1 hot path: patches flattened to 2-D rows."""
    if x.ndim != 2 or w.ndim != 2:
        raise ValueError(f"2-D operands required, got {x.shape} @ {w.shape}")
    return _patches_mm(x, w, int(block_m), _interp(interpret))


# ---------------------------------------------------------------------------
# dense_bwd: fused dgrad + wgrad for y = x @ w (dense1 backward)
# ---------------------------------------------------------------------------


def _dense_bwd_kernel(g_ref, x_ref, w_ref, dx_ref, dw_ref):
    # g [B, H] stationary; x [B, TD], w [TD, H] stream over d_in.
    # Contractions run over full axes (B, H) — a ragged d_in edge only
    # produces garbage in output rows/columns the BlockSpec masks off
    # on write, so no operand masking is needed here.
    g = g_ref[:]
    dx_ref[:] = _dot(g, w_ref[:], ((1,), (1,))).astype(dx_ref.dtype)
    dw_ref[:] = _dot(x_ref[:], g, ((0,), (0,))).astype(dw_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _dense_bwd(x, w, g, block_d, interpret):
    import jax.experimental.pallas as pl

    b, d_in = x.shape
    h = w.shape[1]
    bd = _rows(block_d, d_in, x.dtype)
    if bd < d_in:
        # d_in is the LAST dimension of the x/dx blocks: Mosaic takes
        # the whole axis or a multiple of the 128-lane tile there
        bd = max(bd // 128, 1) * 128
    dx, dw = pl.pallas_call(
        _dense_bwd_kernel,
        grid=(pl.cdiv(d_in, bd),),
        in_specs=[
            pl.BlockSpec((b, h), lambda i: (0, 0)),  # cotangent stationary
            pl.BlockSpec((b, bd), lambda i: (0, i)),
            pl.BlockSpec((bd, h), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b, bd), lambda i: (0, i)),
            pl.BlockSpec((bd, h), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, d_in), x.dtype),
            jax.ShapeDtypeStruct((d_in, h), w.dtype),
        ],
        interpret=interpret,
    )(g, x, w)
    return dx, dw


def dense_bwd(x, w, g, *, block_d: int = _BLOCK_D,
              interpret: bool | None = None):
    """Fused backward of ``y = x @ w``: ``(dx, dw)`` from one pass
    over x and w (cotangent ``g`` VMEM-stationary)."""
    return _dense_bwd(x, w, g, int(block_d), _interp(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dense_mm(x, w, block_d, interpret):
    # forward stays XLA — it sits near its floor (§6.2); only the
    # backward is over-floor enough to pay for a kernel
    return _dot(x, w, ((1,), (0,))).astype(x.dtype)


def _dense_mm_fwd(x, w, block_d, interpret):
    return _dense_mm(x, w, block_d, interpret), (x, w)


def _dense_mm_bwd(block_d, interpret, res, g):
    x, w = res
    dx, dw = _dense_bwd(x, w, g.astype(x.dtype), block_d=block_d,
                        interpret=interpret)
    return dx, dw


_dense_mm.defvjp(_dense_mm_fwd, _dense_mm_bwd)


def dense_matmul(x, w, *, block_d: int = _BLOCK_D,
                 interpret: bool | None = None):
    """``x [B, D] @ w [D, H]`` — XLA forward, fused Pallas backward."""
    if x.ndim != 2 or w.ndim != 2:
        raise ValueError(f"2-D operands required, got {x.shape} @ {w.shape}")
    return _dense_mm(x, w, int(block_d), _interp(interpret))


# ---------------------------------------------------------------------------
# sgd_accum: fused SGD(+momentum) update + optional weighted accumulate
# ---------------------------------------------------------------------------


def _decayed_trace(m_ref, momentum):
    # replicate optax.sgd's promotion order exactly: ``decay * trace``
    # is a trace-dtype multiply (numpy weak typing casts the Python
    # float down), THEN the f32 grad add promotes. Pallas evaluates
    # narrow-dtype arithmetic in f32 WITHOUT the intermediate rounding,
    # so round the product back to the trace dtype by hand — a
    # bf16*bf16 product fits f32 exactly, making round-once identical
    # to a native bf16 multiply.
    decay = jnp.asarray(momentum, m_ref.dtype).astype(jnp.float32)
    return (decay * m_ref[:].astype(jnp.float32)).astype(m_ref.dtype)


def _sgd_kernel(p_ref, m_ref, g_ref, lr_ref, p_out, m_out, *, momentum):
    # the accumulator-dtype cast applies to the STORED state only; the
    # param update consumes the uncast f32 trace (optax semantics)
    m_new = g_ref[:] + _decayed_trace(m_ref, momentum)
    p_out[:] = (p_ref[:] + m_new * -lr_ref[0, 0]).astype(p_out.dtype)
    m_out[:] = m_new.astype(m_out.dtype)


def _sgd_accum_kernel(p_ref, m_ref, g_ref, lr_ref, acc_ref, w_ref,
                      p_out, m_out, acc_out, *, momentum):
    m_new = g_ref[:] + _decayed_trace(m_ref, momentum)
    p_new = (p_ref[:] + m_new * -lr_ref[0, 0]).astype(p_out.dtype)
    p_out[:] = p_new
    m_out[:] = m_new.astype(m_out.dtype)
    acc_out[:] = acc_ref[:] + w_ref[0, 0] * p_new.astype(jnp.float32)


def _sgd_specs(shape, block_m):
    """``(grid, tile spec, scalar spec)`` of the sgd kernels. One
    streamed tile holds at most ``_SGD_TILE`` elements, so the
    accumulate kernel's seven streams, double buffered, stay under the
    scoped-VMEM default at f32. (The tile as first written, 2048 rows by
    the leaf's full width, asked for 160 MB of the v5e's 128 on
    ``Dense_0``'s [3136, 2048].) Rows come in multiples of 32 — the
    sublane tile of every dtype down to int8 — and columns in multiples
    of the 128-lane tile, or the whole axis."""
    import jax.experimental.pallas as pl

    rows, cols = shape
    bc = min(cols, _SGD_COLS)
    lanes = -(-bc // 128) * 128
    bm = min(block_m, max(_SGD_TILE // lanes // 32, 1) * 32, rows)
    grid = (pl.cdiv(rows, bm), pl.cdiv(cols, bc))
    # elementwise: a ragged edge tile only reads garbage into outputs
    # the BlockSpec masks off on write — nothing crosses elements, so
    # no operand masking is needed (unlike the wgrad reduce)
    tile = pl.BlockSpec((bm, bc), lambda i, j: (i, j))
    one = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    return grid, tile, one


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _sgd(p, m, g, lr, momentum, block_m, interpret):
    import jax.experimental.pallas as pl

    grid, tile, one = _sgd_specs(p.shape, block_m)
    return pl.pallas_call(
        functools.partial(_sgd_kernel, momentum=momentum),
        grid=grid,
        in_specs=[tile, tile, tile, one],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct(p.shape, p.dtype),
            jax.ShapeDtypeStruct(m.shape, m.dtype),
        ],
        interpret=interpret,
    )(p, m, g, lr)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _sgd_acc(p, m, g, lr, acc, w, momentum, block_m, interpret):
    import jax.experimental.pallas as pl

    grid, tile, one = _sgd_specs(p.shape, block_m)
    return pl.pallas_call(
        functools.partial(_sgd_accum_kernel, momentum=momentum),
        grid=grid,
        in_specs=[tile, tile, tile, one, tile, one],
        out_specs=[tile, tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct(p.shape, p.dtype),
            jax.ShapeDtypeStruct(m.shape, m.dtype),
            jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        ],
        interpret=interpret,
    )(p, m, g, lr, acc, w)


def _as2d(a):
    return a.reshape(-1, a.shape[-1]) if a.ndim >= 2 else a.reshape(1, -1)


def sgd_accum(p, m, g, lr_gate, *, momentum: float,
              acc=None, weight=None, block_m: int = _BLOCK_M,
              interpret: bool | None = None):
    """Fused ``optax.sgd`` step — and optionally the FedAvg
    contribution — in one streaming pass over the leaf.

    ``m_new = g + momentum * m``; ``p_new = p + m_new * -lr_gate``;
    with ``acc``/``weight`` given, also ``acc_new = acc + weight *
    p_new`` (f32) so the optimizer step and the aggregation
    contribution read the params once. ``lr_gate`` is the learning
    rate pre-multiplied by the federation's update gate (1.0/0.0):
    a gated-off leaf adds exactly ±0.0, i.e. keeps its params
    bit-exactly while its momentum decays — the learner's ``where``
    gate semantics. Returns ``(p_new, m_stored)`` or ``(p_new,
    m_stored, acc_new)``; arbitrary-rank leaves are streamed as
    ``[prod(shape[:-1]), shape[-1]]``.
    """
    shape = p.shape
    p2, m2, g2 = _as2d(p), _as2d(m), _as2d(g)
    lr2 = jnp.asarray(lr_gate, jnp.float32).reshape(1, 1)
    itp = _interp(interpret)
    if acc is None:
        p_new, m_new = _sgd(p2, m2, g2, lr2, float(momentum),
                            int(block_m), itp)
        return p_new.reshape(shape), m_new.reshape(m.shape)
    w2 = jnp.asarray(weight, jnp.float32).reshape(1, 1)
    acc2 = _as2d(acc)
    p_new, m_new, acc_new = _sgd_acc(p2, m2, g2, lr2, acc2, w2,
                                     float(momentum), int(block_m), itp)
    return (p_new.reshape(shape), m_new.reshape(m.shape),
            acc_new.reshape(acc.shape))


def fedavg_accum(p, acc, weight, block_m: int = _BLOCK_M,
                 interpret: bool | None = None):
    """FedAvg accumulate as a *null* ``sgd_accum`` step (round 20):
    ``acc_new = acc + weight * p`` (f32) in one streaming pass, sharing
    the ``_sgd_accum_kernel`` the learner's fused optimizer uses — and
    therefore the same measured ``choose("sgd_accum", ...)`` decision.

    The optimizer half runs with ``g = 0``, ``momentum = 0``,
    ``lr_gate = 0``: ``m_new = 0``, ``p_new = (p + 0 * -0).astype(
    p.dtype) = p`` — the param stream passes through untouched (the
    ``+0.0`` can at most flip a ``-0.0`` to ``+0.0``, inert inside the
    weighted sum), so only the accumulate line does work. This is how
    the cross-device round's fit-epilogue accumulate
    (``parallel/federated.py``) rides the kernel without a second
    kernel body to parity-test. ``acc`` must match ``p``'s streamed 2-D
    shape ``[prod(shape[:-1]), shape[-1]]``. Returns ``acc_new`` only.
    """
    z = jnp.zeros_like(p)
    _, _, acc_new = sgd_accum(p, z, z, 0.0, momentum=0.0, acc=acc,
                              weight=weight, block_m=block_m,
                              interpret=interpret)
    return acc_new


# ---------------------------------------------------------------------------
# measured auto-select gate
# ---------------------------------------------------------------------------

_decisions: dict[str, dict] = {}
_nodes_hint: int = 1


def set_nodes_hint(n: int) -> None:
    """Tell the gate how wide the federation's node vmap is — the
    microbenchmark measures the batched shape actually run. Called by
    ``parallel.federated.init_federation``; defaults to 1 (single
    learner)."""
    global _nodes_hint
    _nodes_hint = max(int(n), 1)


def decisions() -> dict[str, dict]:
    """JSON-able copy of every gate decision this process made
    (impl, ms per variant, forcing), for ``benchmark/`` and chip_smoke."""
    return {k: dict(v) for k, v in _decisions.items()}


def clear_cache() -> None:
    _decisions.clear()


def _repeat_program(fn, reps: int):
    """``fn`` repeated ``reps`` times in one jitted scan — the program
    :func:`_slope_ms` times."""

    @jax.jit
    def run(x0, *rest):
        def body(x, _):
            out = fn(x, *rest)
            first = jax.tree.leaves(out)[0]
            # fold one element back into the carry so scan cannot
            # hoist or elide the repeated call
            return x + (first.reshape(-1)[0] * 0).astype(x.dtype), None

        return jax.lax.scan(body, x0, None, length=reps)[0]

    return run


def _slope_ms(fn, args, r1: int = 2, r2: int = 6) -> float:
    """Per-call ms net of dispatch/sync overhead: time a scan of r2
    repeats minus a scan of r1 repeats over (r2 - r1), so that what
    both scans pay once cancels."""

    def repeat(reps):
        # lowered and compiled ahead of time: a plain call of the jitted
        # function would be staged into whatever trace encloses the
        # gate's call site and hand back a tracer, not a timing
        run = _repeat_program(fn, reps).lower(*args).compile()
        run(*args).block_until_ready()  # warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            run(*args).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    return max((repeat(r2) - repeat(r1)) / (r2 - r1) * 1e3, 0.0)


_MEASURE_BYTES = 1 << 30  # operand bytes one measurement may allocate


def _measure_width(specs) -> int:
    """How many nodes of the vmap the measurement runs. The node axis
    is a grid dimension of every kernel and a batch dimension of every
    XLA candidate, so cost is linear in it and a narrower harness ranks
    the candidates the same — while the full 64-wide one, at the
    evaluation batch, asked for 12.25 GB next to a resident federation
    on a 16 GB v5e. Operands are sized as HBM holds them: the minor
    dimension padded to the 128-lane tile (a 25-wide patches row
    occupies 128)."""
    n = specs[0].shape[0]
    total = sum(
        math.prod(s.shape[:-1]) * (-(-s.shape[-1] // 128) * 128)
        * jnp.dtype(s.dtype).itemsize for s in specs)
    return max(1, min(n, _MEASURE_BYTES * n // total))


def _measure(kind: str, key: str, pallas_fn, xla_fn, specs) -> str:
    # every call site sits inside the round's jit/vmap/scan traces, and
    # a timing needs concrete arrays on the device whatever trace
    # encloses us: operands are built under compile-time eval, the
    # candidates run as ahead-of-time compiled programs (_slope_ms).
    # No except: a kernel Mosaic refuses, or one that faults at launch,
    # is a defect to surface, not a reason to answer "xla".
    t0 = time.perf_counter()
    width = _measure_width(specs)
    with jax.ensure_compile_time_eval():
        args = tuple(jnp.zeros((width,) + s.shape[1:], s.dtype)
                     for s in specs)
    p_ms = _slope_ms(pallas_fn, args)
    x_ms = _slope_ms(xla_fn, args)
    impl = "pallas" if p_ms < x_ms else "xla"
    _decisions[key] = {"kind": kind, "impl": impl, "forced": False,
                       "pallas_ms": round(p_ms, 4), "xla_ms": round(x_ms, 4),
                       "nodes_measured": width,
                       # what deciding cost: both candidates compiled
                       # (or loaded) and timed
                       "measure_s": time.perf_counter() - t0}
    return impl


def _backend() -> str:
    return jax.default_backend()


def choose(kind: str, shapes: tuple, dtype) -> str:
    """Pick "pallas" or "xla" for one op instance.

    ``kind``: "patches" (conv1 fwd+bwd GEMM), "dense_bwd" (dense1
    fused backward), or "sgd_accum" (fused optimizer stream).
    ``shapes``: the per-node operand shapes as seen at the call site.
    Measured decisions are cached per (kind, shapes, dtype, nodes,
    backend); env/backend forcings are recorded too so the decision
    table shows WHY a path ran.
    """
    backend = _backend()
    dt = jnp.dtype(dtype).name
    n = _nodes_hint
    key = f"{kind} n{n} {'x'.join(map(str, shapes[0]))}@" \
          f"{'x'.join(map(str, shapes[1]))} {dt} {backend}"
    cached = _decisions.get(key)
    if cached is not None:
        return cached["impl"]

    env = os.environ.get(ENV_KNOB, "auto").strip().lower()
    if env in ("off", "0", "xla", "false"):
        _decisions[key] = {"kind": kind, "impl": "xla", "forced": True,
                           "reason": f"{ENV_KNOB}={env}"}
    elif env in ("on", "1", "pallas", "true"):
        _decisions[key] = {"kind": kind, "impl": "pallas", "forced": True,
                           "reason": f"{ENV_KNOB}={env}"}
    elif backend != "tpu":
        # interpret-mode Pallas is for parity testing, never for speed
        _decisions[key] = {"kind": kind, "impl": "xla", "forced": True,
                           "reason": f"backend={backend}"}
    elif _flops(kind, shapes) * n < _MIN_GATE_FLOPS:
        # don't burn measurement time on trivial instances (model.init
        # traces with batch 1; tiny eval shapes) — XLA is fine there
        _decisions[key] = {"kind": kind, "impl": "xla", "forced": True,
                           "reason": "below measurement threshold"}
    else:
        return _measure(kind, key, *_candidates(kind, shapes, dtype, n))
    return _decisions[key]["impl"]


_MIN_GATE_FLOPS = 1e8  # per-instance GEMM flops worth measuring


def _flops(kind, shapes) -> float:
    (m, k) = shapes[0]
    if kind == "sgd_accum":
        # memory-bound elementwise stream: score by elements moved,
        # not GEMM flops (which would never clear the threshold)
        return 8.0 * m * k
    (_, n_out) = shapes[1]
    mult = 2.0 if kind == "dense_bwd" else 1.0  # bwd = two GEMMs
    return 2.0 * m * k * n_out * mult


def _candidates(kind: str, shapes, dtype, n):
    """``(pallas_fn, xla_fn, operand specs)`` of one gate kind at the
    ``n``-wide vmapped shape the round runs — the two programs
    :func:`_measure` times."""
    S = jax.ShapeDtypeStruct
    if kind == "patches":
        (m, k), (_, out_n) = shapes
        specs = (S((n, m, k), dtype), S((n, k, out_n), dtype))

        def pallas_fn(x, w):
            f = lambda a, b: patches_matmul(a, b)
            return _grad_through(jax.vmap(f))(x, w)

        def xla_fn(x, w):
            f = lambda a, b: _dot(a, b, ((1,), (0,))).astype(a.dtype)
            return _grad_through(jax.vmap(f))(x, w)

        return pallas_fn, xla_fn, specs
    if kind == "dense_bwd":
        (b, d_in), (_, h) = shapes
        specs = (S((n, b, d_in), dtype), S((n, d_in, h), dtype))

        def pallas_fn(x, w):
            f = lambda a, b: dense_matmul(a, b)
            return _grad_through(jax.vmap(f))(x, w)

        def xla_fn(x, w):
            f = lambda a, b: _dot(a, b, ((1,), (0,))).astype(a.dtype)
            return _grad_through(jax.vmap(f))(x, w)

        return pallas_fn, xla_fn, specs
    if kind == "sgd_accum":
        (m_rows, cols) = shapes[0]
        leaf = S((n, m_rows, cols), dtype)
        specs = (leaf, leaf, leaf, S((n,), jnp.float32))

        def pallas_fn(p, mom, g, lr):
            f = lambda a, b, c, l: sgd_accum(a, b, c, l, momentum=0.9)
            return jax.vmap(f)(p, mom, g, lr)

        def xla_fn(p, mom, g, lr):
            def f(a, b, c, l):
                m_new = c + 0.9 * b
                return a + m_new * -l, m_new.astype(b.dtype)

            return jax.vmap(f)(p, mom, g, lr)

        return pallas_fn, xla_fn, specs
    raise ValueError(f"unknown gate kind: {kind!r}")


def _grad_through(f):
    """Measure fwd+bwd together — the gate's question is the round's
    train step, which always differentiates these ops."""

    def g(x, w):
        loss = lambda a, b: jnp.sum(f(a, b).astype(jnp.float32))
        dx, dw = jax.grad(loss, argnums=(0, 1))(x, w)
        return dx

    return g
