"""Every kernel in ``ops/pallas_gemm.py``, compiled by Mosaic
(``interpret=False``) and run on the chip at the north-star per-node
shapes under the 64-wide ``vmap`` the round uses, against its
``jnp``/``optax`` reference at ``tests/test_pallas_gemm.py``'s
tolerances.

    python scripts/chip_kernel_check.py        # needs a TPU

The CPU suite runs the same kernels through the Pallas interpreter,
which checks the grid and masking logic and nothing about Mosaic: block
shapes, VMEM budgets and transposed contractions are only refused on
the chip. A kernel Mosaic refuses, or one that faults at launch, ends
the run with its traceback. Exit 0 = every kernel compiled and matched.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from p2pfl_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from p2pfl_tpu.ops import pallas_gemm as pg  # noqa: E402

N = 64            # federation width
B = 336           # batch
M1 = B * 28 * 28  # conv1 patch rows per node
BF, F32 = jnp.bfloat16, jnp.float32
GEMM_TOL = 2e-2  # tests/test_pallas_gemm.py: bf16 out, f32 accumulate


def _rand(i, shape, dtype):
    # jitted: eagerly, the f32 draw of a [64, 263424, 25] operand would
    # sit in HBM lane-padded to 128 (8.6 GB) before the cast
    return jax.jit(
        lambda k: jax.random.normal(k, shape, F32).astype(dtype)
    )(jax.random.PRNGKey(i))


def _excess(got, want, tol):
    """max(|got - want| - tol * |want|): passes when <= tol — the
    elementwise ``assert_allclose(atol=tol, rtol=tol)`` of the tests,
    reduced on the device."""
    got, want = got.astype(F32), want.astype(F32)
    return jnp.max(jnp.abs(got - want) - tol * jnp.abs(want))


def gemm_cases():
    """(name, fn(*operands) -> tuple of excesses, operand shapes)."""
    v = jax.vmap

    def fwd(x, w):
        got = v(lambda a, b: pg.stream_gemm(a, b, interpret=False))(x, w)
        return (_excess(got, v(lambda a, b: pg._dot(a, b, ((1,), (0,))))(
            x, w).astype(x.dtype), GEMM_TOL),)

    def wgrad(x, g):
        got = v(lambda a, b: pg.stream_wgrad(a, b, interpret=False))(x, g)
        return (_excess(got, v(lambda a, b: pg._dot(a, b, ((0,), (0,))))(
            x, g), GEMM_TOL),)

    def dense(x, w, g):
        dx, dw = v(lambda a, b, c: pg.dense_bwd(a, b, c, interpret=False))(
            x, w, g)
        rx = v(lambda c, b: pg._dot(c, b, ((1,), (1,))))(g, w)
        rw = v(lambda a, c: pg._dot(a, c, ((0,), (0,))))(x, g)
        return _excess(dx, rx.astype(x.dtype), GEMM_TOL), \
            _excess(dw, rw.astype(w.dtype), GEMM_TOL)

    return [
        ("stream_gemm conv1 fwd [263424,25]@[25,32]", fwd,
         ((N, M1, 25), (N, 25, 32))),
        ("stream_gemm conv1 dgrad [263424,32]@[32,25]", fwd,
         ((N, M1, 32), (N, 32, 25))),
        ("stream_wgrad conv1 [263424,25]^T@[263424,32]", wgrad,
         ((N, M1, 25), (N, M1, 32))),
        ("dense_bwd [336,3136]@[3136,2048]", dense,
         ((N, B, 3136), (N, 3136, 2048), (N, B, 2048))),
    ]


def sgd_cases():
    lr, mom = 0.05, 0.9

    def case(shape, p_dt, m_dt, with_acc):
        tol = 1e-2 if BF in (p_dt, m_dt) else 1e-5

        def fn(p, m, g, acc, gate, w):
            tx = optax.sgd(lr, momentum=mom, accumulator_dtype=m_dt)

            def ref(p, m, g, gate):
                st = (optax.TraceState(trace=m), optax.EmptyState())
                u, st = tx.update(g, st, p)
                u = jnp.where(gate > 0, u, jnp.zeros_like(u))
                return optax.apply_updates(p, u), st[0].trace

            rp, rm = jax.vmap(ref)(p, m, g, gate)
            if with_acc:
                gp, gm, ga = jax.vmap(lambda p, m, g, s, a, w: pg.sgd_accum(
                    p, m, g, lr * s, momentum=mom, acc=a, weight=w,
                    interpret=False))(p, m, g, gate, acc, w)
                ra = acc + w.reshape((-1,) + (1,) * (acc.ndim - 1)) \
                    * rp.astype(F32).reshape(acc.shape)
                return (_excess(gp, rp, tol), _excess(gm, rm, tol),
                        _excess(ga, ra, tol))
            gp, gm = jax.vmap(lambda p, m, g, s: pg.sgd_accum(
                p, m, g, lr * s, momentum=mom, interpret=False))(
                    p, m, g, gate)
            return _excess(gp, rp, tol), _excess(gm, rm, tol)

        return fn, tol

    out = []
    for leaf, shape in (("Dense_0", (3136, 2048)), ("Conv_1", (5, 5, 32, 64))):
        for p_dt, m_dt in ((F32, F32), (F32, BF), (BF, BF)):
            for with_acc in (False, True):
                fn, tol = case(shape, p_dt, m_dt, with_acc)
                name = (f"sgd_accum{'+acc' if with_acc else ''} {leaf} "
                        f"{list(shape)} p={jnp.dtype(p_dt).name} "
                        f"m={jnp.dtype(m_dt).name}")
                out.append((name, tol, fn, shape, p_dt, m_dt))
    return out


def main() -> int:
    if jax.default_backend() != "tpu":
        print(f"chip_kernel_check: JAX's default backend is "
              f"{jax.default_backend()!r}, not 'tpu' — Mosaic only "
              f"compiles on the chip", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    print(f"chip_kernel_check device={dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())} jax={jax.__version__}", flush=True)
    failed = []

    def report(name, tol, excesses):
        worst = max(float(e) for e in excesses)
        ok = worst <= tol
        print(f"{'OK  ' if ok else 'FAIL'} {name}: excess {worst:.3g} "
              f"(tol {tol})", flush=True)
        if not ok:
            failed.append(name)

    for name, fn, shapes in gemm_cases():
        ops = [_rand(i, s, BF) for i, s in enumerate(shapes)]
        report(name, GEMM_TOL, jax.jit(fn)(*ops))
        del ops
    for name, tol, fn, shape, p_dt, m_dt in sgd_cases():
        a2 = (N, -1, shape[-1])
        p = _rand(0, (N,) + shape, p_dt)
        m = _rand(1, (N,) + shape, m_dt)
        g = _rand(2, (N,) + shape, p_dt)
        acc = _rand(3, (N,) + shape, F32).reshape(a2)
        # every other node gated off, as the round's trains&alive does
        gate = (jnp.arange(N) % 2).astype(F32)
        w = jnp.linspace(0.0, 1.0, N, dtype=F32)
        report(name, tol, jax.jit(fn)(p, m, g, acc, gate, w))
    if failed:
        print(f"chip_kernel_check: {len(failed)} kernel(s) off their "
              f"reference: {failed}", file=sys.stderr)
        return 1
    print("chip_kernel_check: every kernel compiled by Mosaic and "
          "matched its reference", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
