"""Interpret-mode parity + gate behavior for ops.pallas_gemm.

The kernels target TPU Mosaic, but every test here runs the SAME
kernel code through Pallas interpret mode on CPU (tier-1:
``JAX_PLATFORMS=cpu``), so the grid/BlockSpec/masking logic is
exercised without an accelerator. Shapes are the bench shapes scaled
down along M only — K/N tile geometry (25→32, 3136→64-class heads)
is what the kernels are specialized to and is kept exact where it
matters (ragged K=25, full-lane N).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2pfl_tpu.ops import pallas_gemm


def _mk(shape, seed, dtype=jnp.bfloat16):
    return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dtype)


def _close(a, b, tol):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    np.testing.assert_allclose(a, b, atol=tol, rtol=tol)


# M values: block-aligned, sub-block, and ragged edge (the NaN-poison
# regression surface for the wgrad masking). block_m=64 in tests keeps
# interpret-mode runtimes sane while still multi-stepping the grid.
_BLOCK = 64
_MS = [64, 40, 200, 129]


@pytest.mark.parametrize("m", _MS)
def test_stream_gemm_forward_parity(m):
    # conv1 geometry: K=25 (ragged vs the 128 lane), N=32
    x, w = _mk((m, 25), 0), _mk((25, 32), 1)
    got = pallas_gemm.stream_gemm(x, w, block_m=_BLOCK, interpret=True)
    want = (x.astype(jnp.float32) @ w.astype(jnp.float32)).astype(x.dtype)
    _close(got, want, 2e-2)  # bf16 out


@pytest.mark.parametrize("m", _MS)
def test_stream_wgrad_parity(m):
    x, g = _mk((m, 25), 2), _mk((m, 32), 3)
    got = pallas_gemm.stream_wgrad(x, g, block_m=_BLOCK, interpret=True)
    want = x.astype(jnp.float32).T @ g.astype(jnp.float32)
    assert got.dtype == jnp.float32  # f32 accumulator exposed
    # accumulation over ceil(m/64) grid steps in f32: tight tolerance
    _close(got, want, 1e-2 * max(m // _BLOCK, 1))
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("m", _MS)
def test_patches_matmul_grad_parity(m):
    """fwd + dgrad + wgrad through the custom VJP vs pure-XLA autodiff."""
    x, w = _mk((m, 25), 4), _mk((25, 32), 5)

    def loss_pallas(x, w):
        y = pallas_gemm.patches_matmul(x, w, block_m=_BLOCK, interpret=True)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def loss_xla(x, w):
        return jnp.sum((x @ w).astype(jnp.float32) ** 2)

    (gx, gw) = jax.grad(loss_pallas, argnums=(0, 1))(x, w)
    (hx, hw) = jax.grad(loss_xla, argnums=(0, 1))(x, w)
    tol = 0.15  # bf16 squared-loss cotangents
    _close(gx, hx, tol)
    _close(gw, hw, tol)
    assert np.isfinite(np.asarray(gw, np.float32)).all()


@pytest.mark.parametrize("d_in", [448, 300, 900])  # aligned / ragged
def test_dense_bwd_parity(d_in):
    # dense1 geometry scaled: B=batch rows, d_in streamed, H=hidden
    b, h = 16, 32
    x, w, g = _mk((b, d_in), 6), _mk((d_in, h), 7), _mk((b, h), 8)
    dx, dw = pallas_gemm.dense_bwd(x, w, g, block_d=128, interpret=True)
    gf = g.astype(jnp.float32)
    _close(dx, gf @ w.astype(jnp.float32).T, 2e-2)
    _close(dw, x.astype(jnp.float32).T @ gf, 2e-2)


def test_dense_matmul_grad_parity():
    x, w = _mk((16, 300), 9), _mk((300, 32), 10)

    def loss_pallas(x, w):
        y = pallas_gemm.dense_matmul(x, w, block_d=128, interpret=True)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def loss_xla(x, w):
        return jnp.sum((x @ w).astype(jnp.float32) ** 2)

    (gx, gw) = jax.grad(loss_pallas, argnums=(0, 1))(x, w)
    (hx, hw) = jax.grad(loss_xla, argnums=(0, 1))(x, w)
    _close(gx, hx, 0.15)
    _close(gw, hw, 0.15)


def test_vmap_batches_the_kernels():
    """The federation vmaps per-node weights over the kernels — the
    batched grid must produce per-slice results identical to looping."""
    n, m = 3, 129
    xs, ws = _mk((n, m, 25), 11), _mk((n, 25, 32), 12)
    f = lambda a, b: pallas_gemm.patches_matmul(
        a, b, block_m=_BLOCK, interpret=True)
    batched = jax.vmap(f)(xs, ws)
    for i in range(n):
        _close(batched[i], f(xs[i], ws[i]), 1e-6)


def test_rejects_non_2d():
    with pytest.raises(ValueError, match="2-D"):
        pallas_gemm.patches_matmul(jnp.zeros((2, 3, 4)), jnp.zeros((4, 5)))
    with pytest.raises(ValueError, match="2-D"):
        pallas_gemm.dense_matmul(jnp.zeros((2, 3)), jnp.zeros((1, 3, 4)))


# ---- sgd_accum stream (round 17: fused optimizer step) --------------------

# interpret mode lowers through XLA:CPU, whose fp-contraction fuses
# mul+add chains into FMAs (no intermediate f32 rounding) — the kernel
# can land 1 ulp from the two-step optax expression, so these parity
# checks use a few-ulp f32 tolerance rather than bit equality. The
# bit-exact contracts that matter to the federation (gate=0 keeps
# params, gate folding) ARE asserted exactly below.
_SGD_TOL = 1e-5


def _optax_sgd_ref(p, m, g, lr, momentum=0.9):
    # optax.sgd term by term: trace-dtype decay multiply, f32 add,
    # uncast update scaled by -lr, stored trace cast back
    m_new = g + momentum * m
    return ((p + m_new * -lr).astype(p.dtype),
            m_new.astype(m.dtype))


@pytest.mark.parametrize("trace_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows", [64, 7])  # aligned + ragged edge
def test_sgd_accum_update_parity(rows, trace_dtype):
    p = _mk((rows, 130), 24, jnp.float32)
    m = _mk((rows, 130), 25, trace_dtype)
    g = _mk((rows, 130), 26, jnp.float32)
    lr = jnp.float32(0.1)
    got_p, got_m = pallas_gemm.sgd_accum(p, m, g, lr, momentum=0.9,
                                         block_m=16, interpret=True)
    want_p, want_m = _optax_sgd_ref(p, m, g, lr)
    _close(got_p, want_p, _SGD_TOL)
    tol = 1e-2 if trace_dtype == jnp.bfloat16 else _SGD_TOL
    _close(got_m, want_m, tol)
    assert got_m.dtype == trace_dtype  # stored in the accumulator dtype


def test_sgd_accum_fused_accumulate_parity():
    """The accumulate arm: acc_new = acc + weight * p_new (f32), fused
    into the same stream as the optimizer step."""
    p = _mk((40, 96), 27, jnp.float32)
    m = _mk((40, 96), 28, jnp.bfloat16)
    g = _mk((40, 96), 29, jnp.float32)
    acc = _mk((40, 96), 30, jnp.float32)
    lr, w = jnp.float32(0.05), jnp.float32(0.25)
    got_p, got_m, got_a = pallas_gemm.sgd_accum(
        p, m, g, lr, momentum=0.9, acc=acc, weight=w,
        block_m=16, interpret=True)
    want_p, _ = _optax_sgd_ref(p, m, g, lr)
    _close(got_p, want_p, _SGD_TOL)
    assert got_a.dtype == jnp.float32
    _close(got_a, acc + w * want_p, _SGD_TOL)


def test_sgd_accum_gate_zero_keeps_params_bit_exact():
    """lr_gate = lr * 0.0: the federation's where-gate folded into the
    kernel — a gated-off node adds exactly +/-0.0 (params bit-kept)
    while its momentum still decays. This is the contract the learner
    wiring relies on, so it is asserted EXACTLY, not with tolerance."""
    p = _mk((33, 64), 31, jnp.float32)
    m = _mk((33, 64), 32, jnp.bfloat16)
    g = _mk((33, 64), 33, jnp.float32)
    got_p, got_m = pallas_gemm.sgd_accum(p, m, g, jnp.float32(0.0),
                                         momentum=0.9, block_m=16,
                                         interpret=True)
    assert np.array_equal(np.asarray(got_p), np.asarray(p))
    _close(got_m, (g + 0.9 * m).astype(m.dtype), 1e-2)


@pytest.mark.parametrize("shape", [(62,), (5, 5, 4, 8)])
def test_sgd_accum_reshapes_arbitrary_rank_leaves(shape):
    """Bias vectors and conv kernels stream as [prod(:-1), last] and
    come back in their own shape."""
    p = _mk(shape, 34, jnp.float32)
    m = _mk(shape, 35, jnp.float32)
    g = _mk(shape, 36, jnp.float32)
    lr = jnp.float32(0.1)
    got_p, got_m = pallas_gemm.sgd_accum(p, m, g, lr, momentum=0.9,
                                         block_m=8, interpret=True)
    assert got_p.shape == shape and got_m.shape == shape
    want_p, want_m = _optax_sgd_ref(p, m, g, lr)
    _close(got_p, want_p, _SGD_TOL)
    _close(got_m, want_m, _SGD_TOL)


# ---- gate behavior -------------------------------------------------------


@pytest.fixture(autouse=True)
def _fresh_gate(monkeypatch):
    pallas_gemm.clear_cache()
    monkeypatch.delenv(pallas_gemm.ENV_KNOB, raising=False)
    yield
    pallas_gemm.clear_cache()
    pallas_gemm.set_nodes_hint(1)


def test_gate_forces_xla_off_tpu():
    impl = pallas_gemm.choose("patches", ((263424, 25), (25, 32)),
                              jnp.bfloat16)
    assert impl == "xla"
    (rec,) = pallas_gemm.decisions().values()
    assert rec["forced"] and rec["reason"].startswith("backend=")


def test_gate_env_knob_forces_both_ways(monkeypatch):
    shapes = ((263424, 25), (25, 32))
    monkeypatch.setenv(pallas_gemm.ENV_KNOB, "on")
    assert pallas_gemm.choose("patches", shapes, jnp.bfloat16) == "pallas"
    pallas_gemm.clear_cache()
    monkeypatch.setenv(pallas_gemm.ENV_KNOB, "off")
    assert pallas_gemm.choose("patches", shapes, jnp.bfloat16) == "xla"
    rec = next(iter(pallas_gemm.decisions().values()))
    assert rec["forced"] and pallas_gemm.ENV_KNOB in rec["reason"]


def test_gate_caches_per_shape_and_nodes():
    shapes = ((100, 25), (25, 32))
    pallas_gemm.set_nodes_hint(4)
    pallas_gemm.choose("patches", shapes, jnp.bfloat16)
    pallas_gemm.set_nodes_hint(8)
    pallas_gemm.choose("patches", shapes, jnp.bfloat16)
    keys = list(pallas_gemm.decisions())
    assert len(keys) == 2 and any(" n4 " in k for k in keys) \
        and any(" n8 " in k for k in keys)


def test_gate_decisions_are_json_able():
    import json

    pallas_gemm.choose("dense_bwd", ((64, 3136), (3136, 2048)),
                       jnp.bfloat16)
    json.dumps(pallas_gemm.decisions())  # must not raise


def test_gate_unknown_kind_raises(monkeypatch):
    with pytest.raises(ValueError, match="unknown gate kind"):
        pallas_gemm._candidates("nope", ((8, 8), (8, 8)), jnp.float32, 1)


# ---- model path ----------------------------------------------------------


def test_femnist_cnn_trains_through_forced_pallas(monkeypatch):
    """The LEAF CNN's value-and-grad with the kernels FORCED on (CPU →
    interpret mode): the flax wiring (PatchConv + GatedDense custom
    VJPs under vmap) must match the XLA path."""
    monkeypatch.setenv(pallas_gemm.ENV_KNOB, "on")
    pallas_gemm.clear_cache()
    from p2pfl_tpu.models.cnn import SmallCNN

    model = SmallCNN(channels=(4, 8), kernel=5, hidden=32, num_classes=10)
    x = _mk((2, 28, 28, 1), 13, jnp.float32)
    y = jnp.array([1, 7])
    params = model.init(jax.random.PRNGKey(0), x)

    def loss(p, x, y):
        logits = model.apply(p, x)
        return jnp.mean(
            -jax.nn.log_softmax(logits)[jnp.arange(y.shape[0]), y])

    l_pallas, g_pallas = jax.value_and_grad(loss)(params, x, y)
    assert any(rec["impl"] == "pallas"
               for rec in pallas_gemm.decisions().values())

    monkeypatch.setenv(pallas_gemm.ENV_KNOB, "off")
    pallas_gemm.clear_cache()
    l_xla, g_xla = jax.value_and_grad(loss)(params, x, y)

    _close(l_pallas, l_xla, 1e-3)
    flat_p = jax.tree.leaves(g_pallas)
    flat_x = jax.tree.leaves(g_xla)
    for a, b in zip(flat_p, flat_x):
        _close(a, b, 5e-2)


def test_learner_fused_sgd_path_matches_optax(monkeypatch):
    """The learner's fused-SGD wiring with the kernels FORCED on
    (CPU → interpret mode): trains close to the exact tx.update path
    over multiple steps, hits the sgd_accum gate kind, and preserves
    the federation gate contracts bit-exactly (gate=0 freezes params;
    gate=1 equals ungated — lr * 1.0 is exact)."""
    from p2pfl_tpu.learning.learner import make_step_fns
    from p2pfl_tpu.models.cnn import SmallCNN

    model = SmallCNN(channels=(4, 8), kernel=5, hidden=32, num_classes=10)
    x = _mk((32, 28, 28, 1), 40, jnp.float32)
    y = jnp.asarray(np.arange(32) % 10)
    mask = jnp.ones(32, bool)

    def run(st0, fns, **kw):
        train = jax.jit(fns.train_epochs, static_argnames=("epochs",))
        return train(st0, x, y, mask, epochs=2, **kw)

    fns = make_step_fns(model, momentum_dtype="bf16", batch_size=8)
    st0 = fns.init(jax.random.PRNGKey(0), x[:1])
    st_ref, _ = run(st0, fns)  # gate forces xla on CPU → exact optax

    monkeypatch.setenv(pallas_gemm.ENV_KNOB, "on")
    pallas_gemm.clear_cache()
    fns_f = make_step_fns(model, momentum_dtype="bf16", batch_size=8)
    st_fused, _ = run(st0, fns_f)
    assert any(rec["kind"] == "sgd_accum" and rec["impl"] == "pallas"
               for rec in pallas_gemm.decisions().values())
    # every other kernel is forced on too, so the comparison absorbs
    # bf16-GEMM noise compounded over 8 steps — loose but real
    for a, b in zip(jax.tree.leaves(st_ref.params),
                    jax.tree.leaves(st_fused.params)):
        _close(a, b, 1e-1)

    st_g0, _ = run(st0, fns_f, gate=jnp.float32(0.0))
    for a, b in zip(jax.tree.leaves(st0.params),
                    jax.tree.leaves(st_g0.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    st_g1, _ = run(st0, fns_f, gate=jnp.float32(1.0))
    for a, b in zip(jax.tree.leaves(st_fused.params),
                    jax.tree.leaves(st_g1.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
