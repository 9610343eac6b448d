"""The federation reference on a configuration whose labels are a
position's, not a row's, and whose loss is its module's own: a toy
next-token module (``data/bench/reference/toy_seq.py``) followed by
``Federation.follow`` against a plain loop written here, and the planted
faults still failing it."""

import importlib.util
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import check  # noqa: E402
from reference.federation import Federation  # noqa: E402

N, ROWS, TEST_ROWS, BATCH, ROUNDS = 4, 9, 7, 4, 2
LR, MOMENTUM = 0.1, 0.9
SPEC = {
    "n_nodes": N, "batch_size": BATCH, "epochs": 1,
    "optimizer": {"name": "sgd", "lr": LR, "momentum": MOMENTUM},
    "param_dtype": "float32", "moment_dtype": "float32",
    "wire_dtype": "float32", "topology": "fully",
    "aggregator": {"name": "fedavg"}, "block_nodes": 2, "eval_batch": 3,
}


@pytest.fixture(scope="module")
def model():
    spec = importlib.util.spec_from_file_location(
        "toy_seq", HERE / "data" / "bench" / "reference" / "toy_seq.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def inputs(model):
    import jax

    rng = np.random.default_rng(7)

    def tokens(*lead):
        x = rng.integers(0, model.V, lead + (model.T,)).astype(np.int32)
        y = np.roll(x, -1, axis=-1)
        y[..., -1] = model.IGNORE  # the last position has no next token
        y[rng.random(y.shape) < 0.2] = model.IGNORE  # padding, here and there
        return x, y

    x, y = tokens(N, ROWS)
    x_test, y_test = tokens(TEST_ROWS)
    mask = np.ones((N, ROWS), bool)
    mask[1, -2:] = False  # a node with fewer rows than the others
    return dict(key=jax.random.PRNGKey(3),
                rngs=np.asarray(jax.random.split(jax.random.PRNGKey(4), N)),
                x=x, y=y, mask=mask, n_samples=mask.sum(axis=1),
                x_test=x_test, y_test=y_test,
                eval_nodes=np.array([0, 2]), rounds=ROUNDS)


def plain_loss(model, p, x, y, kept):
    """Written out position by position: a row's mean over its labelled
    positions, the batch's mean over its kept rows."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(model.forward(p, x), axis=-1)
    total = 0.0
    for b in range(x.shape[0]):
        at = [t for t in range(model.T) if y[b, t] != model.IGNORE]
        row = -sum(logp[b, t, y[b, t]] for t in at) / max(len(at), 1)
        total = total + row * float(kept[b])
    return total / max(float(np.sum(kept)), 1.0)


def test_follow_agrees_with_a_plain_loop(model, inputs):
    import jax
    import jax.numpy as jnp

    got = Federation(model, SPEC).follow(**inputs)

    p0 = model.init(inputs["key"])
    x, y, mask = inputs["x"], inputs["y"], inputs["mask"]
    P = [p0] * N
    M = [jax.tree.map(jnp.zeros_like, p0)] * N
    rngs = list(inputs["rngs"])
    loss = np.zeros((ROUNDS, N))
    steps = ROWS // BATCH
    with jax.default_matmul_precision("highest"):
        for r in range(ROUNDS):
            for i in range(N):
                rngs[i], k = jax.random.split(rngs[i])
                order = np.asarray(jax.random.permutation(k, ROWS))
                for s in range(steps):
                    rows = order[s * BATCH:(s + 1) * BATCH]
                    value, g = jax.value_and_grad(
                        lambda p: plain_loss(model, p, x[i][rows], y[i][rows],
                                             mask[i][rows]))(P[i])
                    M[i] = jax.tree.map(lambda g, m: g + MOMENTUM * m, g, M[i])
                    P[i] = jax.tree.map(lambda p, m: p - LR * m, P[i], M[i])
                    loss[r, i] += float(value) / steps
            if r == 0:
                moment = [[float(jnp.linalg.norm(M[i][k])) for i in range(N)]
                          for k in sorted(p0)]
            w = inputs["n_samples"] / inputs["n_samples"].sum()
            mean = jax.tree.map(lambda *v: sum(a * b for a, b in zip(w, v)), *P)
            P = [mean] * N
        change = [[float(jnp.linalg.norm(P[i][k] - p0[k])) for i in range(N)]
                  for k in sorted(p0)]
        every = np.ones(TEST_ROWS)
        test_loss = float(plain_loss(
            model, P[0], inputs["x_test"], inputs["y_test"], every))
        test_loss0 = float(plain_loss(
            model, p0, inputs["x_test"], inputs["y_test"], every))

    assert got["leaves"] == sorted(model.SHAPES)
    np.testing.assert_allclose(got["loss"], loss, rtol=2e-5)
    np.testing.assert_allclose(got["moment"], moment, rtol=2e-4)
    np.testing.assert_allclose(got["change"], change, rtol=2e-4)
    np.testing.assert_allclose(got["eval_loss"], [test_loss] * 2, rtol=2e-5)
    np.testing.assert_allclose(got["eval0_loss"], [test_loss0], rtol=2e-5)


@pytest.mark.parametrize("fault, fails", [
    ("half_batch", ("loss1_gap", "moment_gap", "change_gap")),
    ("state_unchanged", ("change_gap",)),
])
def test_planted_faults_fail_it(model, inputs, fault, fails):
    sound = Federation(model, SPEC).follow(**inputs)
    broken = Federation(model, SPEC, fault=fault).follow(**inputs)
    every = np.arange(len(inputs["eval_nodes"]))
    gaps = check.gaps(broken, sound, every)
    for name in fails:
        assert gaps[name] > 0.02, (name, gaps)
    if fault == "state_unchanged":
        assert gaps["change_gap"] == pytest.approx(1.0)
