"""PatchConv (models/cnn.py): the im2col lowering of small-contraction
convs must be a drop-in for nn.Conv — same parameter tree, same math.
Round-4 perf work: the vmapped federation's per-node conv1 lowered to
a degenerate grouped conv at <2% MXU; PatchConv is the fix and this
pins its equivalence (incl. the patches channel order, which is
(cin, kh, kw)-major and MUST match the transposed HWIO kernel)."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from p2pfl_tpu.models import get_model
from p2pfl_tpu.models.cnn import PATCH_CONV_MAX_CONTRACTION, PatchConv


@pytest.mark.parametrize("cin,k,feat", [(1, 5, 32), (3, 3, 8), (1, 3, 16)])
def test_patchconv_matches_nnconv(cin, k, feat):
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (4, 12, 12, cin), jnp.float32)
    ref = nn.Conv(feat, (k, k), padding="SAME", dtype=jnp.float32,
                  param_dtype=jnp.float32)
    alt = PatchConv(feat, (k, k), dtype=jnp.float32,
                    param_dtype=jnp.float32)
    params = ref.init(rng, x)
    # identical param tree -> checkpoints/aggregators can't tell
    assert (jax.tree.structure(params)
            == jax.tree.structure(alt.init(rng, x)))
    out_ref = ref.apply(params, x)
    out_alt = alt.apply(params, x)
    assert jnp.max(jnp.abs(out_ref - out_alt)) < 1e-5


def test_femnist_cnn_param_tree_unchanged_by_patchconv():
    """conv1 (contraction 25) runs as the lane-dense BandedConvPool
    (PatchConv before PR 33) but keeps the Conv_0 key (explicit name=)
    and nn.Conv's leaves, so checkpoints from either earlier form still
    load, and aggregators and the benchmark's param_map see no
    difference; conv2 (contraction 800) keeps the conv lowering."""
    model = get_model("femnist-cnn")
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 28, 28, 1)))
    shapes = jax.tree.map(lambda a: a.shape, params["params"])
    assert shapes == {
        "Conv_0": {"kernel": (5, 5, 1, 32), "bias": (32,)},
        "Conv_1": {"kernel": (5, 5, 32, 64), "bias": (64,)},
        "Dense_0": {"kernel": (3136, 2048), "bias": (2048,)},
        "Dense_1": {"kernel": (2048, 62), "bias": (62,)},
    }
    assert 1 * 25 <= PATCH_CONV_MAX_CONTRACTION < 32 * 25


def test_patchconv_gradients_match():
    rng = jax.random.PRNGKey(1)
    x = jax.random.normal(rng, (2, 8, 8, 1), jnp.float32)
    ref = nn.Conv(4, (5, 5), padding="SAME", dtype=jnp.float32,
                  param_dtype=jnp.float32)
    alt = PatchConv(4, (5, 5), dtype=jnp.float32, param_dtype=jnp.float32)
    params = ref.init(rng, x)

    def loss(mod, p):
        return jnp.sum(mod.apply(p, x) ** 2)

    g_ref = jax.grad(lambda p: loss(ref, p))(params)
    g_alt = jax.grad(lambda p: loss(alt, p))(params)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_alt)):
        assert jnp.max(jnp.abs(a - b)) < 1e-4


@pytest.mark.slowtier
def test_pre_patchconv_checkpoint_restores_into_patchconv_model(
        tmp_path, monkeypatch):
    """VERDICT r4 #8: the checkpoint-compat claim, proven with a real
    checkpoint. A federation built from the PRE-PatchConv module (both
    convs as nn.Conv — recreated by disabling the patch gate) is
    trained a step, checkpointed through federation/checkpoint.py, and
    restored into the CURRENT PatchConv model. The restored federation
    must evaluate identically — not just share a param tree.

    slowtier (~8s of compiles, the file's other three tests are <1s
    combined): every invariant it composes has a fast in-suite pin —
    the identical param tree (test_femnist_cnn_param_tree_unchanged_
    by_patchconv), forward/grad equivalence (test_patchconv_matches_
    nnconv, test_patchconv_gradients_match), and checkpoint round-
    tripping itself (test_checkpoint.py). This end-to-end composition
    re-proof runs on the P2PFL_SLOW_TESTS=1 tier."""
    import numpy as np

    from p2pfl_tpu.federation.checkpoint import (
        latest_checkpoint,
        load_checkpoint,
        save_checkpoint,
    )
    from p2pfl_tpu.learning.learner import make_step_fns
    from p2pfl_tpu.models import cnn as cnn_mod
    from p2pfl_tpu.parallel.federated import build_eval_fn, init_federation

    n, s = 2, 16
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, s, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 62, size=(n, s)).astype(np.int32)
    mask = np.ones((n, s), bool)

    # the pre-PatchConv module: gate disabled -> conv1 is nn.Conv
    monkeypatch.setattr(cnn_mod, "PATCH_CONV_MAX_CONTRACTION", 0)
    old_fns = make_step_fns(get_model("femnist-cnn"), batch_size=8)
    fed = init_federation(old_fns, jnp.asarray(x[0, :1]), n,
                          same_init=False)
    states, _ = jax.vmap(old_fns.train_epochs,
                         in_axes=(0, 0, 0, 0, None))(
        fed.states, jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask), 1)
    fed = fed.replace(states=states, round=fed.round + 1)
    save_checkpoint(tmp_path, fed)
    old_eval = build_eval_fn(old_fns)(fed, jnp.asarray(x[0]),
                                      jnp.asarray(y[0]))

    # restore into the CURRENT (PatchConv) model
    monkeypatch.setattr(cnn_mod, "PATCH_CONV_MAX_CONTRACTION", 64)
    new_fns = make_step_fns(get_model("femnist-cnn"), batch_size=8)
    template = init_federation(new_fns, jnp.asarray(x[0, :1]), n,
                               same_init=False)
    restored = load_checkpoint(latest_checkpoint(tmp_path), template)
    for a, b in zip(jax.tree.leaves(fed.states.params),
                    jax.tree.leaves(restored.states.params)):
        assert jnp.array_equal(a, b)
    new_eval = build_eval_fn(new_fns)(restored, jnp.asarray(x[0]),
                                      jnp.asarray(y[0]))
    np.testing.assert_allclose(np.asarray(old_eval["accuracy"]),
                               np.asarray(new_eval["accuracy"]))
    np.testing.assert_allclose(np.asarray(old_eval["loss"]),
                               np.asarray(new_eval["loss"]), rtol=2e-2)
