"""LFM2-8B-A1B at toy widths on the CPU: the program's modules
(``models/lfm2.py`` over the parts it shares with ``models/ling.py`` and
``models/laguna.py``: the one causal convolution, tiled grouped-query
attention, the sorted grouped expert layer with every expert held and
none shared, the tied head) against the equations written out here and
against the plain reference (``benchmark/reference/lfm2_moe.py``), which
imports nothing of the program. float32 compute here, so that a wrong
term shows and rounding does not."""

import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2pfl_tpu.learning.lora import LoraModel, wrap_model
from p2pfl_tpu.models import get_model, laguna, lfm2, ling

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIG = json.loads(
    (ROOT / "benchmark" / "configs" / "lfm2-8b-a1b.json").read_text())
KWARGS = CONFIG["scenario"]["model"]["kwargs"]
CONV, FULL = "conv", "full_attention"
# the rehearsal's toy widths, four layers of them: a convolution over the
# dense FFN, then attention, conv, conv over experts
TOY = {**KWARGS, **CONFIG["rehearse"]["scenario"]["model"]["kwargs"],
       "layer_types": [CONV, FULL, CONV, CONV]}
LORA = {"rank": 4, "alpha": 8.0}
F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def ref_name(p):
    """The program's path of a leaf by the reference's naming rule
    (``params/layer_3/conv/conv_in/kernel/A`` -> ``L3.conv_in.A``), as the
    configuration file's ``param_map`` spells out for the cell's layers."""
    keys = [k for k in "/".join(
        str(getattr(k, "key", k)) for k in p).split("/")
        if k not in ("params", "kernel", "attn", "moe", "conv", "scale",
                     "embedding")]
    if keys[0].startswith("layer_"):
        keys[0] = "L" + keys[0][len("layer_"):]
        if keys[1] == "ffn":
            keys[1:3] = ["ffn_" + keys[2]]
    return ".".join(keys)


def benchmark_module(file):
    spec = importlib.util.spec_from_file_location(
        "bench_" + pathlib.Path(file).stem.replace(".", "_"),
        ROOT / "benchmark" / file)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return benchmark_module("reference/lfm2_moe.py")


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def toy_model(ref, **over):
    """The toy model, its adapter wrapper, and the same weights by the
    reference's names (adapters from the reference's own ``init``)."""
    sizes = {**TOY, **over}
    ref.configure(sizes, LORA)
    model = get_model("lfm2-8b-a1b", dtype=F32, **sizes)
    x = jnp.zeros((1, 24), jnp.int32)
    lm = wrap_model(model, "lfm2-8b-a1b", LORA["rank"],
                    alpha=LORA["alpha"], sample_x=x, seed=3)
    seeded = ref.init(jax.random.PRNGKey(5))
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        lm.init(jax.random.PRNGKey(0), x))
    adapters = jax.tree_util.tree_unflatten(
        treedef, [seeded[ref_name(p)] for p, _ in flat])
    frozen = {ref_name(p): v for p, v in
              jax.tree_util.tree_flatten_with_path(lm.base)[0]}
    assert {k: v.shape for k, v in frozen.items()} == ref.FROZEN_SHAPES
    return lm, adapters, seeded, frozen


def tokens(key, shape, vocab=TOY["vocab"]):
    return jax.random.randint(jax.random.PRNGKey(key), shape, 0, vocab)


def close(got, want, tol=1e-5):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * scale)


def test_param_map_of_the_cell_follows_the_naming_rule():
    rule = lambda path: ref_name(path.split("/"))
    for maps in (CONFIG["param_map"], CONFIG["frozen"]["param_map"]):
        assert all(rule(path) == name for path, name in maps.items())
    # and names every leaf of the model at the cell's layer list: no head
    model = get_model("lfm2-8b-a1b", **{
        **TOY, "layer_types": KWARGS["layer_types"]})
    base = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))
    assert {"/".join(str(k.key) for k in p) for p, _ in
            jax.tree_util.tree_flatten_with_path(base)[0]} == set(
        CONFIG["frozen"]["param_map"])
    assert "params/head" not in CONFIG["frozen"]["param_map"]


# --------------------------------------------------------------------------
# the short convolution


def shifted_products(x, w_in, taps, w_out):
    """Part 1 of the ISSUE written out: ``[B | C | X] = x W_in``, ``u = B
    * X``, ``c_t = w_0 u_(t-2) + w_1 u_(t-1) + w_2 u_t`` with zeros
    before position 0, ``out = (C * c) W_out``. ``x`` [T, d]."""
    b, c, xx = jnp.split(jnp.dot(x, w_in, precision=HI), 3, axis=-1)
    u = b * xx
    T = x.shape[0]
    back = lambda n: jnp.concatenate(
        [jnp.zeros((n, u.shape[1])), u])[:T]  # u_(t - n)
    conv = taps[0] * back(2) + taps[1] * back(1) + taps[2] * back(0)
    return jnp.dot(c * conv, w_out, precision=HI)


@pytest.mark.parametrize("nodes", [None, 2], ids=["alone", "vmap"])
@pytest.mark.parametrize("T", [1, 2, 3, 50, 300])
def test_short_conv_mixer_is_three_shifted_products(T, nodes):
    """Value and the gradients to the input, the taps and both
    projections, at a sequence shorter than the taps too; alone and under
    the ``vmap`` over nodes a round puts around it (the weights shared)."""
    d = 16
    mixer = lfm2.ShortConvMixer(3, dtype=F32)
    ks = jax.random.split(jax.random.PRNGKey(T), 3)
    x = jax.random.normal(ks[0], (nodes or 1, 1, T, d))
    weigh = jax.random.normal(ks[1], x.shape)
    params = mixer.init(ks[2], x[0])["params"]
    assert set(params) == {"conv_in", "conv_taps", "conv_out"}
    assert params["conv_taps"].shape == (3, d)

    def got(p, x):
        apply = lambda xb: mixer.apply({"params": p}, xb)
        y = jax.vmap(apply)(x) if nodes else apply(x[0])[None]
        return jnp.sum(weigh * y)

    def want(p, x):
        y = jax.vmap(jax.vmap(lambda row: shifted_products(
            row, p["conv_in"]["kernel"], p["conv_taps"],
            p["conv_out"]["kernel"])))(x)
        return jnp.sum(weigh * y)

    (l_got, d_got), (l_want, d_want) = (
        jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(params, x)
        for f in (got, want))
    np.testing.assert_allclose(l_got, l_want, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(d_got), jax.tree.leaves(d_want)):
        close(a, b)


def test_short_conv_output_does_not_see_later_positions():
    d, T, t = 16, 40, 17
    mixer = lfm2.ShortConvMixer(3, dtype=F32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, T, d))
    params = mixer.init(jax.random.PRNGKey(1), x)
    later = x.at[:, t + 1:].set(jax.random.normal(
        jax.random.PRNGKey(2), (2, T - t - 1, d)))
    a, b = mixer.apply(params, x), mixer.apply(params, later)
    np.testing.assert_array_equal(a[:, :t + 1], b[:, :t + 1])
    assert float(jnp.max(jnp.abs(a[:, t + 1:] - b[:, t + 1:]))) > 0.1


@pytest.mark.parametrize("T, k", [(1, 4), (3, 4), (50, 4), (50, 3)])
def test_causal_conv_silu_is_what_it_was(T, k):
    """KDA's convolution with its SiLU, as ``models/ling.py`` had it
    before the convolution stood alone, to the bit."""
    x = jax.random.normal(jax.random.PRNGKey(T), (2, T, 8))
    taps = jax.random.normal(jax.random.PRNGKey(k), (k, 8))
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    before = jax.nn.silu(sum(xp[:, i:i + T] * taps[i] for i in range(k)))
    np.testing.assert_array_equal(ling.causal_conv_silu(x, taps), before)
    np.testing.assert_array_equal(
        jax.nn.silu(ling.causal_conv(x, taps)), before)


# --------------------------------------------------------------------------
# attention: the head norms and the rotary embedding, a group of 4


def test_head_norms_and_rotary_against_the_equations(ref):
    """``q <- RMSNorm_D(q)`` and ``k <- RMSNorm_D(k)`` a head, each with
    its own scale, then half-split rotary pairs ``(x_i, x_(i + D/2))``
    at ``theta^(-2i/D)``; query head ``h`` reads key head ``h // 4``;
    scores over ``sqrt(D)``, causal softmax."""
    H, G, D, T, d = 8, 2, 8, 37, TOY["hidden"]
    assert (TOY["heads"], TOY["kv_heads"], TOY["head_dim"]) == (H, G, D)
    inv = laguna.rope_inv_freq(D, TOY["theta"])
    np.testing.assert_allclose(
        inv, [TOY["theta"] ** (-2 * i / D) for i in range(D // 2)], rtol=1e-12)
    mod = lfm2.Lfm2Attention(H, G, D, tuple(inv), TOY["eps"], dtype=F32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, T, d))
    p = dict(mod.init(jax.random.PRNGKey(1), x)["params"])
    assert p["q_norm"].shape == p["k_norm"].shape == (D,)
    p["q_norm"] = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2), (D,))
    p["k_norm"] = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(3), (D,))
    got = mod.apply({"params": p}, x)

    def normed(a, s):
        return s * a / jnp.sqrt(jnp.mean(a * a, -1, keepdims=True) + 1e-5)

    def turned(a):
        ang = np.arange(T)[:, None] * inv[None, :]
        cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
        a1, a2 = a[..., :D // 2], a[..., D // 2:]
        return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin], -1)

    q = turned(normed((x @ p["attn_q"]["kernel"]).reshape(2, T, H, D),
                      p["q_norm"]))
    k = turned(normed((x @ p["attn_k"]["kernel"]).reshape(2, T, G, D),
                      p["k_norm"]))
    v = (x @ p["attn_v"]["kernel"]).reshape(2, T, G, D)
    out = []
    for h in range(H):
        s = jnp.einsum("btd,bsd->bts", q[:, :, h], k[:, :, h // 4]) / D ** 0.5
        s = jnp.where(np.tril(np.ones((T, T), bool)), s, -jnp.inf)
        out.append(jnp.einsum("bts,bsd->btd", jax.nn.softmax(s, -1),
                              v[:, :, h // 4]))
    want = jnp.concatenate(out, -1) @ p["attn_o"]["kernel"]
    close(got, want, 2e-5)
    assert ling.score_tiles("gqa.attn")["computed"] == 1  # one tile at T 37


# --------------------------------------------------------------------------
# the expert layer: one group, every expert held, none shared


def test_route_with_one_group_is_the_plain_biased_sigmoid_top_k():
    n, d, E, k = 50, 16, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (n, d))
    frozen = {"router": jax.random.normal(ks[1], (d, E)),
              "bias": 0.5 * jax.random.normal(ks[2], (E,))}
    idx, w = ling.route(x, frozen, n_group=1, topk_group=1, top_k=k, scale=1.0)
    s = 1.0 / (1.0 + np.exp(-np.asarray(jnp.dot(x, frozen["router"],
                                                 precision=HI), np.float64)))
    chosen = np.argsort(-(s + np.asarray(frozen["bias"])), axis=1)[:, :k]
    assert (np.sort(np.asarray(idx), 1) == np.sort(chosen, 1)).all()
    # the bias moves the choice and not the weights
    assert (np.sort(np.argsort(-s, axis=1)[:, :k], 1)
            != np.sort(chosen, 1)).any()
    picked = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(w, picked / picked.sum(1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(1), 1.0, rtol=1e-5)


def expert_layer(sizes, offset=0, held=None):
    return ling.ExpertFFN(
        sizes["n_experts"], held or sizes["experts_held"], offset,
        sizes["expert_width"], 0, sizes["top_k"], 1, 1, sizes["route_scale"],
        dtype=F32)


def reference_layer(ref, sizes, params, x):
    ref.configure(sizes, LORA)
    frozen = {"L." + k: v for k, v in params.items()}
    return ref.expert_ffn(frozen, "L.", x, lambda a: a)


def test_no_shared_expert_and_every_expert_held_is_the_whole_layer(ref):
    """``shared_width`` 0 makes no shared leaf; with all experts held the
    layer is the reference's loop over all of them, and the parts that
    four shares of the experts give add up to it."""
    layer = expert_layer(TOY)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, TOY["hidden"]))
    params = layer.init(jax.random.PRNGKey(2), x)["params"]
    assert set(params) == {"router", "router_bias", "experts_gate_up",
                           "experts_down"}
    got, stats = layer.apply({"params": params}, x)
    want = reference_layer(ref, TOY, params, x)
    close(got, want, 2e-5)
    assert float(stats[0]) == 0.0
    # by hand: every expert on every row, weighed by the router's choice
    rows = x.reshape(-1, TOY["hidden"])
    idx, w = ling.route(rows, {"router": params["router"],
                               "bias": params["router_bias"]},
                        n_group=1, topk_group=1, top_k=TOY["top_k"], scale=1.0)
    dense = jnp.zeros((rows.shape[0], TOY["n_experts"])).at[
        jnp.arange(rows.shape[0])[:, None], idx].set(w)
    loop = sum(dense[:, e:e + 1] * (ling.swiglu(
        rows @ params["experts_gate_up"][e]) @ params["experts_down"][e])
        for e in range(TOY["n_experts"]))
    close(got.reshape(loop.shape), loop, 2e-5)
    E = TOY["n_experts"]
    parts = []
    for c in range(4):
        cut = slice(c * E // 4, (c + 1) * E // 4)
        mine = {**params, "experts_gate_up": params["experts_gate_up"][cut],
                "experts_down": params["experts_down"][cut]}
        parts.append(expert_layer(TOY, c * E // 4, E // 4).apply(
            {"params": mine}, x)[0])
    close(sum(parts), want, 2e-5)


def test_no_pair_is_dropped_under_a_skewed_bias(ref, monkeypatch):
    """32 held of 32, 4 chosen: a selection bias skewed so that every
    token chooses expert 0, which takes 8 times its even share (all it can
    at 4 of 32), and with 2 of 32 chosen 16 times; blocks made small
    enough that the pairs need several: nothing left out, and the way
    back, a block at a time, is the reference's gradient."""
    for top_k, times in ((4, 8), (2, 16)):
        sizes = {**TOY, "n_experts": 32, "experts_held": 32, "top_k": top_k}
        layer = expert_layer(sizes)
        x = jax.random.normal(jax.random.PRNGKey(1), (3, 50, TOY["hidden"]))
        params = dict(layer.init(jax.random.PRNGKey(2), x)["params"])
        params["router_bias"] = params["router_bias"].at[0].set(10.0)
        monkeypatch.setattr(ling, "BLOCK_ROWS", 128)
        y, stats = layer.apply({"params": params}, x)
        want = reference_layer(ref, sizes, params, x)
        assert np.isfinite(np.asarray(want)).all()
        close(y, want, 2e-5)
        assert float(stats[0]) == 0.0  # dropped pairs
        # expert 0 holds all 150 tokens: ``times`` its even share
        assert float(stats[1]) == pytest.approx(times, rel=1e-6)
        assert 150 * top_k > 2 * 128  # more pairs than two blocks hold
        weigh = jax.random.normal(jax.random.PRNGKey(3), y.shape)
        got = jax.grad(lambda x_: jnp.sum(
            layer.apply({"params": params}, x_)[0] * weigh))(x)
        back = jax.grad(lambda x_: jnp.sum(
            reference_layer(ref, sizes, params, x_) * weigh))(x)
        assert float(jnp.linalg.norm(got - back)) <= 2e-3 * float(
            jnp.linalg.norm(back))


def test_the_cells_pairs_are_two_full_blocks():
    """32,768 tokens a step, 4 of 32 chosen, all held: 131,072 pairs, two
    blocks of ``BLOCK_ROWS``; Ling's and Laguna's cells keep theirs."""
    shape = lambda top_k, experts, held: jax.eval_shape(
        lambda i: ling._dispatch(i, experts, held, 0)[0],
        jax.ShapeDtypeStruct((32768, top_k), jnp.int32)).shape[0]
    assert shape(4, 32, 32) == 2 * ling.BLOCK_ROWS == 131072
    assert shape(8, 512, 64) == 4 * 65536 and shape(10, 256, 64) == 5 * 65536


# --------------------------------------------------------------------------
# the tied head


def test_tied_logits_are_h_times_the_embedding_transposed(ref):
    lm, adapters, _, frozen = toy_model(ref)
    base = lm.base["params"]
    assert "head" not in base and "head" not in frozen
    x = tokens(1, (2, 24))
    h = lm.inner.apply(lm.base, x, method="hidden_states")[0]
    E = base["embed"]["embedding"]
    close(lm.inner.apply(lm.base, x), jnp.dot(h, E.T, precision=HI), 1e-5)
    # an untied model of the same sizes still makes its own head
    untied = get_model("lfm2-8b-a1b", dtype=F32, **{**TOY, "tie_head": False})
    shapes = jax.eval_shape(untied.init, jax.random.PRNGKey(0), x)["params"]
    assert shapes["head"].shape == (TOY["hidden"], TOY["vocab"])
    # Ling and Laguna keep theirs
    for name in ("ling-3.0-flash", "laguna-s-2.1"):
        shapes = jax.eval_shape(get_model(name).init, jax.random.PRNGKey(0),
                                x)["params"]
        assert "head" in shapes


# --------------------------------------------------------------------------
# each kind of layer, and the whole model, against the reference


@pytest.mark.parametrize("kind", ["conv", "attention", "dense", "experts"])
def test_each_layer_against_the_reference(ref, kind):
    lm, _, _, frozen = toy_model(ref)
    base = lm.base["params"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, TOY["hidden"]))
    dense = ref.make_dense({}, frozen, lambda a: a)
    same = lambda a: a
    if kind == "conv":
        got = lfm2.ShortConvMixer(TOY["taps"], dtype=F32).apply(
            {"params": base["layer_2"]["conv"]}, x)
        want = ref.short_conv(dense, "L2.", frozen["L2.conv_taps"], x)
    elif kind == "attention":
        mod = lfm2.Lfm2Attention(
            TOY["heads"], TOY["kv_heads"], TOY["head_dim"],
            tuple(laguna.rope_inv_freq(TOY["head_dim"], TOY["theta"])),
            TOY["eps"], dtype=F32)
        got = mod.apply({"params": base["layer_1"]["attn"]}, x)
        want = ref.attention(dense, "L1.", frozen, x, same)
    elif kind == "dense":
        got = ling.DenseFFN(TOY["dense_width"], dtype=F32).apply(
            {"params": base["layer_0"]["ffn"]}, x)
        want = dense("L0.ffn_down", ref.swiglu(dense("L0.ffn_gate_up", x)))
    else:
        got, stats = expert_layer(TOY).apply(
            {"params": base["layer_1"]["moe"]}, x)
        want = ref.expert_ffn(frozen, "L1.", x, same)
        assert float(stats[0]) == 0.0
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_whole_model_loss_and_adapter_gradients(ref):
    lm, adapters, seeded, frozen = toy_model(ref)
    x, y = tokens(1, (2, 40)), tokens(2, (2, 40))
    mask = jnp.array([True, True])
    loss = lambda a: lm.apply(a, x, y, mask, method="loss")[0]
    plain = lambda p: ref.loss(ref.forward(p, x, frozen=frozen), y, mask)
    (l_got, got), (l_want, want) = (
        jax.jit(jax.value_and_grad(loss))(adapters),
        jax.jit(jax.value_and_grad(plain))(seeded))
    np.testing.assert_allclose(l_got, l_want, rtol=1e-5)
    assert len(want) == 2 * (3 * 2 + 4)  # three conv layers, one attention
    for p, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        w = want[ref_name(p)]
        assert float(jnp.linalg.norm(g - w)) <= 2e-3 * float(
            jnp.linalg.norm(w)), ref_name(p)
    # the logits too, and a masked row counts for nothing
    np.testing.assert_allclose(lm.apply(adapters, x),
                               ref.forward(seeded, x, frozen=frozen),
                               rtol=2e-4, atol=2e-4)
    rows = jax.jit(lambda m: lm.apply(adapters, x, y, m, method="loss")[0])
    both, first, second = (rows(jnp.array(m)) for m in (
        [True, True], [True, False], [False, True]))
    np.testing.assert_allclose(both, (first + second) / 2, rtol=1e-5)


# --------------------------------------------------------------------------
# adapters, the base and the normal path


def test_adapters_ride_on_the_mixers_projections_only():
    model = get_model("lfm2-8b-a1b", dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16, **TOY)
    lm = wrap_model(model, "lfm2-8b-a1b", 4,
                    sample_x=jnp.zeros((1, 24), jnp.int32))
    assert isinstance(lm, LoraModel)
    assert {l.dtype for l in jax.tree.leaves(lm.base)} == {jnp.dtype("bfloat16")}
    sites = {s.key: (s.d_in, s.d_out) for s in lm.sites}
    d, H, G, D = (TOY[k] for k in ("hidden", "heads", "kv_heads", "head_dim"))
    assert len(sites) == 3 * 2 + 4  # no expert, no dense FFN, no embedding
    for i, kind in enumerate(TOY["layer_types"]):
        at = f"params/layer_{i}/"
        if kind == CONV:
            assert sites[at + "conv/conv_in/kernel"] == (d, 3 * d)
            assert sites[at + "conv/conv_out/kernel"] == (d, d)
        else:
            assert sites[at + "attn/attn_q/kernel"] == (d, H * D)
            assert sites[at + "attn/attn_o/kernel"] == (H * D, d)
            assert sites[at + "attn/attn_k/kernel"] == sites[
                at + "attn/attn_v/kernel"] == (d, G * D)


def test_the_cells_adapters_and_base_are_the_issues_count():
    """Rank 16 on the seven convolution layers' two projections and the
    two attention layers' four: 1,802,240 a node; the base 3,136M."""
    z, r = KWARGS, 16
    d, H, G, D = z["hidden"], z["heads"], z["kv_heads"], z["head_dim"]
    conv = r * ((d + 3 * d) + (d + d))
    attn = r * (2 * (d + H * D) + 2 * (d + G * D))
    kinds = z["layer_types"]
    assert (conv, attn, kinds.count(CONV), kinds.count(FULL)) == (
        196_608, 212_992, 7, 2)
    assert 7 * conv + 2 * attn == 1_802_240
    model = get_model("lfm2-8b-a1b", **z)
    base = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(base))
    assert round(total / 1e6) == 3136


def test_layer_types_are_checked():
    with pytest.raises(ValueError, match="layer_types holds"):
        get_model("lfm2-8b-a1b", layer_types=["conv", "sliding_attention"]
                  ).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def scenario_dict(**over):
    return {
        "name": "lfm2-toy", "seed": 3, "n_nodes": 4, "federation": "DFL",
        "topology": "fully", "aggregator": "fedavg",
        "protocol": {"train_set_size": 0}, "wire_dtype": "bf16",
        "data": {"dataset": "tokens-96-40", "batch_size": 1,
                 "val_percent": 0.0, "synthetic_train": 8,
                 "synthetic_test": 3, "seed": 3},
        "model": {"model": "lfm2-8b-a1b", "objective": "next_token",
                  "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
                  "kwargs": {**TOY, "layer_types": [CONV, FULL, CONV]}},
        "lora": {"rank": 4, "alpha": 8.0},
        "training": {"rounds": 2, "epochs_per_round": 1, "optimizer": "adam",
                     "learning_rate": 0.01, "eval_every": 0}, **over}


def test_scenario_runs_the_language_model_path():
    from p2pfl_tpu.config.schema import ScenarioConfig
    from p2pfl_tpu.federation import Scenario
    from p2pfl_tpu.obs import trace as obs_trace

    sc = Scenario(ScenarioConfig.from_dict(scenario_dict()))
    assert obs_trace.stage_seconds()["scenario.init.base"] > 0
    assert "head" not in sc.model.base["params"]
    res = sc.run(rounds=2)
    losses = np.array([r["Train/loss"] for r in res.history
                       if "Train/loss" in r]).reshape(2, 4)
    # every node's training loss falls from the first round to the second
    assert np.isfinite(losses).all() and (losses[1] < losses[0]).all()
    assert np.isfinite(sc.evaluate()["per_node_loss"]).all()
    counted = obs_trace.counted()
    assert not counted["moe.dropped_pairs"]["sum"].any()
    assert (counted["moe.load_max_over_mean"]["max"] >= 1.0).all()
    assert ling.score_tiles("gqa.attn")
    n = sc.config.n_nodes
    assert all(l.shape[0] == n for l in jax.tree.leaves(sc.fed.states.params))
    # conv_in, conv_out of two layers and q, k, v, o of one, A and B each
    assert len(jax.tree.leaves(sc.fed.states.params)) == (2 * 2 + 4) * 2
    sc.close()
