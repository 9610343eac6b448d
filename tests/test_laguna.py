"""Laguna-S-2.1 at toy widths on the CPU: the program's modules
(``models/laguna.py`` over the parts it shares with ``models/ling.py``:
tiled attention with a window and grouped query heads, the sorted
grouped expert layer with this model's softmax router, adapters as side
paths of a frozen base) against the plain reference
(``benchmark/reference/laguna_s.py``: a block of queries against its
keys with the mask written out, a loop over experts), which imports
nothing of the program. float32 compute here, so that a wrong term
shows and rounding does not."""

import functools
import importlib.util
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2pfl_tpu.learning.lora import LoraModel, wrap_model
from p2pfl_tpu.models import get_model, laguna, ling

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIG = json.loads(
    (ROOT / "benchmark" / "configs" / "laguna-s-2.1.json").read_text())
KWARGS = CONFIG["scenario"]["model"]["kwargs"]
# the rehearsal's toy widths, four layers of them: full attention over a
# dense FFN, then window, window, full over experts
TOY = {**KWARGS, **CONFIG["rehearse"]["scenario"]["model"]["kwargs"],
       "layer_types": KWARGS["layer_types"][:1] + KWARGS["layer_types"][2:],
       "mlp_layer_types": KWARGS["mlp_layer_types"][:4],
       "heads": [4, 6, 6, 4]}
LORA = {"rank": 4, "alpha": 8.0}
F32 = jnp.float32
FULL, WINDOW = "full_attention", "sliding_attention"


def ref_name(p):
    """The program's path of a leaf by the reference's naming rule
    (``params/layer_3/attn/attn_q/kernel/A`` -> ``L3.attn_q.A``), as the
    configuration file's ``param_map`` spells out for the cell's layers."""
    keys = [k for k in "/".join(
        str(getattr(k, "key", k)) for k in p).split("/")
        if k not in ("params", "kernel", "attn", "moe", "scale", "embedding")]
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
    return benchmark_module("reference/laguna_s.py")


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def toy_model(ref, **over):
    """The toy model, its adapter wrapper, and the same weights by the
    reference's names (adapters from the reference's own ``init``)."""
    sizes = {**TOY, **over}
    ref.configure(sizes, LORA)
    model = get_model("laguna-s-2.1", dtype=F32, **sizes)
    x = jnp.zeros((1, 24), jnp.int32)
    lm = wrap_model(model, "laguna-s-2.1", LORA["rank"],
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


def test_param_map_of_the_cell_follows_the_naming_rule():
    rule = lambda path: ref_name(path.split("/"))
    for maps in (CONFIG["param_map"], CONFIG["frozen"]["param_map"]):
        assert all(rule(path) == name for path, name in maps.items())
    # and names every leaf of the model at the cell's layer lists
    model = get_model("laguna-s-2.1", **{**TOY, **{
        k: KWARGS[k] for k in ("layer_types", "mlp_layer_types")},
        "heads": [4, 6, 6, 6, 4]})
    base = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))
    assert {"/".join(str(k.key) for k in p) for p, _ in
            jax.tree_util.tree_flatten_with_path(base)[0]} == set(
        CONFIG["frozen"]["param_map"])


# --------------------------------------------------------------------------
# attention's tiles: a window, query heads grouped over key heads


def dense_attention(q, k, v, scale, window=None):
    """Masked softmax attention over the whole ``[T, T]`` score square,
    the keys and values of a head repeated for its group."""
    T, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = j <= i if window is None else (j <= i) & (j > i - window)
    return jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1),
                      v, precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("nodes", [None, 2], ids=["alone", "vmap"])
@pytest.mark.parametrize("group", [1, 6, 9])
@pytest.mark.parametrize("window", [None, 64, 300, 2048])
@pytest.mark.parametrize("T", [100, 512, 768, 1000, 1300])
def test_causal_attention_is_the_dense_masked_softmax(T, window, group, nodes):
    """Value and the gradients to q, k and v, float32, with a block of
    256: under one block, two, three, a padded length, five with a pad;
    no window, one inside a tile, one that spans two, one wider than the
    sequence; one, six and nine query heads a key head; alone and under
    the ``vmap`` over nodes a round puts around it."""
    shape = lambda *tail: (nodes, 1, T) + tail if nodes else (1, T) + tail
    ks = jax.random.split(jax.random.PRNGKey(T + group), 4)
    q = jax.random.normal(ks[0], shape(group, 24))
    k = jax.random.normal(ks[1], shape(1, 24))
    v = jax.random.normal(ks[2], shape(1, 16))
    weigh = jax.random.normal(ks[3], shape(group, 16))

    def both(attention):
        f = jax.value_and_grad(
            lambda q, k, v, weigh: jnp.sum(weigh * attention(
                q, k, v, 24 ** -0.5, window=window)), argnums=(0, 1, 2))
        return jax.jit(jax.vmap(f) if nodes else f)(q, k, v, weigh)

    (got, d_got), (want, d_want) = both(ling.causal_attention), both(
        dense_attention)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-4)
    for a, b in zip(d_got, d_want):
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("T, window, scope, computed, square", [
    (8192, 512, "swa.attn", 93, 1024), (8192, None, "gqa.attn", 528, 1024),
    (4096, None, "mla.attn", 136, 256), (768, 300, "swa.attn", 6, 9),
    (768, 64, "swa.attn", 5, 9)])
def test_the_record_of_the_score_tiles_by_scope(T, window, scope, computed,
                                                square):
    """``score_tiles(scope)`` is what the last trace of
    ``causal_attention`` under that scope forms: 93 of 1024 at the cell's
    8192 positions under a window of 512 with tiles of 256 x 256, the
    causal 528 without one, still 136 of 256 at Ling's 4096; each scope
    keeps its own record, and ``score_tiles()`` answers for
    ``mla.attn``."""
    one = lambda heads, width: jax.ShapeDtypeStruct((1, T, heads, width),
                                                    jnp.bfloat16)
    before = {s: ling.score_tiles(s)
              for s in ("swa.attn", "gqa.attn", "mla.attn") if s != scope}
    jax.eval_shape(
        lambda q, k, v: ling.causal_attention(q, k, v, 1.0, window=window,
                                              scope=scope),
        one(6, 24), one(2, 24), one(2, 16))
    assert ling.score_tiles(scope) == {"computed": computed, "square": square,
                                       "block": 256, "tile": 256}
    assert {s: ling.score_tiles(s) for s in before} == before
    assert ling.score_tiles() == ling.score_tiles("mla.attn")
    assert ling.score_tiles("no.such") == {}


def test_score_tiles_share_reader_reads_the_window_layers_record():
    reader = benchmark_module("readers/swa.score_tiles_share.py")
    one = lambda heads: jax.ShapeDtypeStruct((1, 8192, heads, 8), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: ling.causal_attention(
        q, k, v, 1.0, window=512, scope="swa.attn"), one(9), one(1), one(1))
    assert reader.read({}) == pytest.approx(100 * 93 / 1024)


# --------------------------------------------------------------------------
# the two rotary embeddings


def test_yarn_frequencies_are_the_formula_written_out():
    R, theta, factor, original = 64, 500000.0, 128.0, 8192
    dim = lambda n: R * math.log(original / (2 * math.pi * n)) / (
        2 * math.log(theta))
    low, high = max(math.floor(dim(32)), 0), min(math.ceil(dim(1)), R - 1)
    assert (low, high) == (9, 18)
    want = []
    for i in range(R // 2):
        extra = theta ** (-2 * i / R)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(extra / factor * ramp + extra * (1 - ramp))
    got = laguna.yarn_inv_freq(R, theta, factor, original, 32, 1)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert got[0] == 1.0 and got[-1] == pytest.approx(
        theta ** (-62 / 64) / 128)
    np.testing.assert_allclose(laguna.rope_inv_freq(128, 10000.0),
                               [10000.0 ** (-2 * i / 128) for i in range(64)],
                               rtol=1e-12)


def test_partial_rotary_turns_half_split_pairs_and_leaves_the_rest(ref):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 50, 3, 16))
    inv = laguna.yarn_inv_freq(8, 500000.0, 128.0, 8192, 32, 1)
    got = laguna.rope_half(x, inv, 1.4852030263919618)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])  # untouched
    pos = np.arange(50)[:, None] * inv[None, :]
    cos, sin = (1.4852030263919618 * f(pos)[None, :, None, :]
                for f in (np.cos, np.sin))
    np.testing.assert_allclose(got[..., :4], x[..., :4] * cos - x[..., 4:8] * sin,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[..., 4:8], x[..., 4:8] * cos + x[..., :4] * sin,
                               rtol=1e-5, atol=1e-5)
    # the reference's own, for both kinds of layer
    ref.configure(TOY, LORA)
    for kind, share in ((FULL, "rotary_full"), (WINDOW, "rotary_window")):
        freq, factor = ref.inv_freq(kind)
        assert len(freq) == int(TOY["head_dim"] * TOY[share]) // 2
        np.testing.assert_allclose(ref.rotary(x, kind),
                                   laguna.rope_half(x, freq, factor),
                                   rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# each kind of layer, module against reference function


@pytest.mark.parametrize("kind", ["window", "full", "dense", "experts"])
def test_each_layer_against_the_reference(ref, kind):
    lm, _, _, frozen = toy_model(ref)
    base = lm.base["params"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, TOY["hidden"]))
    dense = ref.make_dense({}, frozen, lambda a: a)
    same = lambda a: a
    if kind in ("window", "full"):
        i, layer_type = (1, WINDOW) if kind == "window" else (3, FULL)
        assert TOY["layer_types"][i] == layer_type and TOY["window"] < 40
        rope = {FULL: (laguna.yarn_inv_freq(8, 500000.0, 128.0, 8192, 32.0, 1.0),
                       TOY["yarn_attention_factor"]),
                WINDOW: (laguna.rope_inv_freq(16, 10000.0), 1.0)}[layer_type]
        mod = laguna.LagunaAttention(
            TOY["heads"][i], TOY["kv_heads"], TOY["head_dim"],
            TOY["window"] if kind == "window" else None, tuple(rope[0]),
            rope[1], dtype=F32)
        got = mod.apply({"params": base[f"layer_{i}"]["attn"]}, x)
        want = ref.attention(dense, f"L{i}.", layer_type, TOY["heads"][i], x,
                             same)
    elif kind == "dense":
        got = ling.DenseFFN(TOY["dense_width"], dtype=F32).apply(
            {"params": base["layer_0"]["ffn"]}, x)
        want = dense("L0.ffn_down", ref.swiglu(dense("L0.ffn_gate_up", x)))
    else:
        got, stats = expert_layer(TOY, 0, TOY["experts_held"]).apply(
            {"params": base["layer_1"]["moe"]}, x)
        want = ref.expert_ffn(dense, frozen, "L1.", x, same)
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
    assert len(want) == 2 * 4 * 4  # q, k, v, o of four layers
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
# the chip's share of the expert layer


def expert_layer(sizes, offset, held):
    return ling.ExpertFFN(
        sizes["n_experts"], held, offset, sizes["expert_width"],
        sizes["shared_width"], sizes["top_k"],
        router=functools.partial(laguna.route_softmax, top_k=sizes["top_k"],
                                 scale=sizes["route_scale"]), dtype=F32)


def reference_layer(ref, sizes, params, x):
    ref.configure(sizes, LORA)
    frozen = {"L." + k: (v["kernel"] if isinstance(v, dict) else v)
              for k, v in params.items()}
    return ref.expert_ffn(ref.make_dense({}, frozen, lambda a: a), frozen,
                          "L.", x, lambda a: a)


def test_the_shares_add_up(ref):
    """The four chips' partial outputs, the shared expert counted once,
    sum to the uncut layer's output: the program's layer told which
    experts it holds against the reference holding all of them."""
    E = TOY["n_experts"]
    held = E // 4
    whole = expert_layer(TOY, 0, E)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, TOY["hidden"]))
    params = whole.init(jax.random.PRNGKey(2), x)["params"]
    assert "router_bias" not in params  # this router has none
    uncut = reference_layer(ref, {**TOY, "experts_held": E}, params, x)
    shared = params["shared_down"]["kernel"]
    only_shared = ling.swiglu(x @ params["shared_gate_up"]["kernel"]) @ shared
    parts = []
    for c in range(4):
        mine = dict(params)
        mine["experts_gate_up"] = params["experts_gate_up"][c * held:(c + 1) * held]
        mine["experts_down"] = params["experts_down"][c * held:(c + 1) * held]
        y, stats = expert_layer(TOY, c * held, held).apply({"params": mine}, x)
        assert float(stats[0]) == 0.0
        parts.append(y - only_shared)
    np.testing.assert_allclose(sum(parts) + only_shared, uncut,
                               rtol=2e-4, atol=2e-5)
    got, _ = whole.apply({"params": params}, x)
    np.testing.assert_allclose(got, uncut, rtol=2e-4, atol=2e-5)


def test_no_pair_is_dropped_under_a_skewed_router(ref, monkeypatch):
    """64 of 256 held, 10 chosen: a router skewed so that every token
    chooses held expert 0, which takes 16 times its even share (and over
    six times the mean of the held), with blocks made small enough that
    the pairs need several: nothing left out, and the way back, a block
    at a time, is the reference's gradient."""
    sizes = {**TOY, "n_experts": 256, "experts_held": 64, "top_k": 10}
    layer = expert_layer(sizes, 0, 64)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 50, TOY["hidden"]))
    params = dict(layer.init(jax.random.PRNGKey(2), x)["params"])
    # rows of positive coordinates and a column of ones: expert 0's logit
    # is the row's sum, the largest by far
    xs = jnp.abs(x)
    params["router"] = (0.1 * params["router"]).at[:, 0].set(1.0)
    monkeypatch.setattr(ling, "BLOCK_ROWS", 128)
    y, stats = layer.apply({"params": params}, xs)
    want = reference_layer(ref, sizes, params, xs)
    assert np.isfinite(np.asarray(want)).all()
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)
    assert float(stats[0]) == 0.0  # dropped pairs
    # every one of the 150 tokens chooses expert 0: over 16 times its even
    # share of 150 x 10 / 256 pairs
    idx, _ = laguna.route_softmax(
        xs.reshape(-1, TOY["hidden"]), {"router": params["router"]},
        top_k=10, scale=2.5)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=256)[:64]
    assert counts[0] == 150 >= 16 * 150 * 10 / 256
    assert float(stats[1]) == pytest.approx(counts.max() / counts.mean())
    assert counts.sum() > 2 * 128  # more pairs than two blocks hold
    weigh = jax.random.normal(jax.random.PRNGKey(3), y.shape)
    got = jax.grad(lambda x_: jnp.sum(
        layer.apply({"params": params}, x_)[0] * weigh))(xs)
    back = jax.grad(lambda x_: jnp.sum(
        reference_layer(ref, sizes, params, x_) * weigh))(xs)
    assert float(jnp.linalg.norm(got - back)) <= 2e-3 * float(
        jnp.linalg.norm(back))


def test_a_block_of_sorted_pairs_holds_a_cap():
    """Ling's cell keeps its one size of block (twice the even share is
    the cap itself); Laguna's 327,680 possible pairs are five blocks."""
    assert ling.BLOCK_ROWS == 65536
    shape = lambda top_k, experts: jax.eval_shape(
        lambda i: ling._dispatch(i, experts, 64, 0)[0],
        jax.ShapeDtypeStruct((32768, top_k), jnp.int32)).shape[0]
    assert shape(8, 512) == 4 * 65536  # Ling: 262,144 pairs, 4 blocks
    assert shape(10, 256) == 5 * 65536  # Laguna: 327,680 pairs, 5 blocks


# --------------------------------------------------------------------------
# adapters, the base and the normal path


def test_adapter_sites_change_shape_with_the_layers_heads():
    model = get_model("laguna-s-2.1", dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16, **TOY)
    lm = wrap_model(model, "laguna-s-2.1", 4,
                    sample_x=jnp.zeros((1, 24), jnp.int32))
    assert isinstance(lm, LoraModel)
    assert {l.dtype for l in jax.tree.leaves(lm.base)} == {jnp.dtype("bfloat16")}
    sites = {s.key: (s.d_in, s.d_out) for s in lm.sites}
    assert len(sites) == 4 * 4  # q, k, v, o of four layers; no expert, no gate
    d, D = TOY["hidden"], TOY["head_dim"]
    for i, heads in enumerate(TOY["heads"]):
        at = f"params/layer_{i}/attn/attn_"
        assert sites[at + "q/kernel"] == (d, heads * D)
        assert sites[at + "o/kernel"] == (heads * D, d)
        assert sites[at + "k/kernel"] == sites[at + "v/kernel"] == (
            d, TOY["kv_heads"] * D)


def test_the_cells_adapters_are_the_issues_count():
    """Rank 16 on q, k, v, o at the published widths: 2.42M a node."""
    d, D, G, r = KWARGS["hidden"], KWARGS["head_dim"], KWARGS["kv_heads"], 16
    assert sum(r * (2 * (d + H * D) + 2 * (d + G * D))
               for H in KWARGS["heads"]) == 2_424_832


def scenario_dict(**over):
    return {
        "name": "laguna-toy", "seed": 3, "n_nodes": 4, "federation": "DFL",
        "topology": "fully", "aggregator": "fedavg",
        "protocol": {"train_set_size": 0}, "wire_dtype": "bf16",
        "data": {"dataset": "tokens-96-40", "batch_size": 1,
                 "val_percent": 0.0, "synthetic_train": 8,
                 "synthetic_test": 3, "seed": 3},
        "model": {"model": "laguna-s-2.1", "objective": "next_token",
                  "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
                  "kwargs": {**TOY, "layer_types": [FULL, WINDOW, FULL],
                             "mlp_layer_types": ["dense", "sparse", "sparse"],
                             "heads": [4, 6, 4]}},
        "lora": {"rank": 4, "alpha": 8.0},
        "training": {"rounds": 2, "epochs_per_round": 1, "optimizer": "adam",
                     "learning_rate": 0.01, "eval_every": 0}, **over}


def test_scenario_runs_the_language_model_path():
    from p2pfl_tpu.config.schema import ScenarioConfig
    from p2pfl_tpu.federation import Scenario
    from p2pfl_tpu.obs import trace as obs_trace

    sc = Scenario(ScenarioConfig.from_dict(scenario_dict()))
    assert obs_trace.stage_seconds()["scenario.init.base"] > 0
    res = sc.run(rounds=2)
    losses = np.array([r["Train/loss"] for r in res.history
                       if "Train/loss" in r]).reshape(2, 4)
    # every node's training loss falls from the first round to the second
    # (two sequences of 40 tokens a node: the test loss has nothing to learn
    # from)
    assert np.isfinite(losses).all() and (losses[1] < losses[0]).all()
    assert np.isfinite(sc.evaluate()["per_node_loss"]).all()
    counted = obs_trace.counted()
    assert not counted["moe.dropped_pairs"]["sum"].any()
    assert (counted["moe.load_max_over_mean"]["max"] >= 1.0).all()
    # both kinds of attention were traced, each under its own scope
    assert ling.score_tiles("gqa.attn") and ling.score_tiles("swa.attn")
    n = sc.config.n_nodes
    assert all(l.shape[0] == n for l in jax.tree.leaves(sc.fed.states.params))
    assert len(jax.tree.leaves(sc.fed.states.params)) == 3 * 4 * 2
    sc.close()


def test_another_models_counters_start_their_own_record():
    """Two language models in one process count under the same names
    with another number of expert layers (Ling's cell 6, Laguna's 4): the
    second model's first round starts a new record, it is not added to
    the first's."""
    from p2pfl_tpu.obs import trace as obs_trace

    rounds = lambda layers, value: {"moe.load_max_over_mean": np.full(
        (4, 1, 2, layers), value)}  # [nodes, epochs, steps, layers]
    obs_trace.note_counted(rounds(6, 3.0))
    obs_trace.note_counted(rounds(4, 2.0))
    obs_trace.note_counted(rounds(4, 1.0))
    got = obs_trace.counted()["moe.load_max_over_mean"]
    assert got["steps"] == 4 and got["max"].tolist() == [2.0] * 4
    assert got["sum"].tolist() == [6.0] * 4
