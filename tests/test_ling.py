"""Ling-3.0-flash at toy widths on the CPU: the program's modules
(``models/ling.py``: chunk-wise KDA, block-causal latent attention, the
sorted grouped expert layer, adapters as side paths of a frozen base)
against the plain reference (``benchmark/reference/ling_flash.py``: the
recurrence, whole-score attention, a loop over experts), which imports
nothing of the program. float32 compute here, so that a wrong term
shows and rounding does not."""

import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2pfl_tpu.learning.lora import LoraModel, frozen_argument, wrap_model
from p2pfl_tpu.models import get_model, ling

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIG = json.loads(
    (ROOT / "benchmark" / "configs" / "ling-3.0-flash.json").read_text())
# the rehearsal's toy widths, three layers of them: KDA over a dense FFN,
# KDA over experts, MLA over experts
TOY = {**CONFIG["scenario"]["model"]["kwargs"],
       **CONFIG["rehearse"]["scenario"]["model"]["kwargs"],
       "first_layer": 0, "layers": 3, "layer_group": 3, "first_dense": 1}
LORA = {"rank": 4, "alpha": 8.0}
F32 = jnp.float32


def ref_name(p):
    """The program's path of a leaf by the reference's naming rule
    (``params/layer_3/kda/kda_q/kernel/A`` -> ``L3.kda_q.A``), as the
    configuration file's ``param_map`` spells out for the cell's layers."""
    keys = [k for k in "/".join(
        str(getattr(k, "key", k)) for k in p).split("/")
        if k not in ("params", "kernel", "kda", "mla", "moe", "scale",
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
    return benchmark_module("reference/ling_flash.py")


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def toy_model(ref, **over):
    """The toy model, its adapter wrapper, and the same weights by the
    reference's names (adapters from the reference's own ``init``)."""
    sizes = {**TOY, **over}
    ref.configure(sizes, LORA)
    model = get_model("ling-3.0-flash", dtype=F32, **sizes)
    x = jnp.zeros((1, 24), jnp.int32)
    lm = wrap_model(model, "ling-3.0-flash", LORA["rank"],
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


def test_param_map_of_the_cell_follows_the_naming_rule():
    rule = lambda path: ref_name(path.split("/"))
    for maps in (CONFIG["param_map"], CONFIG["frozen"]["param_map"]):
        assert all(rule(path) == name for path, name in maps.items())


def tokens(key, shape, vocab=TOY["vocab"]):
    return jax.random.randint(jax.random.PRNGKey(key), shape, 0, vocab)


# --------------------------------------------------------------------------
# the delta rule


def kda_inputs(T, B=2, H=2, K=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, K)))
    v = jax.random.normal(ks[2], (B, T, H, K))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, K)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("T", [100, 7])
def test_chunked_kda_is_the_recurrence_forward_and_backward(ref, T):
    """Chunk 64, lengths that are no multiple of it, decays at their
    fastest (``g`` near -5 a step: 320 a chunk, past what ``exp`` holds
    unless the sub-chunks keep the exponents apart)."""
    args = kda_inputs(T)
    weigh = jax.random.normal(jax.random.PRNGKey(9), (2, T, 2, 16))
    chunked = lambda *a: jnp.sum(ling.kda_chunked(*a, dtype=F32) * weigh)
    plain = lambda *a: jnp.sum(ref.kda_recurrence(*a) * weigh)
    np.testing.assert_allclose(
        ling.kda_chunked(*args, dtype=F32), ref.kda_recurrence(*args),
        rtol=2e-4, atol=2e-5)
    got = jax.grad(chunked, argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(plain, argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
    # a block of heads at a time is the same thing
    np.testing.assert_allclose(
        ling.kda_chunked(*args, dtype=F32, head_block=1),
        ling.kda_chunked(*args, dtype=F32), rtol=1e-5, atol=1e-6)


def chunk_systems(case, n=6, C=64, K=16, seed=0):
    """``n`` chunks' systems as ``kda_heads`` builds them: ``A`` [n, C, C]
    strictly lower, ``A_ij = b_i sum_c k_ic k_jc e^(G_ic - G_jc)``, and
    the right-hand side ``b [V | K e^G]`` [n, C, 2 K]."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    k = jax.random.normal(ks[0], (n, 1 if case == "equal keys" else C, K))
    k = jnp.broadcast_to(k / jnp.linalg.norm(k, axis=-1, keepdims=True),
                         (n, C, K))
    v = jax.random.normal(ks[1], (n, C, K))
    if case == "equal keys":  # beta within 1e-3 of 1, no decay
        beta = 1.0 - 1e-3 * jax.random.uniform(ks[2], (n, C))
        g = jnp.zeros((n, C, K))
    else:
        beta = jax.nn.sigmoid(jax.random.normal(ks[2], (n, C)))
        shift = 12.0 if case == "fastest decay" else 0.0  # g within 1e-4 of -5
        g = -5.0 * jax.nn.sigmoid(
            jax.random.normal(ks[3], (n, C, K)) + shift)
    G = jnp.cumsum(g, axis=1)
    pair = jnp.exp(jnp.minimum(G[:, :, None] - G[:, None, :], 0.0))
    A = jnp.tril(jnp.einsum("nik,njk,nijk->nij", k, k, pair), -1)
    rhs = jnp.concatenate([v, k * jnp.exp(G)], axis=-1)
    return A * beta[..., None], rhs * beta[..., None]


@pytest.mark.parametrize("sides", [1, 2], ids=["one side", "two sides"])
@pytest.mark.parametrize("case", ["random", "equal keys", "fastest decay"])
def test_unit_lower_solve_is_the_triangular_solve(case, sides):
    """The product form against ``jax.lax.linalg.triangular_solve`` in
    float32, forward and every gradient, on chunks like ``kda_inputs``',
    on the near-worst case for cancellation (all keys of a chunk equal,
    ``beta`` at 1, no decay: every entry of ``A`` is near 1 and the
    inverse is all but bidiagonal) and with the decay at its bound; with
    one right-hand side, and with the two that ``kda_heads`` hands it
    apart (``b V`` and ``b K e^G``: one inverse, a product each, and
    ``A``'s gradient the sum over both)."""
    A, rhs = chunk_systems(case)
    rhs = tuple(jnp.split(rhs, sides, axis=-1))
    weigh = tuple(jax.random.normal(jax.random.PRNGKey(7 + i), r.shape)
                  for i, r in enumerate(rhs))
    plain = lambda A, rhs: tuple(jax.lax.linalg.triangular_solve(
        A + jnp.eye(A.shape[-1]), r, left_side=True, lower=True,
        unit_diagonal=True) for r in rhs)
    ours = lambda A, rhs: ling.unit_lower_solve(A, rhs) if sides > 1 else (
        ling.unit_lower_solve(A, rhs[0]),)
    close = lambda a, b: np.testing.assert_allclose(
        a, b, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(b))))
    for a, b in zip(ours(A, rhs), plain(A, rhs)):
        close(a, b)
    got, want = (jax.grad(lambda A, rhs: sum(
        jnp.sum(x * w) for x, w in zip(f(A, rhs), weigh)),
        argnums=(0, 1))(A, rhs) for f in (ours, plain))
    assert not np.triu(got[0]).any()  # the gradient to A: below the diagonal
    close(got[0], jnp.tril(want[0], -1))
    for a, b in zip(got[1], want[1]):
        close(a, b)


def test_chunk_length_has_to_be_a_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        ling.kda_heads(*kda_inputs(48), chunk=48)


def test_the_solves_device_time_is_read_by_its_scope(monkeypatch):
    """``kda.solve_device_s_per_round`` finds the scope nested in
    ``kda.scan`` on the way forward and as the hand-written way back
    names it, in the round program only; ``kda.device_s_per_round`` still
    reads all of the delta rule; a program without the scope reads
    nothing."""
    read = lambda metric, ctx: benchmark_module(
        f"readers/{metric}.py").read(ctx)
    fit = "jit(round_fn)/vmap()/while/body/fit.value_and_grad/"
    scope_s = {
        fit + "jvp(LingLM)/layer_2/kda/kda.scan/kda.solve/dot_general": 1.0,
        fit + "jvp(LingLM)/layer_2/kda/kda.scan/kda.solve/reduce_sum": 2.0,
        fit + "transpose(jvp(LingLM))/layer_2/kda/"
              "transpose(vmap(jvp(kda.scan)))/kda.solve/dot_general": 4.0,
        fit + "jvp(LingLM)/layer_2/kda/kda.scan/cumsum": 8.0,
        "jit(eval_fn)/vmap()/eval.forward/LingLM/layer_2/kda/kda.scan/"
        "kda.solve/dot_general": 16.0}
    monkeypatch.syspath_prepend(str(ROOT / "benchmark"))  # ``scopework``
    ctx = {"trace": {"scope_s": scope_s}, "rounds": 2}
    assert read("kda.solve_device_s_per_round", ctx) == 3.5
    assert read("kda.device_s_per_round", ctx) == 7.5
    without = {k: v for k, v in scope_s.items() if "kda.solve" not in k}
    for trace in (None, {"scope_s": without}):
        assert read("kda.solve_device_s_per_round",
                    {"trace": trace, "rounds": 2}) is None


# --------------------------------------------------------------------------
# latent attention's tiles


def dense_attention(q, k, v, scale):
    """Masked softmax attention over the whole ``[T, T]`` score square."""
    T = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    s = jnp.where(jnp.arange(T)[None, :] <= jnp.arange(T)[:, None], s,
                  -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                      precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("nodes", [None, 2], ids=["alone", "vmap"])
@pytest.mark.parametrize("T", [100, 512, 768, 1000])
def test_causal_attention_is_the_dense_masked_softmax(T, nodes):
    """Value and the gradients to q, k and v, float32, with a block of
    256: under one block, two blocks, three (an odd count), and a length
    that is padded; alone and under the ``vmap`` over nodes a round puts
    around it (the loops' bounds come from their counters and stay
    unbatched)."""
    shape = lambda *tail: (nodes, 2, T) + tail if nodes else (2, T) + tail
    ks = jax.random.split(jax.random.PRNGKey(T), 4)
    q, k = (jax.random.normal(key, shape(2, 24)) for key in ks[:2])
    v, weigh = (jax.random.normal(key, shape(2, 16)) for key in ks[2:])

    def both(attention):
        f = jax.value_and_grad(
            lambda q, k, v, weigh: jnp.sum(
                weigh * attention(q, k, v, 24 ** -0.5)), argnums=(0, 1, 2))
        return jax.jit(jax.vmap(f) if nodes else f)(q, k, v, weigh)

    (got, d_got), (want, d_want) = both(ling.causal_attention), both(
        dense_attention)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    for a, b in zip(d_got, d_want):
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("T, computed, square", [(4096, 136, 256),
                                                 (768, 6, 9), (100, 1, 1)])
def test_the_record_of_the_score_tiles(T, computed, square):
    """``score_tiles()`` is what the last trace of ``causal_attention``
    forms and what the full square holds (``mla.score_tiles_share``
    reads it): 136 of 256 at the cell's 4096 positions with tiles of
    256 x 256, one tile of one under a block."""
    one = lambda width: jax.ShapeDtypeStruct((1, T, 2, width), jnp.bfloat16)
    out = jax.eval_shape(
        lambda q, k, v: ling.causal_attention(q, k, v, 1.0, block=256,
                                              tile=256),
        one(24), one(24), one(16))
    assert out.shape == (1, T, 2, 16) and out.dtype == F32
    side = min(T, 256)
    assert ling.score_tiles() == {"computed": computed, "square": square,
                                  "block": side, "tile": side}


# --------------------------------------------------------------------------
# each mixer and the expert layer, module against reference function


@pytest.mark.parametrize("kind", ["kda", "mla", "moe"])
def test_each_layer_against_the_reference(ref, kind):
    lm, _, _, frozen = toy_model(ref)
    base = lm.base["params"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, TOY["hidden"]))
    dense = ref.make_dense({}, frozen, lambda a: a)
    same = lambda a: a
    kw = dict(dtype=F32)
    if kind == "kda":
        mod = ling.KDAMixer(TOY["heads"], TOY["head_dim"], **kw)
        got = mod.apply({"params": base["layer_0"]["kda"]}, x)
        want = ref.kda_mixer(dense, frozen, "L0.", x, same)
    elif kind == "mla":
        mod = ling.MLAMixer(TOY["heads"], TOY["nope"], TOY["rope"],
                            TOY["v_dim"], TOY["kv_rank"], **kw)
        got = mod.apply({"params": base["layer_2"]["mla"]}, x)
        want = ref.mla_mixer(dense, frozen, "L2.", x, same)
    else:
        z = ref.SIZES
        mod = ling.ExpertFFN(
            z["n_experts"], z["experts_held"], z["expert_offset"],
            z["expert_width"], z["shared_width"], z["top_k"], z["n_group"],
            z["topk_group"], z["route_scale"], **kw)
        got, stats = mod.apply({"params": base["layer_1"]["moe"]}, x)
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
    for p, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        w = want[ref_name(p)]
        assert float(jnp.linalg.norm(g - w)) <= 2e-3 * float(
            jnp.linalg.norm(w)), ref_name(p)
    # a masked row counts for nothing
    np.testing.assert_allclose(
        ref.loss(ref.forward(seeded, x, frozen=frozen), y,
                 jnp.array([True, False])),
        ref.loss(ref.forward(seeded, x[:1], frozen=frozen), y[:1],
                 jnp.array([True])), rtol=1e-5)
    rows = jax.jit(lambda m: lm.apply(adapters, x, y, m, method="loss")[0])
    both, first, second = (rows(jnp.array(m)) for m in (
        [True, True], [True, False], [False, True]))
    np.testing.assert_allclose(both, (first + second) / 2, rtol=1e-5)


# --------------------------------------------------------------------------
# the chip's share of the expert layer


def expert_layer(sizes, offset, held):
    return ling.ExpertFFN(
        sizes["n_experts"], held, offset, sizes["expert_width"],
        sizes["shared_width"], sizes["top_k"], sizes["n_group"],
        sizes["topk_group"], sizes["route_scale"], dtype=F32)


def test_the_shares_add_up(ref):
    """The 8 routing groups' partial outputs, the shared expert counted
    once, sum to the uncut layer's output: the program's layer told which
    experts it holds against the reference holding all of them."""
    E, G = TOY["n_experts"], TOY["n_group"]
    held = E // G
    whole = expert_layer(TOY, 0, E)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, TOY["hidden"]))
    params = whole.init(jax.random.PRNGKey(2), x)["params"]
    ref.configure({**TOY, "experts_held": E, "expert_offset": 0}, LORA)
    frozen = {"L." + k: (v["kernel"] if isinstance(v, dict) else v)
              for k, v in params.items()}
    uncut = ref.expert_ffn(ref.make_dense({}, frozen, lambda a: a), frozen,
                           "L.", x, lambda a: a)
    shared = params["shared_down"]["kernel"]
    only_shared = ling.swiglu(x @ params["shared_gate_up"]["kernel"]) @ shared
    parts = []
    for g in range(G):
        mine = dict(params)
        mine["experts_gate_up"] = params["experts_gate_up"][g * held:(g + 1) * held]
        mine["experts_down"] = params["experts_down"][g * held:(g + 1) * held]
        y, stats = expert_layer(TOY, g * held, held).apply({"params": mine}, x)
        assert float(stats[0]) == 0.0
        parts.append(y - only_shared)
    np.testing.assert_allclose(sum(parts) + only_shared, uncut,
                               rtol=2e-4, atol=2e-5)
    got, _ = whole.apply({"params": params}, x)
    np.testing.assert_allclose(got, uncut, rtol=2e-4, atol=2e-5)


def test_no_pair_is_dropped_under_a_biased_router(ref):
    """A selection bias that sends every token to the held group, expert
    0 first: 8 times the even load in all, every block of rows in use,
    nothing left out."""
    sizes = {**TOY, "experts_held": 4, "n_group": 4, "top_k": 4,
             "topk_group": 2}
    layer = expert_layer(sizes, 0, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 50, TOY["hidden"]))
    params = dict(layer.init(jax.random.PRNGKey(2), x)["params"])
    params["router_bias"] = jnp.zeros(16).at[:4].set(
        jnp.array([8.0, 4.0, 4.0, 4.0]))
    y, stats = layer.apply({"params": params}, x)
    ref.configure(sizes, LORA)
    frozen = {"L." + k: (v["kernel"] if isinstance(v, dict) else v)
              for k, v in params.items()}
    want = ref.expert_ffn(ref.make_dense({}, frozen, lambda a: a), frozen,
                          "L.", x, lambda a: a)
    assert np.isfinite(np.asarray(want)).all()
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)
    assert float(stats[0]) == 0.0  # dropped pairs
    assert float(stats[1]) == 1.0  # every held expert has every token
    # and the way back, a block at a time, is the reference's gradient
    weigh = jax.random.normal(jax.random.PRNGKey(3), y.shape)
    got = jax.grad(lambda x_: jnp.sum(
        layer.apply({"params": params}, x_)[0] * weigh))(x)
    back = jax.grad(lambda x_: jnp.sum(ref.expert_ffn(
        ref.make_dense({}, frozen, lambda a: a), frozen, "L.", x_,
        lambda a: a) * weigh))(x)
    assert float(jnp.linalg.norm(got - back)) <= 2e-3 * float(
        jnp.linalg.norm(back))


def test_the_nodes_rows_go_through_one_dispatch(ref):
    """Under the round's ``vmap`` over nodes the frozen expert layer is
    called once, on the rows of all nodes: as many grouped products as
    one node's call has, each over ``nodes x rows``; and every node gets
    what it would get alone."""
    layer = expert_layer(TOY, 0, TOY["experts_held"])
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 1, 24, TOY["hidden"]))
    params = layer.init(jax.random.PRNGKey(2), x[0])
    alone = lambda xb: layer.apply(params, xb)[0]

    def count(fn, arg):
        text = str(jax.make_jaxpr(fn)(arg))
        return text.count("ragged_dot"), text

    one, _ = count(alone, x[0])
    every, text = count(jax.vmap(alone), x)
    assert every == one > 0
    assert f"[{5 * 24 * TOY['top_k']}]" in text  # the pairs of all nodes, sorted once
    np.testing.assert_allclose(
        jax.vmap(alone)(x), jnp.stack([alone(xb) for xb in x]),
        rtol=1e-5, atol=1e-6)
    # the gradient to the rows too, by one folded pass back
    loss = lambda xs: jnp.sum(jax.vmap(alone)(xs) ** 2)
    want = jnp.stack([jax.grad(lambda xb: jnp.sum(alone(xb) ** 2))(xb)
                      for xb in x])
    np.testing.assert_allclose(jax.grad(loss)(x), want, rtol=1e-4, atol=1e-5)


def sorted_pairs(case, monkeypatch):
    """Tokens, the gradient of a block's output, and the chosen pairs
    sorted into three blocks of 128 rows (``_block``'s arguments behind
    its rows and weights). ``every``: 2 of 8 experts a token, all held:
    three full blocks. ``subset``: 4 of 16, experts 4 to 7 held, absent
    experts' pairs sorted last; 40 tokens choose the held four and 10
    more one of them, so the first block is full, the second has 42 of
    its rows in use and the third is past the last pair."""
    monkeypatch.setattr(ling, "BLOCK_ROWS", 128)
    d, W = 16, 24
    key = jax.random.split(jax.random.PRNGKey(7), 6)
    if case == "every":
        N, E, held, offset, top_k = 192, 8, 8, 0, 2
        idx = jnp.argsort(jax.random.uniform(key[0], (N, E)))[:, :top_k]
    else:
        N, E, held, offset, top_k = 96, 16, 4, 4, 4
        absent = jnp.array([0, 1, 2, 3, 8, 9, 10, 11, 12, 13, 14, 15])
        idx = absent[jnp.argsort(jax.random.uniform(key[0], (N, 12)))[:, :4]]
        idx = idx.at[:40].set(jnp.arange(4, 8)).at[40:50, 2].set(
            4 + jnp.arange(10) % 4)
    x = jax.random.normal(key[1], (N, d))
    g = jax.random.normal(key[2], (N, d))
    w = jax.random.uniform(key[3], (N, top_k), minval=0.1)
    w_gu = jax.random.normal(key[4], (held, d, 2 * W)) / d ** 0.5
    w_d = jax.random.normal(key[5], (held, W, d)) / W ** 0.5
    order, counts, ends, rows, n_blocks = ling._dispatch(idx, E, held, offset)
    assert (rows, n_blocks) == (128, 3)
    pair_w = jnp.pad(w.reshape(-1), (0, order.shape[0] - w.size))[order]
    return (x, pair_w, g, (order, counts, ends, w_gu, w_d),
            dict(rows=rows, top_k=top_k, dtype=F32))


@pytest.mark.parametrize("case, block, used", [
    ("every", 0, 128), ("every", 1, 128), ("every", 2, 128),
    ("subset", 1, 42), ("subset", 2, 0),
], ids=["every expert held, the first full block", "the second full block",
        "the third full block", "a held subset, a part-filled block",
        "a block past the last pair"])
def test_a_blocks_way_back_is_reverse_mode_through_the_block(
        case, block, used, monkeypatch):
    """``_block_back``, three grouped products, against ``jax.vjp`` of
    ``_block``, four: the gradient to the rows and to the pairs' weights
    agree to float32 rounding, and rows past the last pair give and take
    nothing."""
    x, pair_w, g, rest, static = sorted_pairs(case, monkeypatch)
    first = block * 128
    assert min(max(int(rest[2][-1]) - first, 0), 128) == used
    pw = pair_w[first:first + 128]
    _, back = jax.vjp(lambda x_, pw_: ling._block(
        x_, pw_, first, *rest, **static), x, pw)
    want_dx, want_dpw = back(g)
    dx, dpw = ling._block_back(x, pw, g, first, *rest, **static)
    np.testing.assert_allclose(dx, want_dx, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dpw, want_dpw, rtol=1e-5, atol=1e-5)
    assert not np.asarray(dpw[used:]).any()
    assert np.asarray(dpw[:used]).all() and np.asarray(dx).any() == bool(used)


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no remat"])
def test_a_sparse_layers_gradient_holds_five_grouped_products(remat):
    """A training step of one sparse layer (one block of rows): two
    grouped products forward and three on the way back, which forms the
    up-projection again and the down-projection not at all. ``remat``
    adds none: its copy of the expert forward has no reader on the way
    back."""
    sizes = {**TOY, "layers": 2, "remat": remat}  # a dense FFN, then experts
    model = get_model("ling-3.0-flash", dtype=F32, **sizes)
    rows = tokens(0, (2, 24))
    params = model.init(jax.random.PRNGKey(1), rows)
    loss = lambda p: model.apply(p, rows, rows, method="loss")[0]
    text = str(jax.make_jaxpr(jax.grad(loss))(params))
    assert text.count("= ragged_dot_general[") == 5


def test_the_nodes_are_the_delta_rules_batch():
    """Under the round's ``vmap`` over nodes a forward pass of everything
    between ``KDAMixer``'s projections runs once, over the rows of all
    nodes (the scan's state is ``[nodes x batch, block of heads, K, V]``,
    as often in the program as one node's is in its own), and every node
    gets what it would get alone: the output, and the gradients to its
    input, to its own projections (where adapters ride) and to what all
    nodes share (a tap, the norm's scale), which cannot be a node's own."""
    nodes, B, T, H, K = 3, 2, 70, 8, 16
    mixer = ling.KDAMixer(H, K, dtype=F32)
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(ks[0], (nodes, B, T, TOY["hidden"]))
    weigh = jax.random.normal(ks[1], x.shape)
    params = mixer.init(ks[2], x[0])["params"]
    mine = ("kda_q", "kda_k", "kda_v", "kda_o")
    shared = {k: v for k, v in params.items() if k not in mine}
    own = jax.tree.map(  # a node's own projections, as under adapters
        lambda a: a + 0.05 * jax.random.normal(ks[3], (nodes,) + a.shape),
        {k: params[k] for k in mine})
    alone = lambda own, xb, shared=shared: mixer.apply(
        {"params": {**shared, **own}}, xb)
    both = jax.value_and_grad(
        lambda own, xb, wb: jnp.sum(alone(own, xb) * wb), argnums=(0, 1))
    node = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    want = [both(node(own, i), x[i], weigh[i]) for i in range(nodes)]
    got = jax.jit(jax.vmap(both))(own, x, weigh)
    np.testing.assert_allclose(
        jax.vmap(alone)(own, x),
        jnp.stack([alone(node(own, i), x[i]) for i in range(nodes)]),
        rtol=1e-5, atol=1e-6)
    for i in range(nodes):
        for a, b in zip(jax.tree.leaves(node(got, i)),
                        jax.tree.leaves(want[i])):
            np.testing.assert_allclose(
                a, b, rtol=0, atol=2e-5 * float(jnp.max(jnp.abs(b))))
    text = str(jax.make_jaxpr(jax.vmap(alone))(own, x))
    one = str(jax.make_jaxpr(alone)(node(own, 0), x[0]))
    state = lambda batch: f"f32[{batch},4,{K},{K}]"  # the scan's, a block
    assert text.count(state(nodes * B)) == one.count(state(B)) > 0
    assert state(B) not in text
    # the taps and the scale take each node's own gradient all the same
    to_shared = jax.grad(lambda shared, xb, wb: jnp.sum(
        alone(node(own, 0), xb, shared) * wb))
    got = jax.vmap(to_shared, in_axes=(None, 0, 0))(shared, x, weigh)
    want = to_shared(shared, x[1], weigh[1])
    for name in ("q_conv", "o_norm"):
        np.testing.assert_allclose(got[name][1], want[name],
                                   rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="frozen input is mapped"):
        jax.vmap(lambda taps, xb: alone(
            node(own, 0), xb, {**shared, "q_conv": taps}))(
            jnp.stack([shared["q_conv"]] * nodes), x)


# --------------------------------------------------------------------------
# adapters and the base


def test_side_path_adapter_equals_the_merged_kernel(ref):
    lm, adapters, _, _ = toy_model(ref)
    x = tokens(1, (2, 40))
    merged = lm.inner.apply(lm.materialize(adapters), x)
    # the adapters move the logits by tenths; the two forms part by the
    # rounding of two orders of summation (and where that flips a
    # router's choice between two near-equal experts, by a little more)
    assert float(jnp.abs(merged - lm.inner.apply(lm.base, x)).max()) > 0.05
    np.testing.assert_allclose(lm.apply(adapters, x), merged,
                               rtol=2e-3, atol=2e-3)
    # the base is an argument of the compiled program, not a constant in it
    fn = jax.jit(frozen_argument(lm, lambda a, x: lm.apply(a, x)))
    text = fn.lower(adapters, x, lm.base).as_text()
    assert max(len(c.split(">")[0]) for c in text.split("dense<")[1:]) < 4096
    np.testing.assert_allclose(fn(adapters, x, lm.base), merged,
                               rtol=2e-3, atol=2e-3)
    zeroed = jax.tree.map(jnp.zeros_like, lm.base)
    assert not np.allclose(fn(adapters, x, zeroed), merged, atol=1e-2)


def test_base_is_built_in_the_models_own_type():
    model = get_model("ling-3.0-flash", dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16, **TOY)
    lm = wrap_model(model, "ling-3.0-flash", 4,
                    sample_x=jnp.zeros((1, 24), jnp.int32))
    assert isinstance(lm, LoraModel)
    assert {l.dtype for l in jax.tree.leaves(lm.base)} == {jnp.dtype("bfloat16")}
    adapters = lm.init(jax.random.PRNGKey(0), None)
    assert {l.dtype for l in jax.tree.leaves(adapters)} == {jnp.dtype("float32")}
    assert len(lm.sites) == 2 * 4 + 4  # two KDA mixers, one MLA


# --------------------------------------------------------------------------
# the token data set and the normal path


def test_token_data_set_rows_and_labels():
    from p2pfl_tpu.config.schema import DataConfig
    from p2pfl_tpu.datasets import FederatedDataset
    from p2pfl_tpu.datasets.sources import get_dataset

    a = get_dataset("tokens-96-40", seed=7, synthetic_sizes=(12, 3))
    b = get_dataset("tokens-96-40", seed=7, synthetic_sizes=(12, 3))
    assert a.x_train.shape == a.y_train.shape == (12, 40)
    assert a.x_test.shape == (3, 40) and a.x_train.dtype == np.int32
    np.testing.assert_array_equal(a.x_train, b.x_train)
    np.testing.assert_array_equal(a.x_train[:, 1:], a.y_train[:, :-1])
    assert 0 <= a.x_train.min() and a.y_train.max() < 96
    with pytest.raises(ValueError, match="tokens-V-T"):
        get_dataset("tokenz")
    data = FederatedDataset.make(DataConfig(
        dataset="tokens-96-40", val_percent=0.0, synthetic_train=8,
        synthetic_test=3, seed=7), 4)
    x, y, mask, n = data.stacked()
    assert x.shape == y.shape == (4, 2, 40) and x.dtype == np.int32
    assert mask.all() and list(n) == [2, 2, 2, 2]


def scenario_dict(**over):
    small = {**TOY, "first_layer": 1, "layers": 2}  # KDA, MLA; experts
    return {
        "name": "ling-toy", "seed": 3, "n_nodes": 4, "federation": "DFL",
        "topology": "fully", "aggregator": "fedavg",
        "protocol": {"train_set_size": 0}, "wire_dtype": "bf16",
        "data": {"dataset": "tokens-96-40", "batch_size": 1,
                 "val_percent": 0.0, "synthetic_train": 8,
                 "synthetic_test": 3, "seed": 3},
        "model": {"model": "ling-3.0-flash", "objective": "next_token",
                  "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
                  "kwargs": small},
        "lora": {"rank": 4, "alpha": 8.0},
        "training": {"rounds": 3, "epochs_per_round": 1, "optimizer": "adam",
                     "learning_rate": 0.01, "eval_every": 0}, **over}


def test_scenario_runs_the_language_model_path():
    from p2pfl_tpu.config.schema import ScenarioConfig
    from p2pfl_tpu.federation import Scenario
    from p2pfl_tpu.obs import trace as obs_trace

    sc = Scenario(ScenarioConfig.from_dict(scenario_dict()))
    base = jax.tree.leaves(sc.model.base)
    assert all(a is b for a, b in zip(base, jax.tree.leaves(sc._frozen)))
    assert len(sc._frozen) == 1
    assert obs_trace.stage_seconds()["scenario.init.base"] > 0
    before = sc.evaluate()["per_node_loss"]
    res = sc.run(rounds=3)
    losses = [r["Train/loss"] for r in res.history if "Train/loss" in r]
    assert len(losses) == 12 and np.isfinite(losses).all()
    after = sc.evaluate()["per_node_loss"]
    assert np.isfinite(after).all() and np.mean(after) < np.mean(before)
    counted = obs_trace.counted()
    assert counted["moe.dropped_pairs"]["sum"].tolist() == [0.0, 0.0]
    assert counted["moe.dropped_pairs"]["steps"] >= 6
    assert (counted["moe.load_max_over_mean"]["max"] >= 1.0).all()
    # the base is an argument of the round program, not a constant in it
    text = sc._round_fn.lower(
        sc.fed, *sc._data_args, *sc._plan_args(None), *sc._frozen).as_text()
    assert max(len(c.split(">")[0]) for c in text.split("dense<")[1:]) < 4096
    # trained: adapters alone, one stack a node
    n = sc.config.n_nodes
    assert all(l.shape[0] == n for l in jax.tree.leaves(sc.fed.states.params))
    sc.close()
