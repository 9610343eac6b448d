"""Kimi-Linear-48B-A3B at toy widths on the CPU: the program's modules
(``models/kimi_linear.py`` over the parts it shares with
``models/ling.py``: the chunk-wise delta rule in its form for an
unbounded decay, NoPE latent attention, the sorted grouped expert layer
with a shared expert) against the plain reference
(``benchmark/reference/kimi_linear.py``: the recurrence, attention a
block of queries at a time, a loop over experts), which imports nothing
of the program. float32 compute here, so that a wrong term shows and
rounding does not."""

import functools
import hashlib
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2pfl_tpu.learning.lora import LoraModel, wrap_model
from p2pfl_tpu.models import get_model, kimi_linear, ling
from p2pfl_tpu.ops import kda_diag

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIG = json.loads(
    (ROOT / "benchmark" / "configs" / "kimi-linear-48b-a3b.json").read_text())
KWARGS = CONFIG["scenario"]["model"]["kwargs"]
# the rehearsal's toy widths, four layers of them: KDA over the dense FFN,
# KDA, KDA, MLA over experts
TOY = {**KWARGS, **CONFIG["rehearse"]["scenario"]["model"]["kwargs"],
       "layers": 4}
LORA = {"rank": 4, "alpha": 8.0}
F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def ref_name(p):
    """The program's path of a leaf by the reference's naming rule
    (``params/layer_3/kda/kda_q/kernel/A`` -> ``L3.kda_q.A``,
    ``params/layer_1/kda/kda_gb/bias`` -> ``L1.kda_gb.bias``), as the
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
    return benchmark_module("reference/kimi_linear.py")


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def toy_model(ref, **over):
    """The toy model, its adapter wrapper, and the same weights by the
    reference's names (adapters from the reference's own ``init``)."""
    sizes = {**TOY, **over}
    ref.configure(sizes, LORA)
    model = get_model("kimi-linear-48b-a3b", dtype=F32, **sizes)
    x = jnp.zeros((1, 24), jnp.int32)
    lm = wrap_model(model, "kimi-linear-48b-a3b", LORA["rank"],
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
    # and names every leaf of the model at the cell's eight layers
    model = get_model("kimi-linear-48b-a3b", **{**TOY, "layers": 8})
    base = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))
    assert {"/".join(str(k.key) for k in p) for p, _ in
            jax.tree_util.tree_flatten_with_path(base)[0]} == set(
        CONFIG["frozen"]["param_map"])


# --------------------------------------------------------------------------
# the delta rule with an unbounded decay


def kda_inputs(T, B=2, H=2, K=16, seed=0, deepest=40.0):
    """Unit keys, scaled unit queries, and decays anywhere down to
    ``-deepest`` a step: 16 steps of them reach 640, past what ``exp``
    holds in float32 and past the bounded form's clip at 80."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, K)))
    v = jax.random.normal(ks[2], (B, T, H, K))
    g = -deepest * jax.random.uniform(ks[3], (B, T, H, K))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("T", [100, 7, 130])
def test_unbounded_kda_is_the_recurrence_forward_and_backward(ref, T):
    """With no floor the chunk-wise form is the recurrence for decays down
    to -40 a step, at lengths that are no multiple of the chunk, value and
    every gradient, all finite; the same inputs through the bounded form
    (Ling's, whose clip assumes -5 a step) are not."""
    args = kda_inputs(T)
    weigh = jax.random.normal(jax.random.PRNGKey(9), (2, T, 2, 16))
    chunked = lambda *a: jnp.sum(ling.kda_chunked(*a, dtype=F32) * weigh)
    plain = lambda *a: jnp.sum(ref.kda_recurrence(*a) * weigh)
    want = ref.kda_recurrence(*args)
    got = ling.kda_chunked(*args, dtype=F32)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    d_got = jax.grad(chunked, argnums=(0, 1, 2, 3, 4))(*args)
    d_want = jax.grad(plain, argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(d_got, d_want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
    bounded = ling.kda_chunked(*args, dtype=F32, floor=-5.0)
    assert float(jnp.max(jnp.abs(bounded - want))) > 0.1 * float(
        jnp.max(jnp.abs(want)))


def test_a_floor_past_the_clip_takes_the_exact_form():
    """A floor whose 16 steps pass 80 (-6 a step) is not bounded enough
    for the clip: it takes the pairwise form, as no floor does."""
    args = kda_inputs(70, deepest=6.0)
    np.testing.assert_array_equal(ling.kda_chunked(*args, dtype=F32, floor=-6.0),
                                  ling.kda_chunked(*args, dtype=F32))
    assert not np.array_equal(ling.kda_chunked(*args, dtype=F32, floor=-6.0),
                              ling.kda_chunked(*args, dtype=F32, floor=-5.0))


def test_the_diagonals_way_back_is_reverse_mode_through_its_forward():
    """``_diag_pairs``' hand-written way back (the factors formed again
    inside each of its sums) against reverse mode through its own
    forward, and its forward against a loop over the pairs."""
    q, k, _, g, _ = kda_inputs(64, H=3, K=8)
    G = jnp.cumsum(g, axis=1)
    wa, wb = (jax.random.normal(jax.random.PRNGKey(s), (2, 4, 3, 16, 16))
              for s in (1, 2))
    lead = lambda a: jnp.moveaxis(a.reshape(2, 4, 16, 3, 8), 3, 2)
    q, k, G = lead(q), lead(k), lead(G)  # [2, 4 sub-chunks, 3, 16, 8]
    q, k, G = (jnp.moveaxis(a, 2, 1) for a in (q, k, G))  # heads before
    wa, wb = (jnp.moveaxis(w, 2, 1) for w in (wa, wb))

    def loss(diag):
        return lambda q, k, G: sum(jnp.sum(m * w) for m, w in zip(
            diag(q.reshape(2, 3, 64, 8), k.reshape(2, 3, 64, 8),
                 G.reshape(2, 3, 64, 8)), (wa, wb)))

    plain = lambda q, k, G: ling._diag_pairs_fwd(q, k, G, 16)[0]
    fast = lambda q, k, G: ling._diag_pairs(q, k, G, 16)
    a, b = fast(*(x.reshape(2, 3, 64, 8) for x in (q, k, G)))
    i, j = 5, 3  # one pair of sub-chunk 2, by hand
    kk, qq, GG = (x[0, 1, 2] for x in (k, q, G))
    assert float(a[0, 1, 2, i, j]) == pytest.approx(float(jnp.sum(
        kk[i] * kk[j] * jnp.exp(GG[i] - GG[j]))), rel=1e-5)
    assert float(b[0, 1, 2, i, j]) == pytest.approx(float(jnp.sum(
        qq[i] * kk[j] * jnp.exp(GG[i] - GG[j]))), rel=1e-5)
    assert not np.triu(np.asarray(a), 1).any()
    for x, y in zip(jax.grad(loss(fast), argnums=(0, 1, 2))(q, k, G),
                    jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, G)):
        close(x, y, 1e-5)


def interpreted(monkeypatch):
    """The chip's path of ``kda_heads`` on the CPU: ``on_tpu`` true and the
    kernels of ``ops/kda_diag.py`` through Pallas's interpreter."""
    monkeypatch.setattr(ling, "on_tpu", lambda: True)
    for name in ("forward", "backward"):
        monkeypatch.setattr(kda_diag, name, functools.partial(
            getattr(kda_diag, name), interpret=True))


@pytest.mark.parametrize("deepest, lead", [(40.0, (2, 2, 3)),
                                           (0.5, (2, 4, 20))])
def test_the_diagonal_kernels_are_the_xla_form(deepest, lead):
    """``ops/kda_diag.py``'s two kernels (interpreted) against the XLA form
    they replace on the chip, ``_diag_pairs_fwd`` and ``_diag_pairs_back``,
    at 128 channels: the blocks, 0 above the diagonal, and the gradients
    to ``q``, ``k`` and ``G``, for decays down to -40 a step (past what a
    product of ``e^G`` and ``e^-G`` holds) and shallow ones, over 12
    (chunk, batch, head) triples (one grid step of 128, padded) and 160
    (two)."""
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    shape = lead + (64, 128)  # [chunks, batch, heads, C, K]
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], shape)) * 128 ** -0.5
    k = unit(jax.random.normal(ks[1], shape))
    G = jnp.cumsum(-deepest * jax.random.uniform(ks[2], shape), axis=-2)
    (want_a, want_b), kept = ling._diag_pairs_fwd(q, k, G, 16)
    a, b = kda_diag.forward(q, k, G, sub=16, interpret=True)
    assert a.shape == want_a.shape == lead + (4, 16, 16)
    close(a, want_a, 1e-6)
    close(b, want_b, 1e-6)
    assert not np.triu(np.asarray(a), 1).any()
    assert not np.triu(np.asarray(b), 1).any()
    da, db = (jax.random.normal(x, a.shape) for x in ks[3:])
    got = kda_diag.backward(q, k, G, da, db, sub=16, interpret=True)
    for x, y in zip(got, ling._diag_pairs_back(16, kept, (da, db))):
        assert np.isfinite(np.asarray(x)).all()
        close(x, y, 1e-6)


@pytest.mark.parametrize("T", [100, 7])
def test_unbounded_kda_through_the_kernels_is_the_recurrence(ref, T,
                                                             monkeypatch):
    """The chip's path of the form for an unbounded decay (the diagonal
    blocks by the kernels, interpreted) is the recurrence, value and every
    gradient, at 128 channels and decays down to -40 a step, and the
    trace-time record says which form ran; the CPU's path says the XLA
    form did."""
    args = kda_inputs(T, K=128)
    weigh = jax.random.normal(jax.random.PRNGKey(9), (2, T, 2, 128))
    ling.kda_chunked(*args, dtype=F32)
    assert ling.diag_forms() == {"kernel": False, "sub": 16, "chunk": 64}
    interpreted(monkeypatch)
    got = ling.kda_chunked(*args, dtype=F32)
    assert ling.diag_forms() == {"kernel": True, "sub": 16, "chunk": 64}
    np.testing.assert_allclose(got, ref.kda_recurrence(*args), rtol=2e-4,
                               atol=2e-5)
    d_got = jax.grad(lambda *a: jnp.sum(ling.kda_chunked(*a, dtype=F32)
                                        * weigh), argnums=(0, 1, 2, 3, 4))(*args)
    d_want = jax.grad(lambda *a: jnp.sum(ref.kda_recurrence(*a) * weigh),
                      argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(d_got, d_want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


def test_lings_bounded_path_is_the_parents_to_the_bit():
    """Ling's inputs (decays in ``[-5, 0]``) through the bounded form: the
    output and every gradient, float32 and bfloat16 products, at two
    lengths, hash to what the form gave before the unbounded one came."""
    def kda_ling(T):
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
        q = unit(jax.random.normal(ks[0], (2, T, 2, 16))) * 16 ** -0.5
        k = unit(jax.random.normal(ks[1], (2, T, 2, 16)))
        v = jax.random.normal(ks[2], (2, T, 2, 16))
        g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (2, T, 2, 16)))
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, T, 2)))
        return q, k, v, g, beta

    with jax.default_matmul_precision("default"):
        h = hashlib.sha256()
        for T in (100, 7):
            args = kda_ling(T)
            w = jax.random.normal(jax.random.PRNGKey(9), (2, T, 2, 16))
            for dt in (F32, jnp.bfloat16):
                run = lambda *a: ling.kda_chunked(*a, dtype=dt, floor=-5.0)
                h.update(np.asarray(run(*args)).tobytes())
                for a in jax.grad(lambda *a: jnp.sum(run(*a) * w),
                                  argnums=(0, 1, 2, 3, 4))(*args):
                    h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == (
        "e814bb0c53518f3755c2857523efb00f4b5674cc84b8fb6b00a6f31d422321e5")


@pytest.mark.parametrize("name", ["ling-3.0-flash", "laguna-s-2.1",
                                  "lfm2-8b-a1b"])
def test_the_other_models_init_is_the_parents_to_the_bit(name):
    """``MLAMixer``'s new fields and ``CausalLM.counters`` leave the other
    language models' seeded bases as they were (their rehearsal sizes)."""
    expected = {"ling-3.0-flash": "73e9264220a762bd",
                "laguna-s-2.1": "bbc6a61db6d60ef2",
                "lfm2-8b-a1b": "8af7c759ae3320de"}
    cfg = json.loads((ROOT / "benchmark" / "configs" / f"{name}.json")
                     .read_text())
    kw = {**cfg["scenario"]["model"]["kwargs"],
          **cfg["rehearse"]["scenario"]["model"]["kwargs"]}
    p = get_model(name, **kw).init(jax.random.PRNGKey(3),
                                   jnp.zeros((1, 24), jnp.int32))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(p)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest()[:16] == expected[name]


def test_deep_decay_share_is_a_count_by_hand():
    """Sub-chunks of 16 positions from position 0, the last one padded
    with no decay; a (sub-chunk, head, channel) counts where its decays
    sum below -80."""
    g = -jax.random.uniform(jax.random.PRNGKey(0), (2, 37, 3, 4)) * 11.0
    want = 0
    for b in range(2):
        for s0 in range(0, 37, 16):
            total = np.asarray(g[b, s0:s0 + 16]).sum(axis=0)  # [H, K]
            want += int((total < -80.0).sum())
    got = float(kimi_linear.deep_decay_share(g))
    assert want > 0
    assert got == pytest.approx(100.0 * want / (2 * 3 * 3 * 4), rel=1e-6)
    assert float(kimi_linear.deep_decay_share(jnp.full((1, 16, 1, 1), -5.0))
                 ) == 0.0
    assert float(kimi_linear.deep_decay_share(jnp.full((1, 16, 1, 1), -5.1))
                 ) == 100.0


# --------------------------------------------------------------------------
# NoPE latent attention, the expert layer's shares


def test_nope_mla_makes_no_rotary_norm_or_gate(ref):
    """Without rotary embedding, q/k norms and gate, ``MLAMixer`` makes
    none of their parameters and is ``softmax(q k^T / sqrt(192)) v`` over
    ``[k_N | k_R]``; Ling's defaults keep its tree."""
    sizes = dict(heads=2, nope=16, rope=8, v_dim=16, kv_rank=12)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32))
    nope = ling.MLAMixer(**sizes, eps=1e-5, use_nope=True, dtype=F32)
    p = nope.init(jax.random.PRNGKey(2), x)["params"]
    assert set(p) == {"mla_q", "mla_dkv", "c_norm", "mla_ukv", "mla_o"}
    assert set(ling.MLAMixer(**sizes, dtype=F32).init(
        jax.random.PRNGKey(2), x)["params"]) == {
        "mla_q", "mla_dkv", "c_norm", "mla_ukv", "mla_o", "q_norm", "k_norm",
        "mla_g"}
    ref.configure({**TOY, **sizes, "eps": 1e-5}, LORA)
    w = {"L.mla_q": p["mla_q"]["kernel"], "L.mla_dkv": p["mla_dkv"]["kernel"],
         "L.mla_ukv": p["mla_ukv"]["kernel"], "L.mla_o": p["mla_o"]["kernel"],
         "L.c_norm": p["c_norm"]}
    want = ref.mla_mixer(ref.make_dense({}, w, lambda a: a), w, "L.", x,
                         lambda a: a)
    close(nope.apply({"params": p}, x), want, 2e-5)


def expert_layer(sizes, offset=0, held=None, shared=None):
    return ling.ExpertFFN(
        sizes["n_experts"], held or sizes["experts_held"], offset,
        sizes["expert_width"], sizes["shared_width"] if shared is None
        else shared, sizes["top_k"], 1, 1, sizes["route_scale"], dtype=F32)


def test_eight_shares_of_the_experts_add_up_to_the_uncut_layer(ref):
    """32 of 256 held, as the cell holds them: the parts that the 8
    offsets' chips compute, with the shared expert counted once, add up
    to the reference's whole layer (every expert held); no pair dropped."""
    sizes = {**TOY, "n_experts": 32, "experts_held": 4, "top_k": 8}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, TOY["hidden"]))
    whole = expert_layer(sizes, held=32)
    params = whole.init(jax.random.PRNGKey(2), x)["params"]
    ref.configure({**sizes, "experts_held": 32}, LORA)
    frozen = {"L." + k: v for k, v in params.items()
              if not isinstance(v, dict)}
    frozen.update({"L." + k: v["kernel"] for k, v in params.items()
                   if isinstance(v, dict)})
    want = ref.expert_ffn(ref.make_dense({}, frozen, lambda a: a), frozen,
                          "L.", x, lambda a: a)
    parts = []
    for c in range(8):
        cut = slice(4 * c, 4 * c + 4)
        mine = {**params, "experts_gate_up": params["experts_gate_up"][cut],
                "experts_down": params["experts_down"][cut]}
        if c:  # the shared expert is every chip's: counted once
            mine = {k: v for k, v in mine.items() if not k.startswith("shared")}
        y, stats = expert_layer(sizes, 4 * c, 4, None if not c else 0).apply(
            {"params": mine}, x)
        assert float(stats[0]) == 0.0
        parts.append(y)
    close(sum(parts), want, 2e-5)
    close(whole.apply({"params": params}, x)[0], want, 2e-5)


# --------------------------------------------------------------------------
# each kind of layer, and the whole model, against the reference


@pytest.mark.parametrize("kind", ["kda", "mla", "dense", "experts"])
def test_each_layer_against_the_reference(ref, kind):
    lm, _, _, frozen = toy_model(ref)
    base = lm.base["params"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, TOY["hidden"]))
    dense = ref.make_dense({}, frozen, lambda a: a)
    same = lambda a: a
    if kind == "kda":
        got, deep = kimi_linear.KimiDeltaMixer(
            TOY["heads"], TOY["head_dim"], TOY["conv"], TOY["kda_chunk"],
            TOY["eps"], dtype=F32).apply({"params": base["layer_2"]["kda"]}, x)
        *inputs, gate = ref.kda_inputs(dense, frozen, "L2.", x)
        want = ref.kda_output(dense, frozen, "L2.",
                              ref.kda_recurrence(*inputs), gate)
        # the seeded decays reach past the clip somewhere
        assert 0.0 < float(deep) == float(kimi_linear.deep_decay_share(inputs[3]))
    elif kind == "mla":
        got = ling.MLAMixer(
            TOY["heads"], TOY["nope"], TOY["rope"], TOY["v_dim"],
            TOY["kv_rank"], eps=TOY["eps"], use_nope=True,
            dtype=F32).apply({"params": base["layer_4"]["mla"]}, x)
        want = ref.mla_mixer(dense, frozen, "L4.", x, same)
    elif kind == "dense":
        got = ling.DenseFFN(TOY["dense_width"], dtype=F32).apply(
            {"params": base["layer_1"]["ffn"]}, x)
        want = dense("L1.ffn_down", ref.swiglu(dense("L1.ffn_gate_up", x)))
    else:
        got, stats = expert_layer(TOY).apply(
            {"params": base["layer_2"]["moe"]}, x)
        want = ref.expert_ffn(dense, frozen, "L2.", x, same)
        assert float(stats[0]) == 0.0
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_whole_model_loss_and_adapter_gradients(ref):
    lm, adapters, seeded, frozen = toy_model(ref)
    x, y = tokens(1, (2, 40)), tokens(2, (2, 40))
    mask = jnp.array([True, True])
    loss = lambda a: lm.apply(a, x, y, mask, method="loss")
    plain = lambda p: ref.loss(ref.forward(p, x, frozen=frozen), y, mask)
    (l_got, counters), got = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        adapters)
    l_want, want = jax.jit(jax.value_and_grad(plain))(seeded)
    np.testing.assert_allclose(l_got, l_want, rtol=1e-5)
    assert len(want) == 2 * 4 * 4  # three KDA layers and one MLA, 4 sites each
    for p, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        w = want[ref_name(p)]
        assert float(jnp.linalg.norm(g - w)) <= 2e-3 * float(
            jnp.linalg.norm(w)), ref_name(p)
    np.testing.assert_allclose(lm.apply(adapters, x),
                               ref.forward(seeded, x, frozen=frozen),
                               rtol=2e-4, atol=2e-4)
    # a counter a KDA layer and two an expert layer, none dropped
    assert counters["kda.deep_decay_share"].shape == (3,)
    assert counters["moe.dropped_pairs"].shape == (3,)
    assert not np.asarray(counters["moe.dropped_pairs"]).any()
    assert (np.asarray(counters["kda.deep_decay_share"]) > 0).all()


# --------------------------------------------------------------------------
# adapters, the base and the normal path


def test_adapters_ride_on_the_mixers_projections_only():
    model = get_model("kimi-linear-48b-a3b", dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16, **TOY)
    lm = wrap_model(model, "kimi-linear-48b-a3b", 4,
                    sample_x=jnp.zeros((1, 24), jnp.int32))
    assert isinstance(lm, LoraModel)
    assert {l.dtype for l in jax.tree.leaves(lm.base)} == {jnp.dtype("bfloat16")}
    sites = {s.key for s in lm.sites}
    assert sites == {f"params/layer_{l}/kda/kda_{n}/kernel"
                     for l in (1, 2, 3) for n in "qkvo"} | {
        f"params/layer_4/mla/mla_{n}/kernel"
        for n in ("q", "dkv", "ukv", "o")}


def test_the_cells_adapters_and_base_are_the_hand_count():
    """Rank 16 on six KDA layers' q, k, v, o and two MLA layers' q,
    kv-down, kv-up, o: 3,303,424 a node; the base 2,092.5M (103.2 + 5 x
    273.7 + 2 x 263.3 + 94.4)."""
    z, r = KWARGS, 16
    d, H, K = z["hidden"], z["heads"], z["head_dim"]
    N, R, Dv, C = z["nope"], z["rope"], z["v_dim"], z["kv_rank"]
    kda = r * 4 * (d + H * K)
    mla = r * ((d + H * (N + R)) + (d + C + R) + (C + H * (N + Dv))
               + (H * Dv + d))
    assert 6 * kda + 2 * mla == 3_303_424
    model = get_model("kimi-linear-48b-a3b", **z)
    base = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    count = lambda t: sum(int(np.prod(l.shape)) for l in jax.tree.leaves(t))
    # 4.19 GB in bfloat16, 26.2% of the chip
    assert count(base) == 2_092_574_656
    assert count(base["layer_1"]) == 103_223_968
    assert count(base["layer_2"]) == 273_683_360
    assert count(base["layer_4"]) == 263_279_872
    assert count(base["layer_2"]["kda"]) == 39_518_368
    assert count(base["layer_4"]["mla"]) == 29_114_880


def test_layer_lists_are_checked():
    for lists in (dict(kda_layers=[1, 2], full_attn_layers=[4]),
                  dict(kda_layers=[1, 2, 3, 4], full_attn_layers=[4])):
        with pytest.raises(ValueError, match="published layer"):
            get_model("kimi-linear-48b-a3b", **{**TOY, **lists}).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def scenario_dict(**over):
    return {
        "name": "kimi-toy", "seed": 3, "n_nodes": 2, "federation": "DFL",
        "topology": "fully", "aggregator": "fedavg",
        "protocol": {"train_set_size": 0}, "wire_dtype": "bf16",
        "data": {"dataset": "tokens-96-40", "batch_size": 1,
                 "val_percent": 0.0, "synthetic_train": 4,
                 "synthetic_test": 3, "seed": 3},
        "model": {"model": "kimi-linear-48b-a3b", "objective": "next_token",
                  "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
                  "kwargs": {**TOY, "layers": 2}},
        # two KDA layers: MLA's default targets would match nothing
        "lora": {"rank": 4, "alpha": 8.0,
                 "targets": ["kda_q", "kda_k", "kda_v", "kda_o"]},
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
                       if "Train/loss" in r]).reshape(2, 2)
    # every node's training loss falls from the first round to the second
    assert np.isfinite(losses).all() and (losses[1] < losses[0]).all()
    assert np.isfinite(sc.evaluate()["per_node_loss"]).all()
    counted = obs_trace.counted()
    assert not counted["moe.dropped_pairs"]["sum"].any()
    # two KDA layers' shares, each step
    deep = counted["kda.deep_decay_share"]
    assert deep["sum"].shape == (2,) and deep["steps"] == 4
    assert 0.0 < float(deep["max"].max()) <= 100.0
    # q, k, v, o of two KDA layers, A and B each
    assert len(jax.tree.leaves(sc.fed.states.params)) == 2 * 4 * 2
    sc.close()
