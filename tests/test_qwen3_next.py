"""Qwen3-Next-80B-A3B at toy widths on the CPU: the program's modules
(``models/qwen3_next.py`` over the parts it shares with
``models/ling.py``: the chunk-wise delta rule in its form for a decay a
head, with fewer key heads than value heads; gated attention through
``causal_attention``; the sorted grouped expert layer with a gated shared
expert) against the plain reference
(``benchmark/reference/qwen3_next.py``: the recurrence, attention a block
of queries at a time, a loop over experts), which imports nothing of the
program. float32 compute here, so that a wrong term shows and rounding
does not. And the models that share those parts: Ling's and Kimi
Linear's programs compute what they computed before, to the bit."""

import hashlib
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2pfl_tpu.learning.lora import LoraModel, wrap_model
from p2pfl_tpu.models import get_model, ling, qwen3_next

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIG = json.loads(
    (ROOT / "benchmark" / "configs" / "qwen3-next-80b-a3b.json").read_text())
KWARGS = CONFIG["scenario"]["model"]["kwargs"]
# the rehearsal's toy widths and chunk of 16 positions, over published
# layers 1 to 4: GDN, GDN, attention, GDN, each over experts
TOY = {**KWARGS, **CONFIG["rehearse"]["scenario"]["model"]["kwargs"],
       "layers": 4}
LORA = {"rank": 4, "alpha": 8.0}
F32 = jnp.float32


def ref_name(p):
    """The program's path of a leaf by the reference's naming rule
    (``params/layer_3/attn/q_proj/kernel/A`` -> ``L3.q_proj.A``,
    ``params/layer_1/input_norm/scale`` -> ``L1.input_norm``), as the
    configuration file's ``param_map`` spells out for the cell's layers."""
    keys = [k for k in "/".join(
        str(getattr(k, "key", k)) for k in p).split("/")
        if k not in ("params", "kernel", "gdn", "attn", "moe", "scale",
                     "embedding")]
    if keys[0].startswith("layer_"):
        keys[0] = "L" + keys[0][len("layer_"):]
    return ".".join(keys)


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "bench_ref_qwen3_next", ROOT / "benchmark" / "reference" / "qwen3_next.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def toy_model(ref, **over):
    """The toy model, its adapter wrapper, and the same weights by the
    reference's names (adapters from the reference's own ``init``)."""
    sizes = {**TOY, **over}
    ref.configure(sizes, LORA)
    model = get_model("qwen3-next-80b-a3b", dtype=F32, **sizes)
    x = jnp.zeros((1, 24), jnp.int32)
    lm = wrap_model(model, "qwen3-next-80b-a3b", LORA["rank"],
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
    model = get_model("qwen3-next-80b-a3b", **{**TOY, "layers": 8})
    base = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))
    assert {"/".join(str(k.key) for k in p) for p, _ in
            jax.tree_util.tree_flatten_with_path(base)[0]} == set(
        CONFIG["frozen"]["param_map"])


# --------------------------------------------------------------------------
# the delta rule with a decay a value head


def gdn_inputs(T, B=2, Hk=2, H=4, K=16, seed=0, deepest=40.0):
    """Unit keys, scaled unit queries on ``Hk`` key heads, values on
    ``H`` value heads, and one decay a value head and position anywhere
    down to ``-deepest``: 16 steps of them pass the bounded form's clip
    at 80, a chunk of them what ``exp`` holds in float32."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, Hk, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, Hk, K)))
    v = jax.random.normal(ks[2], (B, T, H, K))
    g = -deepest * jax.random.uniform(ks[3], (B, T, H))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


def by_value_head(q, k, v, g, beta):
    """The same inputs for the per-channel form: keys and queries
    repeated to the value heads (head ``h`` reads ``h // group``), the
    decay broadcast over the channels."""
    group = v.shape[2] // q.shape[2]
    rep = lambda a: jnp.repeat(a, group, axis=2)
    return rep(q), rep(k), v, jnp.broadcast_to(g[..., None], v.shape[:3] + (
        q.shape[-1],)), beta


@pytest.mark.parametrize("T", [100, 7])
def test_scalar_form_is_the_recurrence_and_the_channel_form(ref, T):
    """A decay with no channel axis takes the scalar form: the recurrence
    and ``kda_heads``' per-channel form (with the decay broadcast over the
    channels and the keys repeated) for decays down to -40 a step, at
    lengths that are no multiple of the chunk, value and every gradient,
    all finite; through the bounded form (Ling's clip) the same inputs
    are wrong. The trace-time record says which form each call took."""
    args = gdn_inputs(T)
    weigh = jax.random.normal(jax.random.PRNGKey(9), (2, T, 4, 16))
    wide = lambda f: lambda *a: f(*by_value_head(*a))
    forms = {
        "scalar": lambda *a: ling.kda_heads(*a, dtype=F32, scope="t.s"),
        "channel": wide(lambda *a: ling.kda_heads(*a, dtype=F32,
                                                  scope="t.c")),
        "recurrence": lambda q, k, v, g, beta: ref.gdn_recurrence(
            *by_value_head(q, k, v, g, beta)[:3], g, beta)}
    want = forms["recurrence"](*args)
    grads = {name: jax.grad(lambda *a: jnp.sum(f(*a) * weigh),
                            argnums=(0, 1, 2, 3, 4))(*args)
             for name, f in forms.items()}
    for name in ("scalar", "channel"):
        got = forms[name](*args)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        for a, b in zip(grads[name], grads["recurrence"]):
            assert np.isfinite(np.asarray(a)).all()
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
    traced = ling.chunk_forms()
    assert set(traced["t.s"]["forms"]) == {"scalar"}
    assert traced["t.s"]["diag"] is None and traced["t.s"]["chunk"] == 64
    assert set(traced["t.c"]["forms"]) == {"channel"}
    assert traced["t.c"]["diag"] == "xla"
    bounded = wide(lambda *a: ling.kda_heads(*a, dtype=F32, floor=-5.0))(*args)
    assert float(jnp.max(jnp.abs(bounded - want))) > 0.1 * float(
        jnp.max(jnp.abs(want)))


def test_value_head_reads_key_head_h_over_the_group():
    """Four value heads over two key heads: moving key head 0 moves value
    heads 0 and 1 and leaves 2 and 3 as they were (``repeat_interleave``,
    as the published layer repeats them)."""
    q, k, v, g, beta = gdn_inputs(70)
    o = ling.kda_heads(q, k, v, g, beta, dtype=F32)
    moved = k.at[:, :, 0].set(k[:, :, 0][:, :, ::-1])
    o2 = ling.kda_heads(q, moved, v, g, beta, dtype=F32)
    changed = np.asarray(jnp.max(jnp.abs(o2 - o), axis=(0, 1, 3)))
    assert (changed[:2] > 1e-3).all() and (changed[2:] == 0).all()


def test_scalar_form_forms_no_sub_chunk_factors(monkeypatch):
    """The scalar form neither calls the pairwise diagonal of the
    per-channel form nor forms any ``[.., sub, sub, K]`` array: its
    largest intermediate a chunk is ``[C, C]`` a value head."""
    def refuse(*a, **kw):
        raise AssertionError("the per-channel diagonal was called")

    monkeypatch.setattr(ling, "_diag_pairs", refuse)
    args = gdn_inputs(130, K=32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(ling.kda_heads(
        *a, dtype=F32)), argnums=(0, 1, 2, 3, 4)))(*args)
    shapes = {tuple(v.aval.shape) for e in jaxpr.eqns for v in e.outvars}
    assert not [s for s in shapes if s[-3:] == (16, 16, 32)], shapes


# --------------------------------------------------------------------------
# gated attention, N0, the gated shared expert, the expert layer's shares


def test_zero_centred_norm_is_one_plus_its_weight():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 7, 32))
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (32,))
    rms = jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    close(qwen3_next.zero_centred_norm(x, w, 1e-6), x / rms * (1 + w), 1e-6)
    norm = qwen3_next.ZeroCentredRMSNorm(dtype=F32)
    p = norm.init(jax.random.PRNGKey(2), x)
    # seeded, not 0: a norm that forgot its ``1 +`` cannot agree
    assert float(jnp.min(jnp.abs(p["params"]["scale"]))) > 0
    close(norm.apply(p, x), x / rms * (1 + p["params"]["scale"]), 1e-6)


@pytest.mark.parametrize("kind", ["gdn", "attn", "experts"])
def test_each_layer_against_the_reference(ref, kind):
    """Gated DeltaNet (two key heads, each read by two value heads, a
    block of one key head at a time), gated attention (4 query heads of
    16 over 2, rotary over the first 4 dimensions, the N0 weights the
    seed's, not 0) and the expert layer with its gated shared expert."""
    lm, _, _, frozen = toy_model(ref)
    base = lm.base["params"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, TOY["hidden"]))
    dense = ref.make_dense({}, frozen, lambda a: a)
    same = lambda a: a
    if kind == "gdn":
        got = qwen3_next.GatedDeltaNet(
            TOY["key_heads"], TOY["value_heads"], TOY["linear_head_dim"],
            head_block=1, dtype=F32).apply({"params": base["layer_2"]["gdn"]}, x)
        *inputs, gate = ref.gdn_inputs(dense, frozen, "L2.", x)
        want = ref.gdn_output(dense, frozen, "L2.",
                              ref.gdn_recurrence(*inputs), gate)
    elif kind == "attn":
        assert float(jnp.min(jnp.abs(base["layer_3"]["attn"]["q_norm"]))) > 0
        got = qwen3_next.GatedAttention(
            TOY["heads"], TOY["kv_heads"], TOY["head_dim"],
            tuple(qwen3_next.rope_inv_freq(4, TOY["theta"])),
            dtype=F32).apply({"params": base["layer_3"]["attn"]}, x)
        want = ref.gated_attention(dense, frozen, "L3.", x, same)
    else:
        got, stats = expert_layer(TOY).apply(
            {"params": base["layer_2"]["moe"]}, x)
        want = ref.expert_ffn(dense, frozen, "L2.", x, same)
        assert float(stats[0]) == 0.0
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def expert_layer(sizes, offset=0, held=None, shared=None, gate=True):
    return ling.ExpertFFN(
        sizes["n_experts"], held or sizes["experts_held"], offset,
        sizes["expert_width"], sizes["shared_width"] if shared is None
        else shared, sizes["top_k"],
        router=lambda x, fr: qwen3_next.route_softmax(
            x, fr, top_k=sizes["top_k"], scale=1.0),
        shared_gate=gate, dtype=F32)


def test_the_shared_experts_gate_is_a_sigmoid_of_its_own_projection(ref):
    """With the gate the shared expert's output is scaled by ``sigmoid(x
    w_sg)``; without it (every other model) the layer makes no such
    parameter and adds the shared expert unweighted."""
    sizes = {**TOY, "n_experts": 16, "experts_held": 4}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, TOY["hidden"]))
    gated = expert_layer(sizes)
    p = gated.init(jax.random.PRNGKey(2), x)["params"]
    assert p["shared_gate"]["kernel"].shape == (TOY["hidden"], 1)
    plain = expert_layer(sizes, gate=False)
    unweighted = {k: v for k, v in p.items() if k != "shared_gate"}
    assert set(plain.init(jax.random.PRNGKey(2), x)["params"]) == set(
        unweighted)
    with_gate, without = (gated.apply({"params": p}, x)[0],
                          plain.apply({"params": unweighted}, x)[0])
    w = p["shared_gate"]["kernel"]
    shared = without - expert_layer(sizes, shared=0, gate=False).apply(
        {"params": {k: v for k, v in unweighted.items()
                    if not k.startswith("shared")}}, x)[0]
    close(with_gate, without - shared + jax.nn.sigmoid(x @ w) * shared, 1e-5)


def test_eight_shares_of_the_experts_add_up_to_the_uncut_layer(ref):
    """64 of 512 held, as the cell holds them (8 of 64 here): the parts
    that the 8 offsets' chips compute, with the gated shared expert
    counted once, add up to the reference's whole layer (every expert
    held); no pair dropped."""
    sizes = {**TOY, "n_experts": 64, "experts_held": 8, "top_k": 10}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, TOY["hidden"]))
    whole = expert_layer(sizes, held=64)
    params = whole.init(jax.random.PRNGKey(2), x)["params"]
    ref.configure({**sizes, "experts_held": 64}, LORA)
    frozen = {"L." + k: v for k, v in params.items()
              if not isinstance(v, dict)}
    frozen.update({"L." + k: v["kernel"] for k, v in params.items()
                   if isinstance(v, dict)})
    want = ref.expert_ffn(ref.make_dense({}, frozen, lambda a: a), frozen,
                          "L.", x, lambda a: a)
    parts = []
    for c in range(8):
        cut = slice(8 * c, 8 * c + 8)
        mine = {**params, "experts_gate_up": params["experts_gate_up"][cut],
                "experts_down": params["experts_down"][cut]}
        if c:  # the shared expert and its gate are every chip's: counted once
            mine = {k: v for k, v in mine.items() if not k.startswith("shared")}
        y, stats = expert_layer(sizes, 8 * c, 8, None if not c else 0,
                                gate=not c).apply({"params": mine}, x)
        assert float(stats[0]) == 0.0
        parts.append(y)
    close(sum(parts), want, 2e-5)
    close(whole.apply({"params": params}, x)[0], want, 2e-5)


# --------------------------------------------------------------------------
# the whole model


def test_whole_model_loss_and_adapter_gradients(ref):
    lm, adapters, seeded, frozen = toy_model(ref)
    # 40 positions are three chunks of 16: the state crosses chunks
    assert TOY["chunk"] < 40
    x, y = tokens(1, (2, 40)), tokens(2, (2, 40))
    mask = jnp.array([True, True])
    loss = lambda a: lm.apply(a, x, y, mask, method="loss")
    plain = lambda p: ref.loss(ref.forward(p, x, frozen=frozen), y, mask)
    (l_got, counters), got = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        adapters)
    l_want, want = jax.jit(jax.value_and_grad(plain))(seeded)
    np.testing.assert_allclose(l_got, l_want, rtol=1e-5)
    # three GDN layers of 2 sites and one attention layer of 4
    assert len(want) == 2 * (3 * 2 + 4)
    for p, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        w = want[ref_name(p)]
        assert float(jnp.linalg.norm(g - w)) <= 2e-3 * float(
            jnp.linalg.norm(w)), ref_name(p)
    np.testing.assert_allclose(lm.apply(adapters, x),
                               ref.forward(seeded, x, frozen=frozen),
                               rtol=2e-4, atol=2e-4)
    assert counters["moe.dropped_pairs"].shape == (4,)
    assert not np.asarray(counters["moe.dropped_pairs"]).any()
    # every GDN layer took the scalar form, none the per-channel one
    assert set(ling.chunk_forms()["gdn.scan"]["forms"]) == {"scalar"}


def test_adapters_ride_on_the_mixers_published_projections_only():
    model = get_model("qwen3-next-80b-a3b", dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16, **TOY)
    lm = wrap_model(model, "qwen3-next-80b-a3b", 4,
                    sample_x=jnp.zeros((1, 24), jnp.int32))
    assert isinstance(lm, LoraModel)
    assert {l.dtype for l in jax.tree.leaves(lm.base)} == {jnp.dtype("bfloat16")}
    sites = {s.key for s in lm.sites}
    assert sites == {f"params/layer_{l}/gdn/{n}/kernel" for l in (1, 2, 4)
                     for n in ("in_proj_qkvz", "out_proj")} | {
        f"params/layer_3/attn/{n}_proj/kernel" for n in "qkvo"}


def test_the_cells_adapters_and_base_are_the_hand_count():
    """Rank 16 on six GDN layers' in_proj_qkvz and out_proj and two
    attention layers' q (query and gate), k, v, o: 2,654,208 a node; the
    base 1.98B (6 x 239.2M + 2 x 232.8M + 77.8M), 3.96 GB in bfloat16."""
    z, r = KWARGS, 16
    d = z["hidden"]
    qkvz = 2 * (z["key_heads"] + z["value_heads"]) * z["linear_head_dim"]
    HK, AD = z["value_heads"] * z["linear_head_dim"], z["heads"] * z["head_dim"]
    gdn = r * ((d + qkvz) + (HK + d))
    attn = r * ((d + 2 * AD) + 2 * (d + z["kv_heads"] * z["head_dim"])
                + (AD + d))
    assert 6 * gdn + 2 * attn == 2_654_208
    model = get_model("qwen3-next-80b-a3b", **z)
    base = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    count = lambda t: sum(int(np.prod(l.shape)) for l in jax.tree.leaves(t))
    experts = 64 * 3 * d * 512
    assert count(base["layer_1"]["moe"]) == experts + d * 512 + 3 * d * 512 + d
    assert count(base["layer_1"]) == 239_245_504
    assert count(base["layer_3"]) == 232_790_528
    assert count(base) == 1_978_847_360
    assert 3.9e9 < 2 * count(base) < 4.0e9


def scenario_dict(**over):
    return {
        "name": "qwen3-next-toy", "seed": 3, "n_nodes": 2, "federation": "DFL",
        "topology": "fully", "aggregator": "fedavg",
        "protocol": {"train_set_size": 0}, "wire_dtype": "bf16",
        "data": {"dataset": "tokens-96-40", "batch_size": 1,
                 "val_percent": 0.0, "synthetic_train": 4,
                 "synthetic_test": 3, "seed": 3},
        "model": {"model": "qwen3-next-80b-a3b", "objective": "next_token",
                  "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
                  "kwargs": TOY},
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
                       if "Train/loss" in r]).reshape(2, 2)
    # every node's training loss falls from the first round to the second
    assert np.isfinite(losses).all() and (losses[1] < losses[0]).all()
    assert np.isfinite(sc.evaluate()["per_node_loss"]).all()
    assert not obs_trace.counted()["moe.dropped_pairs"]["sum"].any()
    # in_proj_qkvz and out_proj of three GDN layers, q, k, v, o of one
    # attention layer, A and B each
    assert len(jax.tree.leaves(sc.fed.states.params)) == 2 * (3 * 2 + 4)
    sc.close()


# --------------------------------------------------------------------------
# the models that share the delta rule, unchanged to the bit


def kda_digest():
    """Kimi Linear's form of the delta rule (a decay a channel, no floor),
    output and every gradient, float32 and bfloat16 products, at two
    lengths."""
    h = hashlib.sha256()
    for T in (100, 7):
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
        q = unit(jax.random.normal(ks[0], (2, T, 2, 16))) * 16 ** -0.5
        k = unit(jax.random.normal(ks[1], (2, T, 2, 16)))
        v = jax.random.normal(ks[2], (2, T, 2, 16))
        g = -40.0 * jax.random.uniform(ks[3], (2, T, 2, 16))
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, T, 2)))
        w = jax.random.normal(jax.random.PRNGKey(9), (2, T, 2, 16))
        for dt in (F32, jnp.bfloat16):
            run = lambda *a: ling.kda_chunked(*a, dtype=dt)
            h.update(np.asarray(run(q, k, v, g, beta)).tobytes())
            for a in jax.grad(lambda *a: jnp.sum(run(*a) * w),
                              argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta):
                h.update(np.asarray(a).tobytes())
    return h.hexdigest()


def model_digest(name):
    """A cell's model at its rehearsal widths in bfloat16 under rank-4
    adapters: the loss, the counters and every adapter gradient."""
    cfg = json.loads((ROOT / "benchmark" / "configs" / f"{name}.json")
                     .read_text())
    kw = {**cfg["scenario"]["model"]["kwargs"],
          **cfg["rehearse"]["scenario"]["model"]["kwargs"]}
    model = get_model(name, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, **kw)
    x, y = tokens(1, (2, 40), kw["vocab"]), tokens(2, (2, 40), kw["vocab"])
    lm = wrap_model(model, name, 4, alpha=8.0, sample_x=x[:1], seed=3)
    a = lm.init(jax.random.PRNGKey(0), x[:1])
    (l, counters), g = jax.jit(jax.value_and_grad(
        lambda a: lm.apply(a, x, y, jnp.array([True, True]), method="loss"),
        has_aux=True))(a)
    h = hashlib.sha256()
    for leaf in [l, *jax.tree.leaves(counters), *jax.tree.leaves(g)]:
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("what, digest", [
    ("kda", "46b168a82cc8d1c52366b19b29f758fc10613d40dc8962a1c94374823ef2da82"),
    ("ling-3.0-flash",
     "3f758acb8e7532150e2b3c193d2b2c0dd162c2a91c960e1eb159c2c79f69b344"),
    ("kimi-linear-48b-a3b",
     "5f75f8a942bd6611029e8d53f1fe5faaa6ba97f1560ddc7e738037f47864e2c0"),
])
def test_lings_and_kimis_programs_are_the_parents_to_the_bit(what, digest):
    """The scalar form, the solve's scope, ``ExpertFFN``'s gate and the
    final norm's hook leave Ling's and Kimi Linear's programs as they
    were: the digests are the parent commit's (default precision)."""
    with jax.default_matmul_precision("default"):
        got = kda_digest() if what == "kda" else model_digest(what)
    assert got == digest
