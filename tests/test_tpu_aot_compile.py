"""Compile-only guards for the TPU, run without one: libtpu is installed,
so XLA:TPU compiles here for a v5e that is described and not attached
(.claude/skills/verify/SKILL.md, "No chip needed to find compile
errors"). Nothing executes and nothing is timed. Skipped, not failed,
where the topology cannot be described. Keep such tests in this one file:
only one process at a time may load the TPU's library."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from p2pfl_tpu.models import get_model


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the runtime raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip: off around it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_femnist_cnn_step_has_no_conv1_sized_relayout(one_chip,
                                                      no_persistent_cache):
    """The north-star cell's own shapes (64 nodes, batch 336, bfloat16):
    one vmapped value-and-grad of femnist-cnn. Before PR 33 the optimized
    HLO held six copies and reshapes of conv1's 25-wide im2col patches
    and of its [64,336,28,28,32] output, 89 ms of every 206 ms round on
    the v5e; a later jax or edit must not bring them back unseen."""
    n, batch = 64, 336
    model = get_model("femnist-cnn", param_dtype=jnp.bfloat16)
    params = jax.eval_shape(jax.vmap(
        lambda key: model.init(key, jnp.zeros((1, 28, 28, 1)))),
        jax.random.split(jax.random.PRNGKey(0), n))

    def loss(p, x, y):
        logits = model.apply(p, x)
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), y[:, None], axis=1))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    hlo = jax.jit(jax.vmap(jax.value_and_grad(loss))).lower(
        on_chip(params),
        jax.ShapeDtypeStruct((n, batch, 28, 28, 1), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((n, batch), jnp.int32, sharding=one_chip),
    ).compile().as_text()

    conv1_elements = n * batch * 28 * 28 * 32
    relaid = []
    for m in re.finditer(
            r"= \w+\[([\d,]+)\]\S* (?:copy|reshape|transpose)\(", hlo):
        dims = [int(d) for d in m.group(1).split(",")]
        elements = 1
        for d in dims:
            elements *= d
        if 25 in dims or elements >= conv1_elements:
            relaid.append(m.group(0))
    assert "convolution" in hlo  # the text is the optimized module
    assert not relaid, relaid


def test_ling_expert_layer_is_one_grouped_product_over_all_nodes(
        one_chip, no_persistent_cache):
    """The Ling cell's expert layer at its own shapes (8 nodes x 4096
    tokens, hidden 2560, 64 held experts of width 768, 512 routed over,
    8 a token), vmapped over the nodes as the round does: XLA:TPU's own
    grouped matmul (a ``ragged-dot`` custom call, not a dense expansion
    over the experts), as many of them as ONE node's call has, each over
    the rows of all nodes; forward and the way back."""
    from p2pfl_tpu.models import ling

    n, T, d, held, width = 8, 4096, 2560, 64, 768
    import functools

    layer = ling._frozen_experts(
        router=functools.partial(ling.route, n_group=8, topk_group=4, top_k=8,
                                 scale=2.5), offset=0, dtype=jnp.bfloat16)
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                               sharding=one_chip)
    frozen = {"router": bf16(d, 512), "bias": bf16(512),
              "gate_up": bf16(held, d, 2 * width), "down": bf16(held, width, d)}

    def step(x, g, frozen):
        y, back = jax.vjp(lambda x_: jax.vmap(
            lambda xb: layer(xb, frozen)[0])(x_), x)
        return y, back(g)[0]

    compiled = jax.jit(step).lower(bf16(n, T, d), bf16(n, T, d),
                                   frozen).compile()
    hlo = compiled.as_text()
    rows = 2 * n * T  # a block: twice the even share of the 8 x 4096 x 8 pairs
    grouped = re.findall(r"ragged-dot\S* = \w+\[(\d+),(\d+)\]\S* custom-call",
                         hlo)
    # gate-up and down on the way forward; on the way back gate-up again
    # and the two input gradients: the down-projection is formed once
    assert sorted(grouped) == sorted(
        [(str(rows), str(2 * width)), (str(rows), str(d))] * 2
        + [(str(rows), str(width))]), grouped
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9


def delta_rule_shapes(one_chip, weighed=False):
    """The Ling cell's delta rule as the round runs it: 8 nodes under
    ``vmap``, a sequence of 4096, a block of 4 heads of 128, float32 in:
    q, k, v, g, beta, and the weights of a weighed sum of the output."""
    n, T, H, K = 8, 4096, 4, 128
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    return [f32(n, 1, T, H, K)] * 4 + [f32(n, 1, T, H)] + [
        f32(n, 1, T, H, K)] * weighed


def test_ling_delta_rule_holds_no_triangular_solve(one_chip,
                                                   no_persistent_cache):
    """The Ling cell's delta rule at its own shapes as the round runs it
    (bfloat16 products), value and gradient. XLA:TPU's
    ``triangular_solve`` (a custom call that inverts the diagonal blocks,
    3.5 ms an execution on the v5e at these shapes and a seventh of the
    cell's round, PERF.md Findings PR 36) is not in the program; the
    solve's products on the matrix unit carry ``kda.solve``, the scope its
    device time is read by, and so do the inverse's block products. Since
    PR 40 the two right-hand sides go apart: two products forward (on all
    nodes' chunks at once, the chunk axis leading: ``[64, 8, 4, 64,
    128]``), the same two again where the way back recomputes the block,
    and four back (``T^T g`` for each side, ``d X^T`` for each into
    ``A``'s gradient), none 256 wide (three of ``[.., 64, 256]`` and
    ``[.., 64, 64]`` until then). The way back keeps the nodes' ``vmap``
    (``head_blocks``), so the program reads 18.4e9 bytes: 3.7e9 the
    forward pass with the nodes as its batch and 14.7e9 the block again
    and back (14.578e9 at the parent, which kept a single block's
    residuals and ran it once); and it needs less temporary memory than
    it did with the custom call."""
    from p2pfl_tpu.models import ling

    def loss(q, k, v, g, beta, weigh):
        return jnp.sum(weigh * ling.kda_chunked(q, k, v, g, beta,
                                                dtype=jnp.bfloat16,
                                                floor=-5.0))

    compiled = jax.jit(jax.vmap(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4)))).lower(
        *delta_rule_shapes(one_chip, weighed=True)).compile()
    hlo = compiled.as_text()
    assert "convolution" in hlo  # the text is the optimized module
    assert not [target for target in re.findall(
        r'custom_call_target="([^"]+)"', hlo) if "riangular" in target]
    scoped = re.findall(
        r"= f32\[([\d,]+)\]\S* (\w+)\(.*op_name=\"([^\"]*kda\.solve[^\"]*)\"",
        hlo)
    back = lambda name: "transpose(" in name
    products = [(dims.split(","), back(name)) for dims, op, name in scoped
                if op == "convolution"]
    assert (["64", "8", "4", "64", "128"], False) in products, products
    assert all(dims[-2] == "64" and dims[-1] in ("64", "128")
               for dims, _ in products), products
    # the inverse's block products: multiply and sum, on the way forward
    assert any(op == "reduce" and not back(name) for _, op, name in scoped)
    # they are the program's products of float32 operands in six passes
    # (the others take bfloat16 operands): 2 forward, 2 again, 4 back
    highest = re.findall(r"operand_precision=\{highest,highest\}.*"
                         r"op_name=\"([^\"]*)\"", hlo)
    assert len(highest) == 8 and all(
        "kda.solve" in name for name in highest), highest
    assert compiled.cost_analysis()["bytes accessed"] <= 18.8e9
    # 1,662,749,184 with the custom call (the same program at 24068e8),
    # 1,609 MB at the parent of PR 40, 1,310 MB since
    assert compiled.memory_analysis().temp_size_in_bytes <= 1_400_000_000


def test_ling_delta_rule_forward_stages_its_operands_once(
        one_chip, no_persistent_cache):
    """A forward pass of the delta rule as the model calls it from the
    round's ``vmap`` over 8 nodes (two of a step's three passes and all of
    an evaluation's): the nodes are its batch and the chunk axis leads
    from ``chunks()`` to the scan, so every staged operand is ``[64, 8, 4,
    64, 128]``; none is laid out batch-first (``[8, 64, 4, 64, 128]``: the
    parent's form, eight float32 copies of ``[8, 1, 64, 64, 4, 128]``
    between its stages) or with the nodes behind the chunks (``[64, 8, 1,
    4, 64, 128]``: what a ``vmap`` of the scan makes); and the program
    reads at most 3.8e9 bytes (``cost_analysis()``; 3.70e9 now, 4.395e9
    at the parent, 5.47 ms at the HBM's rate for 5.90 ms measured an
    execution there: ``PERF.md`` section 7, the yardstick)."""
    from p2pfl_tpu.models import ling

    compiled = jax.jit(jax.vmap(lambda *rows: ling.kda_chunked(
        *rows, dtype=jnp.bfloat16, floor=-5.0))).lower(
        *delta_rule_shapes(one_chip)).compile()
    hlo = compiled.as_text()
    assert "convolution" in hlo  # the text is the optimized module
    assert "f32[64,8,4,64,128]" in hlo
    for staged in ("f32[8,64,4,64,128]", "f32[8,1,64,4,64,128]",
                   "f32[64,8,1,4,64,128]", "f32[8,1,64,64,4,128]"):
        assert staged not in hlo, staged
    assert compiled.cost_analysis()["bytes accessed"] <= 3.8e9


def fused_attention(hlo, scope):
    """What an optimized module holds of ``causal_attention`` on the TPU
    (``ops/flash_attention.py``): the fused kernel's forward, its
    queries' way back and its keys' and values' way back, each one
    ``tpu_custom_call`` whose op name bears ``scope``, the two way back
    under ``transpose(..)``; no tiled loop; no float32 array whose last
    two axes are a block of score rows by a tile of keys (a tile's
    scores stay in VMEM); and every named device op under ``scope``."""
    calls = re.findall(r"%(flash_attention_\w+?)(?:\.\d+)? = .*"
                       r"custom_call_target=\"tpu_custom_call\".*?"
                       r"op_name=\"([^\"]*)\"", hlo)
    assert sorted(name for name, _ in calls) == [
        "flash_attention_dkv", "flash_attention_dq", "flash_attention_fwd"]
    for name, op in calls:
        assert scope in op and ("transpose(" in op) == (
            name != "flash_attention_fwd"), (name, op)
    assert not re.findall(r" while\(", hlo)
    tiles = [m for m in re.findall(r"f32\[([\d,]+)\]", hlo)
             if min(map(int, m.split(",")[-2:])) >= 256]
    assert not tiles, tiles
    named = [name for name in re.findall(
        r"= \w+\[\d[\d,]*\]\S* [\w-]+\(.*op_name=\"([^\"]*)\"", hlo)
        if "/" in name]
    assert named and all(scope in name for name in named), [
        name for name in named if scope not in name]


def test_ling_attention_forms_no_tile_above_the_diagonal(one_chip,
                                                         no_persistent_cache,
                                                         monkeypatch):
    """The Ling cell's latent attention at its own shapes as the round
    runs it (8 nodes under ``vmap``, a sequence of 4096, 32 heads of 192
    and 128, bfloat16), value and gradient under the scope its device
    time is read by. Until PR 38 a block of 256 queries met all 4096
    keys under the mask (92 mentions of ``f32[8,32,256,4096]`` in the
    optimized module, a sixth of the cell's round on the v5e); from PR
    38 to 43 six loops formed 256 x 256 score tiles in HBM; since PR 44
    the TPU runs the fused kernel (``fused_attention``), blocks and tiles
    of 1024 (10 of 16 pairs), and the step needs no more temporary memory
    than the square did."""
    from p2pfl_tpu.models import ling

    monkeypatch.setattr(ling, "on_tpu", lambda: True)  # the chip's path
    n, T, H, D, Dv = 8, 4096, 32, 192, 128
    shaped = lambda dtype, width: jax.ShapeDtypeStruct(
        (n, 1, T, H, width), dtype, sharding=one_chip)

    def loss(q, k, v, weigh):
        with jax.named_scope("mla.attn"):
            return jnp.sum(weigh * ling.causal_attention(q, k, v, D ** -0.5))

    compiled = jax.jit(jax.vmap(jax.value_and_grad(
        loss, argnums=(0, 1, 2)))).lower(
        shaped(jnp.bfloat16, D), shaped(jnp.bfloat16, D),
        shaped(jnp.bfloat16, Dv), shaped(jnp.float32, Dv)).compile()
    fused_attention(compiled.as_text(), "mla.attn")
    assert ling.score_tiles("mla.attn") == {
        "computed": 10, "square": 16, "block": 1024, "tile": 1024,
        "kernel": True}
    # 3,399,016,448 with the square (the same program at b4d8916); the
    # tiled form's 1,611,160,064 read the arguments in place, where the
    # kernel takes them heads first
    assert compiled.memory_analysis().temp_size_in_bytes <= 3_399_016_448


@pytest.mark.parametrize(
    "n, T, heads, D, window, scope, pairs, sizes, temp", [
        (4, 8192, 72, 128, 512, "swa.attn", (31, 256), (512, 512), 4e9),
        (4, 8192, 48, 128, None, "gqa.attn", (136, 256), (512, 512), 4e9),
        (16, 2048, 32, 64, None, "gqa.attn", (10, 16), (512, 512), 1e9)],
    ids=["laguna-window", "laguna-full", "lfm2"])
def test_laguna_attention_forms_only_the_tiles_a_block_sees(
        one_chip, no_persistent_cache, monkeypatch, n, T, heads, D, window,
        scope, pairs, sizes, temp):
    """The grouped-query attention of the Laguna cell's two kinds of
    layer (4 nodes under ``vmap``, a sequence of 8192, 72 or 48 query
    heads of 128 over 8 key heads) and of the LFM2 cell (16 nodes, 2048,
    32 heads of 64 over 8), bfloat16, value and gradient. Since PR 44 the
    TPU runs the fused kernel (``fused_attention``): a window layer's
    blocks of 512 meet 31 of the 256 pairs (two tiles of 512 a block: the
    one the window starts in and the diagonal's), a full layer's the
    causal 136 (LFM2's 10 of 16); keys and values keep their
    8 heads (nothing of theirs as large as the queries); the output
    leaves in bfloat16, as the model asks. Until PR 43 the tiled form's
    loops formed ``[nodes, 8, group x 256, 256]`` float32 score tiles in
    HBM (temporaries 1,955,981,312, 1,349,081,600 and 609,400,320 B at
    these shapes: it read the arguments in place, where the kernel takes
    them heads first; in the round programs, whose producers write them
    so, the kernel's temporaries are 1.18 GB fewer (Laguna) and 0.10 GB
    more (LFM2), ``PERF.md`` section 7, "After PR 44")."""
    from p2pfl_tpu.models import ling

    monkeypatch.setattr(ling, "on_tpu", lambda: True)  # the chip's path
    G = 8
    shaped = lambda dtype, h: jax.ShapeDtypeStruct(
        (n, 1, T, h, D), dtype, sharding=one_chip)

    def loss(q, k, v, weigh):
        with jax.named_scope(scope):
            return jnp.sum(weigh * ling.causal_attention(
                q, k, v, D ** -0.5, window=window, scope=scope,
                out_dtype=jnp.bfloat16))

    compiled = jax.jit(jax.vmap(jax.value_and_grad(
        loss, argnums=(0, 1, 2)))).lower(
        shaped(jnp.bfloat16, heads), shaped(jnp.bfloat16, G),
        shaped(jnp.bfloat16, G), shaped(jnp.bfloat16, heads)).compile()
    hlo = compiled.as_text()
    fused_attention(hlo, scope)
    assert ling.score_tiles(scope) == dict(
        zip(("computed", "square", "block", "tile"), pairs + sizes),
        kernel=True)
    assert re.search(rf"%flash_attention_fwd\S* = \(bf16\[{n},1,{heads},{T},"
                     rf"{D}\]", hlo)  # the output in bfloat16
    assert not re.findall(rf"bf16\[{n},(?:1,)?{heads},{T},{D}\]\S* "
                          r"(?:broadcast|concatenate)\(", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < temp


def abstract_cell(name, monkeypatch):
    """The cell's ``Scenario``, built from the cell's own files with the
    frozen base left abstract (nothing of its GBs is made or placed
    here), for a chip whose path the models trace: the patches hold for
    the test's own duration."""
    import importlib.util
    import pathlib

    from p2pfl_tpu.federation import Scenario
    from p2pfl_tpu.learning import lora
    from p2pfl_tpu.models import ling
    from p2pfl_tpu.parallel import transport

    monkeypatch.setattr(ling, "on_tpu", lambda: True)  # the chip's path
    home = pathlib.Path(__file__).resolve().parent.parent / "benchmark"
    monkeypatch.syspath_prepend(str(home))
    spec = importlib.util.spec_from_file_location("benchmark_run_aot",
                                                  home / "run.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    abstract = lambda a: isinstance(a, jax.ShapeDtypeStruct)
    monkeypatch.setattr(lora, "base_params_for", lambda model, seed, x:
                        jax.eval_shape(model.init, jax.random.PRNGKey(seed), x))
    asarray, place, ready = (jnp.asarray, transport.MeshTransport._place,
                             jax.block_until_ready)
    monkeypatch.setattr(jnp, "asarray", lambda a, *args, **kw:
                        a if abstract(a) else asarray(a, *args, **kw))
    monkeypatch.setattr(transport.MeshTransport, "_place", lambda self, x, s:
                        x if abstract(x) else place(self, x, s))
    monkeypatch.setattr(jax, "block_until_ready", lambda tree: tree if any(
        map(abstract, jax.tree.leaves(tree))) else ready(tree))
    return Scenario(bench.Cell(name, False).scenario_config(7))


def on_device(tree, sharding):
    import numpy as np

    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        np.shape(a), a.dtype, sharding=sharding), tree)


def held_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def test_lfm2_cells_round_program_fits_the_chip(one_chip, no_persistent_cache,
                                                monkeypatch):
    """The WHOLE round program of ``lfm2-8b-a1b.dfl16-full-lora-s2048`` at
    the cell's own sizes (published layers 1 to 9 at the published
    widths, 16 nodes, 2 steps of one sequence of 2048, the frozen 6.3 GB
    base an argument), built by ``Scenario`` from the cell's own files
    with the base left abstract, compiles for the described v5e: XLA:TPU
    refuses a program that does not fit the chip's 15.75 GiB. It stood at
    14.16 GiB when the cell came (8.92 GB of temporaries over 6.62 GB of
    arguments). The expert layers' pairs are two blocks of 65,536 rows
    (every one of the 32 experts held, 4 a token), the head's logits are
    never wider than a chunk of 128 positions, and nothing is shared:
    no op bears ``moe.shared``."""
    import numpy as np

    sc = abstract_cell("lfm2-8b-a1b.dfl16-full-lora-s2048", monkeypatch)
    try:
        args = on_device((sc.fed, *sc._data_args, *sc._plan_args(None),
                          *sc._frozen), one_chip)
        base = sum(np.prod(l.shape) * l.dtype.itemsize
                   for l in jax.tree.leaves(sc._frozen))
        assert 6.2e9 < base < 6.4e9  # 3,136M parameters in bfloat16
        compiled = sc._round_fn.lower(*args).compile()
    finally:
        sc.close()
    assert held_bytes(compiled) < 15.75 * 2 ** 30, held_bytes(compiled)
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes > 0.25 * 16e9  # the driver's floor
    hlo = compiled.as_text()
    grouped = re.findall(r"ragged-dot\S* = \w+\[(\d+),(\d+)\]\S* custom-call",
                         hlo)
    assert grouped and {rows for rows, _ in grouped} == {"65536"}, grouped
    assert "f32[16,128,65536]" in hlo and "f32[16,2048,65536]" not in hlo
    assert "lfm2.conv" in hlo and "moe.shared" not in hlo


def unfused(hlo):
    """The shapes of the instructions an optimized module holds outside
    its fusions' computations: the arrays that are written to memory."""
    shapes, inside = [], False
    for line in hlo.splitlines():
        if line and not line.startswith(" "):
            inside = "fused" in line.split("(")[0] or "fusion" in line.split(
                "(")[0]
        elif not inside:
            shapes += re.findall(r"= (\w+\[[\d,]*\])", line)
    return shapes


def test_kimi_cells_programs_fit_the_chip(one_chip, no_persistent_cache,
                                          monkeypatch):
    """The WHOLE round and evaluation programs of
    ``kimi-linear-48b-a3b.dfl2-full-lora-s16384`` at the cell's own sizes
    (published layers 1 to 8 at the published widths, 2 nodes, 2 steps of
    one sequence of 16,384, the frozen 4.19 GB base an argument) compile
    for the described v5e under its 15.75 GiB (the round program at 15.66
    GiB when the cell came: 12.55 GB of temporaries over 4.27 GB of
    arguments). The delta rule's diagonal sub-blocks are formed by the
    Pallas kernels of ``ops/kda_diag.py``, whose custom calls bear the
    scope the device time is read by: ``kda.scan/kda.diag`` on the way
    forward (three a KDA layer and step: the folded forward, the layer's
    recomputation and the head block's) and under ``transpose(jvp(..))``
    on the way back (one a KDA layer), where the hand-written rule sets
    it. The factors of all pairs of a block of heads, ``f32[.., 16, 16,
    128]``, are no array of the program; formed a key at a time they
    were, and the round program did not fit."""
    sc = abstract_cell("kimi-linear-48b-a3b.dfl2-full-lora-s16384",
                       monkeypatch)
    try:
        round_args = on_device((sc.fed, *sc._data_args, *sc._plan_args(None),
                                *sc._frozen), one_chip)
        eval_args = on_device((sc.fed, sc._x_test, sc._y_test, *sc._frozen),
                              one_chip)
        programs = {"round": sc._round_fn.lower(*round_args).compile(),
                    "eval": sc._eval_fn.lower(*eval_args).compile()}
    finally:
        sc.close()
    for name, compiled in programs.items():
        assert held_bytes(compiled) < 15.75 * 2 ** 30, (name,
                                                         held_bytes(compiled))
        # the benchmark's floor: the base alone is over a quarter of the chip
        assert compiled.memory_analysis().argument_size_in_bytes > 0.25 * 16e9
    hlo = programs["round"].as_text()
    calls = {"kda_diag_fwd": [], "kda_diag_bwd": []}
    for kernel, name in re.findall(r'%(kda_diag_fwd|kda_diag_bwd)\S* = .* '
                                   r'custom-call\(.*op_name="([^"]*)"', hlo):
        calls[kernel].append(name)
    assert len(calls["kda_diag_fwd"]) == 18 and len(calls["kda_diag_bwd"]) == 6
    assert all("/kda.scan/kda.diag/" in n for n in calls["kda_diag_fwd"])
    assert all("transpose(jvp(" in n and "/kda.scan/kda.diag/" in n
               for n in calls["kda_diag_bwd"])  # the way back
    whole = [s for s in unfused(hlo) if re.search(r"\[[\d,]*,16,16,128\]", s)]
    assert not whole, whole
    assert "mla.attn" in hlo and "moe.shared" in hlo


def test_kimi_cells_reference_fits_the_chip(one_chip, no_persistent_cache,
                                            monkeypatch):
    """The plain reference's epoch of ``kimi-linear-48b-a3b.dfl2-full-lora-
    s16384`` (``benchmark/reference/kimi_linear.py`` through
    ``reference/federation.py``, the two nodes one after the other, the
    4.19 GB base an argument) compiles for the described v5e: with each
    KDA layer's recurrence inputs kept for the way back (1.3 GB a layer at
    16,384 positions) it needed 18.85 GiB and every run of the cell failed
    there, after its window (``PERF.md`` Findings, Kimi Linear). Each
    layer recomputed whole on the way back left it at 16.41 GiB with the
    base it holds beside its temporaries; with a KDA layer's inputs formed
    once more inside that, 15.21 GiB; 15.30 GiB with attention's queries
    in four groups of key prefixes (17.72 GiB in eight)."""
    import pathlib

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as PS

    home = pathlib.Path(__file__).resolve().parent.parent / "benchmark"
    monkeypatch.syspath_prepend(str(home))
    import importlib.util

    spec = importlib.util.spec_from_file_location("benchmark_run_ref",
                                                  home / "run.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    from reference import federation

    cell = bench.Cell("kimi-linear-48b-a3b.dfl2-full-lora-s16384", False)
    ref = cell.reference_model()
    ref.configure(cell.config["scenario"]["model"]["kwargs"],
                  cell.config["scenario"]["lora"])
    monkeypatch.setattr(federation.jax, "devices",
                        lambda *a: [one_chip.device_set.pop()])
    fed = federation.Federation(ref, cell.reference_spec(), chips=1)
    n, T = 2, 16384
    at = lambda spec_: NamedSharding(fed.mesh, spec_)
    sds = lambda shape, dtype, spec_: jax.ShapeDtypeStruct(
        shape, dtype, sharding=at(spec_))
    P = {k: sds((n,) + v, jnp.float32, PS("d")) for k, v in ref.SHAPES.items()}
    frozen = {k: sds(v, jnp.bfloat16, PS())
              for k, v in ref.FROZEN_SHAPES.items()}
    rows = lambda dtype: sds((n, 2, T), dtype, PS("d"))
    compiled = fed._epoch.lower(
        P, {"m": P, "v": P, "t": sds((n,), jnp.float32, PS("d"))},
        sds((n, 2), jnp.uint32, PS("d")), rows(jnp.int32), rows(jnp.int32),
        sds((n, 2), jnp.bool_, PS("d")), frozen).compile()
    # the base is an argument and is not donated: it is held beside the
    # temporaries, and what the chip refuses is the sum
    assert held_bytes(compiled) < 15.75 * 2 ** 30, held_bytes(compiled)


def test_qwen3_next_cells_round_program_fits_the_chip(one_chip,
                                                      no_persistent_cache,
                                                      monkeypatch):
    """The WHOLE round program of ``qwen3-next-80b-a3b.dfl4-full-lora-s8192``
    at the cell's own sizes (published layers 1 to 8 at the published
    widths, 4 nodes, 2 steps of one sequence of 8192, the frozen 3.96 GB
    base an argument) compiles for the described v5e under its 15.75 GiB:
    15.24 GiB when the cell came (12.28 GB of temporaries over 4.09 GB of
    arguments), 16.01 GiB with the delta rule 4 key heads a block, where
    the blocks' kept inputs of later layers stood at the peak. Gated
    DeltaNet's delta rule takes the scalar form in every layer: no
    ``kda.diag`` kernel, no ``[.., 16, 16, 128]`` pairwise factors; gated
    attention runs the fused kernel under ``gattn.attn``."""
    from p2pfl_tpu.models import ling

    sc = abstract_cell("qwen3-next-80b-a3b.dfl4-full-lora-s8192", monkeypatch)
    try:
        args = on_device((sc.fed, *sc._data_args, *sc._plan_args(None),
                          *sc._frozen), one_chip)
        compiled = sc._round_fn.lower(*args).compile()
    finally:
        sc.close()
    assert held_bytes(compiled) < 15.75 * 2 ** 30, held_bytes(compiled)
    # the benchmark's floor: the base alone is a quarter of the chip
    assert compiled.memory_analysis().argument_size_in_bytes > 0.25 * 16e9
    hlo = compiled.as_text()
    assert "kda_diag" not in hlo and "kda.diag" not in hlo
    assert not [s for s in unfused(hlo) if re.search(r"\[[\d,]*,16,16,128\]", s)]
    assert set(ling.chunk_forms()["gdn.scan"]["forms"]) == {"scalar"}
    kernels = re.findall(r'%(flash_attention_\w+?)(?:\.\d+)? = .*custom-call'
                         r'\(.*op_name="([^"]*)"', hlo)
    assert kernels and all("gattn.attn" in name for _, name in kernels)
    assert ling.score_tiles("gattn.attn")["kernel"] is True
    assert "gdn.scan" in hlo and "gdn.conv" in hlo and "moe.shared" in hlo
