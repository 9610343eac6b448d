"""The program's side of the seam with ``benchmark/``.

``BENCHMARK.json`` + ``benchmark/run.py`` is the repo's one yardstick,
and it reaches into the program by name: a reader file per metric, the
harness's own imports, a ``ScenarioConfig`` built from each cell's data
files. The benchmark's own tests (``benchmark/tests/``) are subprocess
rehearsals that tier-1 never collects, so a program change that renames
one of those names would pass every test here and stop every cell at
the driver's check. These cases hold the names and call shapes in
process, on no device. They are parametrised from ``BENCHMARK.json``:
a later cell or reader is covered by being listed there.

``benchmark/run.py`` is imported by path, as ``benchmark/tests/`` do;
nothing of it is copied here.
"""

import ast
import dataclasses
import importlib
import importlib.util
import inspect
import json
import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
HOME = ROOT / "benchmark"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def harness():
    """``benchmark/run.py`` as a module, with ``benchmark/`` importable
    while this file's cases run (readers import ``spans``, ``scopework``
    and ``tracereduce`` from there) and gone again afterwards."""
    before = set(sys.modules)
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(str(HOME))  # undo restores all of sys.path
        spec = importlib.util.spec_from_file_location(
            "benchmark_run", HOME / "run.py")
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        yield module
    for name in set(sys.modules) - before:
        file = getattr(sys.modules[name], "__file__", None)
        if file and pathlib.Path(file).is_relative_to(HOME):
            del sys.modules[name]


def program_names(path):
    """What the source at ``path`` takes from ``p2pfl_tpu``, as dotted
    names: every ``from p2pfl_tpu.a import b``, every ``import
    p2pfl_tpu.a``, and every ``b.c`` or ``getattr(b, "c", ...)`` on a
    name so bound (a reader may tolerate a missing attribute and fill
    nothing; the seam may not)."""
    tree = ast.parse(pathlib.Path(path).read_text())
    bound, names = {}, set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and (node.module or "").split(
                ".")[0] == "p2pfl_tpu":
            for a in node.names:
                bound[a.asname or a.name] = f"{node.module}.{a.name}"
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.split(".")[0] == "p2pfl_tpu":
                    names.add(a.name)
                    if a.asname:
                        bound[a.asname] = a.name
    names.update(bound.values())
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and isinstance(
                node.value, ast.Name) and node.value.id in bound:
            names.add(f"{bound[node.value.id]}.{node.attr}")
        elif (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "getattr" and len(node.args) >= 2
                and isinstance(node.args[0], ast.Name)
                and node.args[0].id in bound
                and isinstance(node.args[1], ast.Constant)):
            names.add(f"{bound[node.args[0].id]}.{node.args[1].value}")
    return sorted(names)


def resolves(dotted):
    """Whether ``dotted`` names a module, or an attribute chain under
    the longest module prefix that imports."""
    parts = dotted.split(".")
    for cut in range(len(parts), 0, -1):
        try:
            obj = importlib.import_module(".".join(parts[:cut]))
        except ImportError:
            continue
        try:
            for attr in parts[cut:]:
                obj = getattr(obj, attr)
        except AttributeError:
            return False
        return True
    return False


def missing(path):
    return [n for n in program_names(path) if not resolves(n)]


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_reader_finds_what_it_reads(harness, metric):
    path = HOME / "readers" / f"{metric}.py"
    assert path.is_file(), f"per_layer metric {metric} has no reader file"
    gone = missing(path)
    assert not gone, f"reader {metric}: the program no longer has {gone}"
    reader = harness.load_module(path, "bench_reader")
    inspect.signature(reader.read).bind({})  # read(ctx)


def test_a_name_made_to_disappear_fails_its_reader(monkeypatch):
    from p2pfl_tpu.ops import pallas_gemm

    path = HOME / "readers" / "kernels.gate_measure_s.py"
    assert missing(path) == []
    monkeypatch.delattr(pallas_gemm, "decisions")
    assert missing(path) == ["p2pfl_tpu.ops.pallas_gemm.decisions"]


@pytest.mark.parametrize("rehearse", [False, True], ids=["own", "rehearsal"])
@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_builds_its_scenario_config(harness, workload, rehearse):
    """The cell's ``configs/`` and ``traffic/`` files make a
    ``ScenarioConfig`` the program accepts (an unknown key or a value
    ``__post_init__`` refuses raises here), and name a class the
    program's ``federation`` has. Nothing is placed on a device."""
    from p2pfl_tpu import federation
    from p2pfl_tpu.config.schema import ScenarioConfig

    cell = harness.Cell(workload, rehearse)
    cfg = cell.scenario_config(7)
    assert isinstance(cfg, ScenarioConfig)
    assert cfg.n_nodes == cell.n_nodes and cfg.seed == cfg.data.seed == 7
    assert cfg.training.rounds == cell.traffic["followed_rounds"]
    scenario = getattr(federation, cell.traffic["scenario_class"])
    inspect.signature(scenario).bind(cfg)


def test_harness_finds_the_program_it_drives(harness):
    """Every name ``benchmark/``'s own sources take from the program
    resolves, and what ``run.py`` and ``control.py`` call has the shape
    they call it with."""
    for path in sorted(HOME.glob("*.py")):
        gone = missing(path)
        assert not gone, f"benchmark/{path.name}: the program no longer has {gone}"

    from p2pfl_tpu import federation
    from p2pfl_tpu.federation.events import Events
    from p2pfl_tpu.models import cnn, ling
    from p2pfl_tpu.obs import trace as obs_trace
    from p2pfl_tpu.ops import pallas_gemm
    from p2pfl_tpu.utils import compile_cache

    def takes(fn, *args, **kwargs):
        inspect.signature(fn).bind(*args, **kwargs)

    for name in sorted({json.loads(f.read_text())["scenario_class"]
                        for f in (HOME / "traffic").glob("*.json")}):
        scenario = getattr(federation, name)
        takes(scenario.run, None, rounds=3)  # -> ScenarioResult
        takes(scenario.evaluate, None)
        takes(scenario.add_observer, None, lambda event, payload: None)
        takes(scenario.close, None)
    assert {"history", "round_times_s"} <= {
        f.name for f in dataclasses.fields(federation.ScenarioResult)}
    # ``Driven._on_event`` closes its spans on these three
    assert {"ROUND_STARTED", "AGGREGATION_FINISHED",
            "ROUND_FINISHED"} <= set(Events.__members__)

    takes(compile_cache.enable)
    for fn in (obs_trace.install_xla_listener, obs_trace.reset_xla_counters):
        takes(fn)
    assert float(obs_trace.xla_compile_seconds()) >= 0
    assert int(obs_trace.xla_recompiles()) >= 0
    # the readers' side: seconds and counters kept since process start,
    # the span ring as raw tuples, the trace-time records
    assert isinstance(obs_trace.stage_seconds(), dict)
    assert float(obs_trace.trace_lower_seconds()) >= 0
    assert float(obs_trace.cache_load_seconds()) >= 0
    assert isinstance(obs_trace.counted(), dict)
    assert isinstance(obs_trace.get_tracer().spans(), list)
    assert isinstance(pallas_gemm.decisions(), dict)
    assert isinstance(cnn.lowerings(), dict)
    assert isinstance(ling.score_tiles(), dict)
    # kept by the scope a model's attention runs under
    # (``readers/swa.score_tiles_share.py``)
    assert isinstance(ling.score_tiles("swa.attn"), dict)


def test_host_span_names_are_names_the_program_emits(harness):
    """The readers of the host's side of a run (``benchmark/hostspans.py``)
    spell no new span name themselves: each is a constant of the
    program's, and each constant is a name the program hands
    ``tracer.span()`` in ``federation/scenario.py`` (the stall watch's:
    the name its tick appends to the ring). A constant that stayed while
    its span went would leave its reader silent on the chip."""
    import hostspans
    import spans

    from p2pfl_tpu.federation import scenario
    from p2pfl_tpu.obs import trace as obs_trace
    from p2pfl_tpu.parallel import transport

    emitted = set()
    for node in ast.walk(ast.parse(inspect.getsource(scenario))):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                and node.func.attr == "span" and node.args):
            name = node.args[0]
            emitted.add(getattr(scenario, name.id) if isinstance(
                name, ast.Name) else name.value)
    assert {scenario.SPAN_RUN, scenario.SPAN_RUN_ENTER,
            scenario.SPAN_RUN_EXIT} <= emitted
    asked = {hostspans.RUN, hostspans.LOG_METRICS, hostspans.LOG_RESOURCES,
             hostspans.LOG_WRITE, hostspans.WAIT, hostspans.LOG,
             spans.ROUND, spans.EVALUATE}
    assert None not in asked and asked <= emitted, asked - emitted
    assert hostspans.STALL == obs_trace.STALL_SPAN
    assert "STALL_SPAN" in inspect.getsource(obs_trace._StallWatch.tick)
    assert not obs_trace.STALL_SPAN.startswith(("scenario.", "bench."))
    # the two programs' names are the builders' own
    from p2pfl_tpu.parallel import federated

    source = inspect.getsource(federated)
    for program in (hostspans.ROUND_PROGRAM, hostspans.EVAL_PROGRAM):
        assert program in (transport.ROUND_PROGRAM, transport.EVAL_PROGRAM)
        assert f"def {program}(" in source


def test_score_tiles_share_reads_the_programs_record(harness, monkeypatch):
    """``mla.score_tiles_share``: nothing on a program without the
    record (the parent's ``models/ling.py``), and after a trace of the
    cell's 4096 positions at tiles of 256 x 256 the 136 tiles at or
    under the diagonal over the square's 256."""
    import jax
    import jax.numpy as jnp

    from p2pfl_tpu.models import ling

    reader = harness.load_module(
        HOME / "readers" / "mla.score_tiles_share.py", "bench_reader")
    one = lambda width: jax.ShapeDtypeStruct((1, 4096, 2, width),
                                             jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: ling.causal_attention(
        q, k, v, 1.0, block=256, tile=256), one(24), one(24), one(16))
    assert reader.read({}) == 53.125
    monkeypatch.setattr(ling, "_score_tiles", {})  # no such layer traced
    assert reader.read({}) is None
    monkeypatch.delattr(ling, "score_tiles")
    assert reader.read({}) is None


def test_window_score_tiles_share_reads_its_own_scopes_record(harness,
                                                              monkeypatch):
    """``swa.score_tiles_share``: nothing where no window layer was
    traced, nothing on a program whose ``score_tiles`` takes no scope
    (the parent's) or has none, and after a trace of the cell's 8192
    positions under a window of 512 the 93 tiles the blocks' windows
    touch over the square's 1024; a trace under another scope does not
    move it."""
    import jax
    import jax.numpy as jnp

    from p2pfl_tpu.models import ling

    reader = harness.load_module(
        HOME / "readers" / "swa.score_tiles_share.py", "bench_reader")
    monkeypatch.setattr(ling, "_score_tiles", {})
    assert reader.read({}) is None
    one = lambda heads: jax.ShapeDtypeStruct((1, 8192, heads, 8),
                                             jnp.bfloat16)
    trace = lambda **how: jax.eval_shape(
        lambda q, k, v: ling.causal_attention(q, k, v, 1.0, **how),
        one(9), one(1), one(1))
    trace(window=512, scope="swa.attn")
    trace(scope="gqa.attn")
    assert reader.read({}) == 100.0 * 93 / 1024
    monkeypatch.setattr(ling, "score_tiles", lambda: {"computed": 1})
    assert reader.read({}) is None
    monkeypatch.delattr(ling, "score_tiles")
    assert reader.read({}) is None


def scopes_read(path):
    """The scope names a reader hands ``scopework`` (every string it
    passes, ``also=`` apart: those name the compiler's ops)."""
    names = []
    for node in ast.walk(ast.parse(pathlib.Path(path).read_text())):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "scopework"):
            names += [a.value for a in node.args
                      if isinstance(a, ast.Constant) and isinstance(a.value, str)]
    return names


def test_lfm2_readers_scopes_are_in_the_programs_lowering():
    """The ``lfm2.*`` readers find device time by the program's own
    ``jax.named_scope``s (``lfm2.conv``, ``gqa.attn``, ``moe.*``,
    ``lora.side``, ``lm.head_loss``): each name a reader passes is in the
    name stack of the model's lowered step, and the new one,
    ``lfm2.conv``, on the way back too (attention's and the expert
    layer's ways back are written by hand and set their scopes bare); a
    renamed scope would leave its reader silent on the chip and pass
    every other test here. Nothing is shared in this model's expert
    layer, so no reader of its cell names ``moe.shared``."""
    import jax
    import jax.numpy as jnp

    from p2pfl_tpu.learning.lora import wrap_model
    from p2pfl_tpu.models import get_model

    model = get_model("lfm2-8b-a1b", layer_types=["conv", "full_attention"],
                      dense_layers=1)
    x = jnp.zeros((1, 16), jnp.int32)
    lm = wrap_model(model, "lfm2-8b-a1b", 2, sample_x=x)
    step = jax.grad(lambda a: lm.apply(a, x, x, None, method="loss")[0])
    text = jax.jit(step).lower(
        lm.init(jax.random.PRNGKey(0), x)).as_text(debug_info=True)
    readers = sorted((HOME / "readers").glob("lfm2.*.py"))
    scopes = {s for path in readers for s in scopes_read(path)}
    assert {"lfm2.conv", "gqa.attn", "moe.experts", "lora.side",
            "lm.head_loss"} <= scopes and "moe.shared" not in scopes
    for scope in sorted(scopes):
        assert f"/{scope}/" in text, f"no op of the step bears {scope}"
    assert re.search(r'"[^"]*transpose\([^"]*/lfm2\.conv/[^"]*"', text)
    assert "moe.shared" not in text


def test_lfm2_cell_names_what_the_program_has(harness):
    """The cell's data files against the program: the model and the data
    set by name, the adapters' default targets, and a frozen tree with no
    ``head`` (``frozen.from`` is ``model.base``; the tied embedding is
    its one vocabulary-sized leaf)."""
    from p2pfl_tpu.datasets.sources import token_spec
    from p2pfl_tpu.models import list_models
    from p2pfl_tpu.models.base import default_lora_targets

    cell = harness.Cell("lfm2-8b-a1b.dfl16-full-lora-s2048", False)
    model = cell.scenario["model"]
    assert model["model"] in list_models() and model["kwargs"]["tie_head"]
    assert token_spec(cell.scenario["data"]["dataset"]) == (65536, 2048)
    assert set(default_lora_targets(model["model"])) == {
        "conv_in", "conv_out", "attn_q", "attn_k", "attn_v", "attn_o"}
    assert cell.config["frozen"]["from"] == "model.base"
    names = set(cell.config["frozen"]["param_map"].values())
    assert "embed" in names and "head" not in names
    # every trained leaf sits on a frozen kernel of the map
    kernels = {p[:-len("/kernel")] for p in cell.config["frozen"]["param_map"]
               if p.endswith("/kernel")}
    assert {p.rsplit("/kernel/", 1)[0] for p in cell.config["param_map"]} \
        <= kernels
