"""The Qwen3-Next-80B-A3B cell's own files: the configuration keeps the
published widths and states its cut, the FLOP count agrees with one by
hand, the cell rehearses through ``run_cell`` on the CPU and is
``correct`` under its ``rehearse_limits``, the model with its value
heads reading the wrong key heads is not, and its readers report
nothing where the program has nothing to read. The cell's rehearsal
through the command line, its control and its planted faults run with
every other cell's in ``test_rehearsal.py`` and ``test_control.py``."""

import argparse
import json
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import flops  # noqa: E402
import run as bench  # noqa: E402

CELL = "qwen3-next-80b-a3b.dfl4-full-lora-s8192"
CONFIG = json.loads(
    (HERE.parent / "configs" / "qwen3-next-80b-a3b.json").read_text())
# the catalog of published model configurations, one JSON row a model
CATALOG = pathlib.Path(os.environ.get("MODEL_CATALOG", "architectures.jsonl"))

PUBLISHED_WIDTHS = {
    "hidden_size": 2048, "num_attention_heads": 16, "num_key_value_heads": 2,
    "head_dim": 256, "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512, "num_experts_per_tok": 10,
    "norm_topk_prob": True, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "rms_norm_eps": 1e-06,
    "full_attention_interval": 4, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "tie_word_embeddings": False,
}


def test_configuration_keeps_the_published_widths_and_states_its_cut():
    arch = CONFIG["architecture"]
    for key, value in PUBLISHED_WIDTHS.items():
        assert arch[key] == value and CONFIG[key] == value, key
    assert all(CONFIG[k] == v for k, v in arch.items())
    assert sorted(CONFIG["reduced"]) == sorted(CONFIG["published"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (arch["num_hidden_layers"], arch["num_experts"],
            arch["vocab_size"]) == (8, 64, 18992)
    assert CONFIG["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 8 * 64,
                                   "vocab_size": 8 * 18992}
    assert CONFIG["router_outputs"] == 512
    assert "8 chips share each layer" in CONFIG["deployment"]
    assert "layers 1 to 8" in CONFIG["layers_kept"]
    assert {"block", "layer_rule", "gdn", "attention", "experts", "mtp",
            "init", "sequence", "data", "optimizer", "adapters", "dtypes",
            "loss_chunk", "head_block"} <= set(CONFIG["assumed"])
    entry = next(c for c in bench.load_json(bench.ROOT / "BENCHMARK.json")[
        "configs"] if c["name"] == "qwen3-next-80b-a3b")
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    # what the program is built from is those widths, 64 of 512 held
    kw = CONFIG["scenario"]["model"]["kwargs"]
    assert (kw["hidden"], kw["key_heads"], kw["value_heads"],
            kw["linear_head_dim"], kw["conv"], kw["heads"], kw["kv_heads"],
            kw["head_dim"], kw["partial_rotary"], kw["theta"],
            kw["expert_width"], kw["shared_width"], kw["n_experts"],
            kw["experts_held"], kw["expert_offset"], kw["top_k"], kw["vocab"],
            kw["eps"], kw["full_attention_interval"]) == (
        2048, 16, 32, 128, 4, 16, 2, 256, 0.25, 1e7, 512, 512, 512, 64, 0, 10,
        18992, 1e-6, 4)
    assert (kw["layers"], kw["first_layer"]) == (8, 1)
    assert CONFIG["scenario"]["data"]["dataset"] == "tokens-18992-8192"


@pytest.mark.skipif(not CATALOG.is_file(), reason="no model catalog (MODEL_CATALOG)")
def test_every_key_of_the_catalogs_config_is_held_or_listed_as_reduced():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert CONFIG["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert changed == set(CONFIG["reduced"])
    for key in CONFIG["reduced"]:
        assert CONFIG["published"][key] == row["config"][key]


def test_required_flops_of_a_sequence():
    cell = bench.Cell(CELL, False)
    per = flops.per_sample(cell.config, cell.scenario)
    T, d = 8192, 2048
    # the hand count, forward a token at 8192 positions: 0.880 GFLOP;
    # the six GDN mixers 0.427 (their recurrence 0.019), the two attention
    # layers 0.244 (their causal half 0.134), the expert layers 0.130, the
    # head 0.078
    gdn = 2 * d * 12288 + 2 * d * 64 + 2 * 4096 * d
    attn = 2 * d * 8192 + 2 * 2 * d * 512 + 2 * 4096 * d
    experts = 2 * d * 512 + 6 * d * 512 + 2 * d + 1.25 * 6 * d * 512
    frozen = 6 * gdn + 2 * attn + 8 * experts + 2 * d * 18992
    own = 2 * 2 * 16 * 512 * (T + 1) / 2 + 6 * (6 * 128 * 128 * 32
                                                 + 2 * 4 * 8192)
    adapters = 2 * 16 * (6 * ((d + 12288) + (4096 + d)) + 2 * (
        (d + 8192) + 2 * (d + 512) + (4096 + d)))
    assert round(8 * experts / 1e6) == 130 and round(2 * d * 18992 / 1e6) == 78
    assert per["forward"] == pytest.approx(T * (frozen + adapters + own))
    assert round(per["forward"] / T / 1e6) == 880
    first_in = 2 * d * (12288 + 64)
    assert per["train"] == pytest.approx(
        T * (2 * frozen - first_in + 3 * (adapters + own)))
    # a round: 4 nodes x 2 steps x 1 sequence, 122.4 TFLOP
    assert flops.round_flops(cell.config, cell.scenario, 2) == 8 * per["train"]
    assert round(8 * per["train"] / 1e11) == 1224
    count = bench.load_module(bench.HERE / "counts" / "qwen3_next.py", "count")
    work = count.scope_work(cell.config, cell.scenario)
    # the recurrence over 32 value heads; q and k read once a key head
    assert work["gdn.scan"]["forward"] == (
        6 * 6 * 128 * 128 * 32,
        6 * (16 * 2 * 128 * 2 + 32 * (128 * 2 + 4 + 4 + 128 * 2)))
    assert work["gattn.attn"]["forward"] == (
        2 * 2 * 16 * 2 * 256 * (T + 1) / 2, 2 * (2 * 16 * 256 + 2 * 2 * 256) * 2)
    # 1.25 held pairs a token, 8 expert layers
    assert work["moe.experts"]["forward"][0] == 8 * 1.25 * 6 * d * 512
    # the delta rule is bound by bytes, attention and the experts by FLOPs
    peak = bench.load_json(bench.HERE / "peaks.json")["TPU v5 lite"]
    bound = lambda f, b: ("flops" if f / peak["bf16_flops_per_s"]
                          > b / peak["hbm_bytes_per_s"] else "bytes")
    assert [bound(*work[s]["forward"]) for s in (
        "gdn.scan", "gattn.attn", "moe.experts")] == ["bytes", "flops", "flops"]


def rehearse(seed=2147484141, sabotage=None):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=1.0,
                              trace=0, rehearse_cpu=True)
    return bench.run_cell(args, sabotage=sabotage)


def test_cell_rehearses_through_run_cell_and_is_correct():
    line = rehearse()
    assert line["correct"] is True and line["failed"] == 0
    limits = bench.load_json(bench.HERE / "cells" / f"{CELL}.json")
    assert set(line["compared"]) == set(limits["limits"])
    for name, v in line["compared"].items():
        assert v["limit"] == limits["rehearse_limits"].get(
            name, limits["limits"][name])
        assert v["value"] <= v["limit"], name
    assert set(line["metrics"]) == {"round_s", "eval_s", "setup_s"}
    assert line["facts"]["rehearsal"] is True


def test_value_heads_on_the_wrong_key_heads_fail_the_cell(monkeypatch):
    """The same model with value head ``h`` reading key head ``h % 16``
    (``tile`` in place of ``repeat_interleave``), planted in the program
    before its first trace: the comparison refuses the run."""
    import jax.numpy as jnp

    from p2pfl_tpu.models import ling, qwen3_next

    def tiled(q, k, v, g, beta, **kw):
        group = v.shape[2] // q.shape[2]
        wide = lambda a: jnp.tile(a, (1, 1, group, 1))
        return ling.kda_heads(wide(q), wide(k), v,
                              jnp.broadcast_to(g[..., None], v.shape[:3] + (
                                  q.shape[-1],)), beta, **kw)

    monkeypatch.setattr(qwen3_next, "kda_heads", tiled)
    line = rehearse()
    assert line["correct"] is False
    assert [k for k, v in line["compared"].items() if v["value"] > v["limit"]]


@pytest.mark.parametrize("metric", [
    m["name"] for m in bench.load_json(bench.ROOT / "BENCHMARK.json")["per_layer"]
    if m.get("workloads") == [CELL]])
def test_readers_report_nothing_where_there_is_nothing_to_read(metric, monkeypatch):
    """On a program without the scopes, the counters or the records (the
    parent's), and in a CPU rehearsal with no device trace."""
    from p2pfl_tpu.models import ling
    from p2pfl_tpu.obs import trace as obs_trace

    monkeypatch.setattr(obs_trace, "counted", lambda: {})
    monkeypatch.setattr(obs_trace, "stage_seconds", lambda: {})
    monkeypatch.setattr(ling, "score_tiles", lambda scope="mla.attn": {})
    monkeypatch.setattr(ling, "chunk_forms", lambda: {})
    cell = bench.Cell(CELL, True)
    reader = bench.load_module(bench.HERE / "readers" / f"{metric}.py", "r")
    unscoped = {"scope_s": {"(no scope)": 3.0, "jit(round_fn)/exchange.mix/dot": 1.0}}
    peak = bench.load_json(bench.HERE / "peaks.json")["TPU v5 lite"]
    for trace in (None, unscoped):
        ctx = {"cell": cell, "trace": trace, "rounds": 4, "evals": 2,
               "chips": 1, "peak": peak, "rows_per_node": 2}
        assert reader.read(ctx) is None
    for module, name in ((obs_trace, "counted"), (obs_trace, "stage_seconds"),
                         (ling, "score_tiles"), (ling, "chunk_forms")):
        monkeypatch.delattr(module, name)
    assert reader.read({"cell": cell, "trace": None, "rounds": 4, "evals": 2,
                        "chips": 1, "peak": peak, "rows_per_node": 2}) is None


def test_roofline_readers_divide_the_counts_work_by_the_scopes_seconds():
    """A made-up trace in which each scope took exactly the least seconds
    its required work allows reads 100."""
    cell = bench.Cell(CELL, False)
    count = bench.load_module(bench.HERE / "counts" / "qwen3_next.py", "count")
    work = count.scope_work(cell.config, cell.scenario)
    peak = bench.load_json(bench.HERE / "peaks.json")["TPU v5 lite"]
    rounds, evals = 2, 1
    tokens = {"train": rounds * 2 * 4 * 8192, "forward": evals * 4 * 4 * 8192}
    for metric, scope in (("qwen3next.gdn_scan_roofline", "gdn.scan"),
                          ("qwen3next.attn_roofline", "gattn.attn"),
                          ("qwen3next.moe_experts_roofline", "moe.experts")):
        least = sum(tokens[phase] * max(f / peak["bf16_flops_per_s"],
                                        b / peak["hbm_bytes_per_s"])
                    for phase, (f, b) in work[scope].items())
        trace = {"scope_s": {
            f"jit(round_fn)/vmap()/{scope}/dot_general": 0.75 * least,
            f"jit(eval_fn)/transpose(jvp({scope}))/dot_general": 0.25 * least}}
        ctx = {"cell": cell, "trace": trace, "rounds": rounds, "evals": evals,
               "chips": 1, "peak": peak, "rows_per_node": 2}
        reader = bench.load_module(bench.HERE / "readers" / f"{metric}.py", "r")
        assert reader.read(ctx) == pytest.approx(100.0)
