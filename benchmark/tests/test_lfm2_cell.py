"""The LFM2-8B-A1B cell's own files: the configuration keeps the
published widths and states its cut, the FLOP count is the ISSUE's
arithmetic, the cell rehearses through ``run_cell`` on the CPU and is
``correct`` under its ``rehearse_limits``, and its readers report
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

CELL = "lfm2-8b-a1b.dfl16-full-lora-s2048"
CONFIG = json.loads((HERE.parent / "configs" / "lfm2-8b-a1b.json").read_text())
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")

PUBLISHED_WIDTHS = {
    "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 8,
    "conv_L_cache": 3, "conv_bias": False, "intermediate_size": 7168,
    "moe_intermediate_size": 1792, "num_experts": 32,
    "num_experts_per_tok": 4, "routed_scaling_factor": 1,
    "norm_topk_prob": True, "use_expert_bias": True, "norm_eps": 1e-05,
    "rope_theta": 1000000, "vocab_size": 65536,
}
KEPT = ["conv", "full_attention", "conv", "conv", "conv", "full_attention",
        "conv", "conv", "conv"]


def test_configuration_keeps_the_published_widths_and_states_its_cut():
    arch = CONFIG["architecture"]
    for key, value in PUBLISHED_WIDTHS.items():
        assert arch[key] == value and CONFIG[key] == value, key
    assert all(CONFIG[k] == v for k, v in arch.items())
    assert sorted(CONFIG["reduced"]) == sorted(CONFIG["published"]) == [
        "layer_types", "num_dense_layers", "num_hidden_layers"]
    assert (arch["num_hidden_layers"], arch["num_dense_layers"]) == (9, 1)
    assert arch["layer_types"] == KEPT
    assert "1 chip shares each layer" in CONFIG["deployment"]
    assert "layers 1 to 9" in CONFIG["deployment"]
    assert {"tied_head", "experts", "convolution", "attention", "dense_ffn",
            "loss_chunk"} <= set(CONFIG["assumed"])
    entry = next(c for c in bench.load_json(bench.ROOT / "BENCHMARK.json")["configs"]
                 if c["name"] == "lfm2-8b-a1b")
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    # what the program is built from is those widths, every expert held
    kw = CONFIG["scenario"]["model"]["kwargs"]
    assert (kw["hidden"], kw["heads"], kw["kv_heads"], kw["head_dim"],
            kw["taps"], kw["dense_width"], kw["expert_width"], kw["n_experts"],
            kw["experts_held"], kw["expert_offset"], kw["top_k"],
            kw["route_scale"], kw["vocab"], kw["theta"], kw["eps"]) == (
        2048, 32, 8, 64, 3, 7168, 1792, 32, 32, 0, 4, 1.0, 65536, 1e6, 1e-5)
    assert kw["heads"] * kw["head_dim"] == kw["hidden"]
    assert (kw["layer_types"], kw["dense_layers"], kw["tie_head"]) == (
        KEPT, 1, True)


@pytest.mark.skipif(not CATALOG.is_file(), reason="no catalog beside the guide")
def test_every_key_of_the_catalogs_config_is_held_or_listed_as_reduced():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "LFM2-8B-A1B")
    assert CONFIG["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert changed == set(CONFIG["reduced"])
    assert CONFIG["layer_types"] == row["config"]["layer_types"][1:10]


def test_required_flops_of_a_sequence():
    cell = bench.Cell(CELL, False)
    per = flops.per_sample(cell.config, cell.scenario)
    T = 2048
    # the ISSUE's hand count, forward a token: a convolution operator
    # 33.6 MFLOP, attention's projections 21.0 and its causal half 8.4,
    # the dense FFN 88.1, the experts 88.1 + 0.13 for the router, the
    # head 268.4: 121.7 + 2 x 117.6 + 6 x 121.8 + 268.4 = 1.36 GFLOP
    conv = 2 * 2048 * 6144 + 2 * 2048 * 2048 + 7 * 2048
    attn = 2 * 2048 * (2048 + 1024) + 2 * 2048 * 2048
    experts = 2 * 2048 * 32 + 4 * 6 * 2048 * 1792
    frozen = 7 * conv + 2 * attn + 6 * 2048 * 7168 + 8 * experts \
        + 2 * 2048 * 65536
    adapters = 2 * (7 * 196_608 + 2 * 212_992)
    own = 2 * 2 * 32 * 2 * 64 * (T + 1) / 2
    assert (round(conv / 1e5), round(attn / 1e5), round(experts / 1e5)) == (
        336, 210, 882)
    assert per["forward"] == T * (frozen + adapters + own)
    assert round(per["forward"] / T / 1e7) == 136
    assert per["train"] == T * (2 * frozen - 2 * 2048 * 6144
                                + 3 * (adapters + own))
    # a round: 16 nodes x 2 steps x 1 sequence
    assert flops.round_flops(cell.config, cell.scenario, 2) == 32 * per["train"]
    count = bench.load_module(bench.HERE / "counts" / "lfm2_moe.py", "count")
    work = count.scope_work(cell.config, cell.scenario)
    assert work["lfm2.conv"]["forward"] == (7 * 7 * 2048, 7 * 8192 * 2)
    assert work["gqa.attn"]["forward"] == (2 * 2 * 32 * 128 * 1024.5,
                                           2 * (2 * 32 + 2 * 8) * 64 * 2)
    # 4 pairs a token, all held, 8 expert layers: 22.0 MFLOP a pair
    assert work["moe.experts"]["forward"][0] == 8 * 4 * 6 * 2048 * 1792
    assert round(6 * 2048 * 1792 / 1e5) == 220
    # the convolution is bound by bytes, the other two by FLOPs
    peak = bench.load_json(bench.HERE / "peaks.json")["TPU v5 lite"]
    bound = lambda f, b: ("flops" if f / peak["bf16_flops_per_s"]
                          > b / peak["hbm_bytes_per_s"] else "bytes")
    assert [bound(*work[s]["forward"]) for s in (
        "lfm2.conv", "gqa.attn", "moe.experts")] == ["bytes", "flops", "flops"]


def test_cell_rehearses_through_run_cell_and_is_correct():
    args = argparse.Namespace(workload=CELL, seed=2147484141, seconds=1.0,
                              trace=0, rehearse_cpu=True)
    line = bench.run_cell(args)
    assert line["correct"] is True and line["failed"] == 0
    limits = bench.load_json(bench.HERE / "cells" / f"{CELL}.json")
    assert set(line["compared"]) == set(limits["limits"])
    for name, v in line["compared"].items():
        assert v["limit"] == limits["rehearse_limits"].get(
            name, limits["limits"][name])
        assert v["value"] <= v["limit"], name
    assert set(line["metrics"]) == {"round_s", "eval_s", "setup_s"}
    assert line["facts"]["rehearsal"] is True


@pytest.mark.parametrize("metric", [
    m["name"] for m in bench.load_json(bench.ROOT / "BENCHMARK.json")["per_layer"]
    if m.get("workloads") == [CELL]])
def test_readers_report_nothing_where_there_is_nothing_to_read(metric, monkeypatch):
    """On a program without the scopes or the counters (the parent's),
    and in a CPU rehearsal with no device trace."""
    from p2pfl_tpu.obs import trace as obs_trace

    monkeypatch.setattr(obs_trace, "counted", lambda: {})
    cell = bench.Cell(CELL, True)
    reader = bench.load_module(bench.HERE / "readers" / f"{metric}.py", "r")
    unscoped = {"scope_s": {"(no scope)": 3.0, "jit(round_fn)/exchange.mix/dot": 1.0}}
    peak = bench.load_json(bench.HERE / "peaks.json")["TPU v5 lite"]
    for trace in (None, unscoped):
        ctx = {"cell": cell, "trace": trace, "rounds": 4, "evals": 2,
               "chips": 1, "peak": peak, "rows_per_node": 2}
        assert reader.read(ctx) is None
    monkeypatch.delattr(obs_trace, "counted")
    assert reader.read({"cell": cell, "trace": None, "rounds": 4, "evals": 2,
                        "chips": 1, "peak": peak, "rows_per_node": 2}) is None


def test_roofline_readers_divide_the_counts_work_by_the_scopes_seconds():
    """A made-up trace in which each scope took exactly the least seconds
    its required work allows reads 100."""
    cell = bench.Cell(CELL, False)
    count = bench.load_module(bench.HERE / "counts" / "lfm2_moe.py", "count")
    work = count.scope_work(cell.config, cell.scenario)
    peak = bench.load_json(bench.HERE / "peaks.json")["TPU v5 lite"]
    rounds, evals = 2, 1
    tokens = {"train": rounds * 2 * 16 * 2048, "forward": evals * 16 * 4 * 2048}
    for metric, scope in (("lfm2.conv_roofline", "lfm2.conv"),
                          ("lfm2.gqa_attn_roofline", "gqa.attn"),
                          ("lfm2.moe_experts_roofline", "moe.experts")):
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
