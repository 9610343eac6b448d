"""The Laguna-S-2.1 cell's own files: the configuration keeps the
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

CELL = "laguna-s-2.1.dfl4-full-lora-s8192"
CONFIG = json.loads((HERE.parent / "configs" / "laguna-s-2.1.json").read_text())
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")

PUBLISHED_WIDTHS = {
    "hidden_size": 3072, "num_attention_heads": 48, "num_key_value_heads": 8,
    "head_dim": 128, "sliding_window": 512, "intermediate_size": 12288,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "num_experts_per_tok": 10, "moe_routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "gating": "per-head", "rms_norm_eps": 1e-06,
}


def test_configuration_keeps_the_published_widths_and_states_its_cut():
    arch = CONFIG["architecture"]
    for key, value in PUBLISHED_WIDTHS.items():
        assert arch[key] == value and CONFIG[key] == value, key
    assert all(CONFIG[k] == v for k, v in arch.items())
    assert sorted(CONFIG["reduced"]) == sorted(CONFIG["published"])
    assert {k: arch[k] for k in ("num_hidden_layers", "num_experts",
                                 "vocab_size")} == {
        "num_hidden_layers": 5, "num_experts": 64, "vocab_size": 25088}
    assert arch["layer_types"] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert arch["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert arch["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert arch["gating_types"] == ["per_head"] * 5
    assert CONFIG["router_outputs"] == 256 and "4 chips" in CONFIG["deployment"]
    full = arch["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["factor"], full["rope_theta"],
            full["partial_rotary_factor"]) == ("yarn", 128, 500000, 0.5)
    entry = next(c for c in bench.load_json(bench.ROOT / "BENCHMARK.json")["configs"]
                 if c["name"] == "laguna-s-2.1")
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    # what the program is built from is those widths
    kw = CONFIG["scenario"]["model"]["kwargs"]
    assert (kw["hidden"], kw["heads"], kw["kv_heads"], kw["head_dim"],
            kw["window"], kw["dense_width"], kw["expert_width"],
            kw["shared_width"], kw["n_experts"], kw["top_k"],
            kw["route_scale"]) == (
        3072, [48, 72, 72, 72, 48], 8, 128, 512, 12288, 1024, 1024, 256, 10, 2.5)
    assert (kw["theta_full"], kw["yarn_factor"], kw["yarn_original"],
            kw["yarn_attention_factor"], kw["rotary_full"], kw["theta_window"],
            kw["rotary_window"]) == (
        500000.0, 128.0, 8192, 1.4852030263919618, 0.5, 10000.0, 1.0)
    assert (kw["experts_held"], kw["vocab"]) == (64, 25088)
    assert kw["vocab"] * 4 == 100352 and kw["experts_held"] * 4 == kw["n_experts"]


@pytest.mark.skipif(not CATALOG.is_file(), reason="no catalog beside the guide")
def test_every_key_of_the_catalogs_config_is_held_or_listed_as_reduced():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Laguna-S-2.1")
    assert CONFIG["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert changed == set(CONFIG["reduced"])


def test_required_flops_of_a_sequence():
    cell = bench.Cell(CELL, False)
    per = flops.per_sample(cell.config, cell.scenario)
    T = 8192
    # frozen products 1.2067 GFLOP a token (layer 0 0.415 with its causal
    # half, a window layer 0.212, the full expert layer 0.256, the head
    # 0.154, attention's own products apart), adapters 4.85 MFLOP,
    # attention's required products 0.2562 GFLOP: 2 x 0.1007 the causal
    # halves of 48 heads, 3 x 0.0183 the band of 72
    assert per["forward"] == T * (1_206_730_752 + 4_849_664 + 256_208_256)
    assert per["train"] == T * (2 * 1_206_730_752 - 50_626_560
                                + 3 * (4_849_664 + 256_208_256))
    # a round: 4 nodes x 2 steps x 1 sequence
    assert flops.round_flops(cell.config, cell.scenario, 2) == 8 * per["train"]
    count = bench.load_module(bench.HERE / "counts" / "laguna_s.py", "count")
    work = count.scope_work(cell.config, cell.scenario)
    assert work["swa.attn"]["forward"] == (3 * 2 * 72 * 256 * 496.03125,
                                           3 * (2 * 72 + 2 * 8) * 128 * 2)
    assert work["gqa.attn"]["forward"] == (2 * 2 * 48 * 256 * 4096.5,
                                           2 * (2 * 48 + 2 * 8) * 128 * 2)
    # 2.5 held pairs a token expected, 4 expert layers
    assert work["moe.experts"]["forward"][0] == 4 * 2.5 * 6 * 3072 * 1024


def test_cell_rehearses_through_run_cell_and_is_correct():
    args = argparse.Namespace(workload=CELL, seed=2147484039, seconds=1.0,
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
    """On a program without the scopes, the counters or the record, and
    in a CPU rehearsal with no device trace."""
    from p2pfl_tpu.models import ling
    from p2pfl_tpu.obs import trace as obs_trace

    monkeypatch.setattr(obs_trace, "counted", lambda: {})
    monkeypatch.setattr(ling, "_score_tiles", {})
    cell = bench.Cell(CELL, True)
    reader = bench.load_module(bench.HERE / "readers" / f"{metric}.py", "r")
    unscoped = {"scope_s": {"(no scope)": 3.0, "jit(round_fn)/exchange.mix/dot": 1.0}}
    peak = bench.load_json(bench.HERE / "peaks.json")["TPU v5 lite"]
    for trace in (None, unscoped):
        ctx = {"cell": cell, "trace": trace, "rounds": 4, "evals": 2,
               "chips": 1, "peak": peak, "rows_per_node": 2}
        assert reader.read(ctx) is None


def test_roofline_readers_divide_the_counts_work_by_the_scopes_seconds():
    """A made-up trace in which each scope took exactly the least seconds
    its required work allows reads 100."""
    cell = bench.Cell(CELL, False)
    count = bench.load_module(bench.HERE / "counts" / "laguna_s.py", "count")
    work = count.scope_work(cell.config, cell.scenario)
    peak = bench.load_json(bench.HERE / "peaks.json")["TPU v5 lite"]
    rounds, evals = 2, 1
    tokens = {"train": rounds * 2 * 4 * 8192, "forward": evals * 4 * 4 * 8192}
    for metric, scope in (("swa.attn_roofline", "swa.attn"),
                          ("gqa.attn_roofline", "gqa.attn"),
                          ("laguna.moe_experts_roofline", "moe.experts")):
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
