"""The Kimi-Linear-48B-A3B cell's own files: the configuration keeps the
published widths and states its cut, the FLOP count agrees with one
by hand, the cell rehearses through ``run_cell`` on the CPU and is
``correct`` under its ``rehearse_limits``, the model run through the
delta rule's bounded form (Ling's clip) is not, and its readers report
nothing where the program has nothing to read. The cell's rehearsal
through the command line, its control and its planted faults run with
every other cell's in ``test_rehearsal.py`` and ``test_control.py``."""

import argparse
import functools
import json
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import flops  # noqa: E402
import run as bench  # noqa: E402

CELL = "kimi-linear-48b-a3b.dfl2-full-lora-s16384"
CONFIG = json.loads(
    (HERE.parent / "configs" / "kimi-linear-48b-a3b.json").read_text())
# the catalog of published model configurations, one JSON row a model
CATALOG = pathlib.Path(os.environ.get("MODEL_CATALOG", "architectures.jsonl"))

PUBLISHED_WIDTHS = {
    "hidden_size": 2304, "num_attention_heads": 32, "num_key_value_heads": 32,
    "head_dim": 72, "intermediate_size": 9216, "moe_intermediate_size": 1024,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "q_lora_rank": None, "mla_use_nope": True,
    "num_experts_per_token": 8, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "num_expert_group": 1, "topk_group": 1,
    "moe_router_activation_func": "sigmoid", "rms_norm_eps": 1e-05,
    "first_k_dense_replace": 1, "tie_word_embeddings": False,
}
KDA, MLA = [1, 2, 3, 5, 6, 7], [4, 8]


def test_configuration_keeps_the_published_widths_and_states_its_cut():
    arch = CONFIG["architecture"]
    for key, value in PUBLISHED_WIDTHS.items():
        assert arch[key] == value and CONFIG[key] == value, key
    assert all(CONFIG[k] == v for k, v in arch.items())
    assert sorted(CONFIG["reduced"]) == sorted(CONFIG["published"]) == [
        "linear_attn_config", "num_experts", "num_hidden_layers", "vocab_size"]
    assert (arch["num_hidden_layers"], arch["num_experts"],
            arch["vocab_size"]) == (8, 32, 20480)
    lac = arch["linear_attn_config"]
    assert (lac["kda_layers"], lac["full_attn_layers"]) == (KDA, MLA)
    assert (lac["head_dim"], lac["num_heads"], lac["short_conv_kernel_size"]
            ) == (128, 32, 4)
    assert CONFIG["published"]["vocab_size"] == 8 * 20480
    assert CONFIG["published"]["num_experts"] == 8 * 32
    assert "8 chips share each layer" in CONFIG["deployment"]
    assert "layers 1 to 8" in CONFIG["layers_kept"]
    assert {"block", "layer_rule", "kda", "mla", "experts", "init",
            "sequence", "data", "optimizer", "adapters", "dtypes",
            "loss_chunk"} <= set(CONFIG["assumed"])
    entry = next(c for c in bench.load_json(bench.ROOT / "BENCHMARK.json")[
        "configs"] if c["name"] == "kimi-linear-48b-a3b")
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    # what the program is built from is those widths, 32 of 256 held
    kw = CONFIG["scenario"]["model"]["kwargs"]
    assert (kw["hidden"], kw["heads"], kw["head_dim"], kw["nope"], kw["rope"],
            kw["v_dim"], kw["kv_rank"], kw["conv"], kw["dense_width"],
            kw["expert_width"], kw["shared_width"], kw["n_experts"],
            kw["experts_held"], kw["expert_offset"], kw["top_k"],
            kw["route_scale"], kw["vocab"], kw["eps"], kw["first_dense"]) == (
        2304, 32, 128, 128, 64, 128, 512, 4, 9216, 1024, 1024, 256, 32, 0, 8,
        2.446, 20480, 1e-5, 1)
    assert (kw["layers"], kw["kda_layers"], kw["full_attn_layers"]) == (
        8, KDA, MLA)
    assert CONFIG["scenario"]["data"]["dataset"] == "tokens-20480-16384"


@pytest.mark.skipif(not CATALOG.is_file(), reason="no model catalog (MODEL_CATALOG)")
def test_every_key_of_the_catalogs_config_is_held_or_listed_as_reduced():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert CONFIG["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert changed == set(CONFIG["reduced"])
    for key in ("num_hidden_layers", "num_experts", "vocab_size",
                "linear_attn_config"):
        assert CONFIG["published"][key] == row["config"][key]
    kept = dict(row["config"]["linear_attn_config"])
    kept.update(kda_layers=KDA, full_attn_layers=MLA)
    assert CONFIG["linear_attn_config"] == kept


def test_required_flops_of_a_sequence():
    cell = bench.Cell(CELL, False)
    per = flops.per_sample(cell.config, cell.scenario)
    T = 16384
    # the hand count, forward a token at 16,384 positions: 1.380
    # GFLOP; frozen products 1.018 with the expert layers' 0.206 among
    # them, MLA's causal half 0.336, the recurrence 0.019
    d, HK = 2304, 4096
    kda = 3 * 2 * d * HK + 2 * 2 * d * 128 + 2 * 2 * 128 * HK + 2 * d * 32 \
        + 2 * HK * d
    mla = 2 * d * 6144 + 2 * d * 576 + 2 * 512 * 8192 + 2 * 4096 * d
    experts = 2 * d * 256 + 6 * d * 1024 + 1.0 * 6 * d * 1024
    frozen = 6 * kda + 2 * mla + 6 * d * 9216 + 7 * experts + 2 * d * 20480
    own = 2 * 2 * 32 * 320 * (T + 1) / 2 + 6 * (6 * 128 * 128 * 32
                                                 + 2 * 4 * 3 * HK)
    adapters = 2 * 16 * (6 * 4 * (d + HK) + 2 * (
        (d + 6144) + (d + 576) + (512 + 8192) + (4096 + d)))
    assert round(frozen / 1e6) == 1018 and round(7 * experts / 1e6) == 206
    assert per["forward"] == pytest.approx(T * (frozen + adapters + own))
    assert round(per["forward"] / T / 1e7) == 138
    first_in = 2 * d * (3 * HK + 2 * 128 + 32)
    assert per["train"] == pytest.approx(
        T * (2 * frozen - first_in + 3 * (adapters + own)))
    # a round: 2 nodes x 2 steps x 1 sequence: 200.8 TFLOP, 3.063 GFLOP a
    # token (an estimate by hand read 3.04 and 199.5)
    assert flops.round_flops(cell.config, cell.scenario, 2) == 4 * per["train"]
    assert round(4 * per["train"] / 1e11) == 2008
    assert round(per["train"] / T / 1e6) == 3063
    count = bench.load_module(bench.HERE / "counts" / "kimi_linear.py", "count")
    work = count.scope_work(cell.config, cell.scenario)
    assert work["kda.scan"]["forward"] == (6 * 6 * 128 * 128 * 32,
                                           6 * 32 * (6 * 128 + 4 * 128 + 4
                                                     + 2 * 128))
    assert work["mla.attn"]["forward"] == (2 * 2 * 32 * 320 * (T + 1) / 2,
                                           2 * 32 * (2 * 192 + 2 * 128) * 2)
    # 1.0 held pair a token, 7 expert layers
    assert work["moe.experts"]["forward"][0] == 7 * 6 * d * 1024
    # the delta rule is bound by bytes, attention and the experts by FLOPs
    peak = bench.load_json(bench.HERE / "peaks.json")["TPU v5 lite"]
    bound = lambda f, b: ("flops" if f / peak["bf16_flops_per_s"]
                          > b / peak["hbm_bytes_per_s"] else "bytes")
    assert [bound(*work[s]["forward"]) for s in (
        "kda.scan", "mla.attn", "moe.experts")] == ["bytes", "flops", "flops"]


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


def test_the_bounded_form_fails_the_cell(monkeypatch):
    """The same model through the delta rule's bounded form (Ling's clip
    at 80 over a sub-chunk), planted in the program before its first
    trace: the seeded decays pass the clip (``kda.deep_decay_share`` > 0)
    and the comparison refuses the run."""
    from p2pfl_tpu.models import kimi_linear, ling
    from p2pfl_tpu.obs import trace as obs_trace

    monkeypatch.setattr(kimi_linear, "kda_heads",
                        functools.partial(ling.kda_heads, floor=-5.0))
    line = rehearse()
    assert line["correct"] is False
    assert [k for k, v in line["compared"].items() if v["value"] > v["limit"]]
    deep = obs_trace.counted()["kda.deep_decay_share"]
    assert float(deep["sum"].sum()) > 0


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
    cell = bench.Cell(CELL, True)
    reader = bench.load_module(bench.HERE / "readers" / f"{metric}.py", "r")
    unscoped = {"scope_s": {"(no scope)": 3.0, "jit(round_fn)/exchange.mix/dot": 1.0}}
    peak = bench.load_json(bench.HERE / "peaks.json")["TPU v5 lite"]
    for trace in (None, unscoped):
        ctx = {"cell": cell, "trace": trace, "rounds": 4, "evals": 2,
               "chips": 1, "peak": peak, "rows_per_node": 2}
        assert reader.read(ctx) is None
    for module, name in ((obs_trace, "counted"), (obs_trace, "stage_seconds"),
                         (ling, "score_tiles")):
        monkeypatch.delattr(module, name)
    assert reader.read({"cell": cell, "trace": None, "rounds": 4, "evals": 2,
                        "chips": 1, "peak": peak, "rows_per_node": 2}) is None


def test_deep_decay_share_reads_the_mean_over_layers_and_steps(monkeypatch):
    from p2pfl_tpu.obs import trace as obs_trace

    got = {"kda.deep_decay_share": {"sum": np.array([30.0, 90.0, 0.0]),
                                    "max": np.array([20.0, 50.0, 0.0]),
                                    "steps": 2}}
    monkeypatch.setattr(obs_trace, "counted", lambda: got)
    reader = bench.load_module(
        bench.HERE / "readers" / "kimi.kda_deep_decay_share.py", "r")
    assert reader.read({}) == pytest.approx(20.0)


def test_roofline_readers_divide_the_counts_work_by_the_scopes_seconds():
    """A made-up trace in which each scope took exactly the least seconds
    its required work allows reads 100."""
    cell = bench.Cell(CELL, False)
    count = bench.load_module(bench.HERE / "counts" / "kimi_linear.py", "count")
    work = count.scope_work(cell.config, cell.scenario)
    peak = bench.load_json(bench.HERE / "peaks.json")["TPU v5 lite"]
    rounds, evals = 2, 1
    tokens = {"train": rounds * 2 * 2 * 16384, "forward": evals * 2 * 4 * 16384}
    for metric, scope in (("kimi.kda_scan_roofline", "kda.scan"),
                          ("kimi.mla_attn_roofline", "mla.attn"),
                          ("kimi.moe_experts_roofline", "moe.experts")):
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
