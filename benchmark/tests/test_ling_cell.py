"""The Ling-3.0-flash cell's own files: the configuration keeps the
published widths and states its cut, the FLOP count is the ISSUE's
arithmetic, the scope readers find a scope on the way forward and on the
way back and report nothing where the program has nothing to read (the
parent of the PR that brought them). The cell's rehearsal, its control
and its planted faults run with every other cell's in
``test_rehearsal.py`` and ``test_control.py``."""

import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import flops  # noqa: E402
import run as bench  # noqa: E402
import scopework  # noqa: E402

CELL = "ling-3.0-flash.dfl8-full-lora-s4096"
CONFIG = json.loads((HERE.parent / "configs" / "ling-3.0-flash.json").read_text())

PUBLISHED_WIDTHS = {
    "hidden_size": 2560, "num_attention_heads": 32, "head_dim": 128,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "qk_head_dim": 192, "v_head_dim": 128, "moe_intermediate_size": 768,
    "moe_shared_expert_intermediate_size": 768, "intermediate_size": 6144,
    "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
    "routed_scaling_factor": 2.5, "layer_group_size": 6,
    "short_conv_kernel_size": 4, "kda_lower_bound": -5, "rope_theta": 6000000,
    "q_lora_rank": None,
}


def test_configuration_keeps_the_published_widths_and_states_its_cut():
    arch = CONFIG["architecture"]
    for key, value in PUBLISHED_WIDTHS.items():
        assert arch[key] == value and CONFIG[key] == value, key
    # every key of the source at the top level too, as it is run
    assert all(CONFIG[k] == v for k, v in arch.items())
    assert sorted(CONFIG["reduced"]) == sorted(CONFIG["published"])
    assert CONFIG["published"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "vocab_size": 157184,
        "num_nextn_predict_layers": 1}
    assert {k: arch[k] for k in CONFIG["reduced"]} == {
        "num_hidden_layers": 7, "first_k_dense_replace": 1, "num_experts": 64,
        "vocab_size": 19648, "num_nextn_predict_layers": 0}
    entry = next(c for c in bench.load_json(bench.ROOT / "BENCHMARK.json")["configs"]
                 if c["name"] == "ling-3.0-flash")
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    # what the program is built from is those widths
    kw = CONFIG["scenario"]["model"]["kwargs"]
    assert (kw["hidden"], kw["heads"], kw["head_dim"], kw["kv_rank"], kw["nope"],
            kw["rope"], kw["v_dim"], kw["expert_width"], kw["shared_width"],
            kw["dense_width"], kw["n_experts"], kw["top_k"], kw["n_group"],
            kw["topk_group"], kw["route_scale"]) == (
        2560, 32, 128, 512, 128, 64, 128, 768, 768, 6144, 512, 8, 8, 4, 2.5)
    assert (kw["layers"], kw["experts_held"], kw["vocab"]) == (7, 64, 19648)
    assert kw["vocab"] * 8 == 157184 and kw["experts_held"] * 8 == kw["n_experts"]


def test_required_flops_of_a_sequence():
    cell = bench.Cell(CELL, False)
    per = flops.per_sample(cell.config, cell.scenario)
    T = 4096
    # about 1.1 GFLOP a token forward: six KDA mixers, the MLA mixer with
    # its causal half at 4096, the dense FFN, six expert layers with one
    # held expert a token expected, the head's slice
    assert per["forward"] == T * 1_114_697_728
    # frozen products twice (forward, input gradient; the first mixer's
    # input projections once), adapters, attention and the recurrence thrice
    assert per["train"] == T * (2 * 1_047_298_048 - 84_213_760
                                + 3 * (5_982_208 + 61_417_472))
    # a round: 8 nodes x 2 steps x 1 sequence
    assert flops.round_flops(cell.config, cell.scenario, 2) == 16 * per["train"]


def test_scope_seconds_forward_back_and_by_program():
    trace = {"scope_s": {
        "jit(round_fn)/vmap()/while/body/fit.value_and_grad/jvp(LingLM)/layer_2/moe/moe.experts/ragged_dot": 1.0,
        "jit(round_fn)/vmap()/while/body/fit.value_and_grad/transpose(jvp(moe.experts))/ragged_dot": 2.0,
        "jit(eval_fn)/vmap()/while/body/eval.forward/LingLM/layer_2/moe/moe.experts/ragged_dot": 4.0,
        "jit(round_fn)/exchange.mix/dot_general": 8.0,
        "(no scope)": 16.0}}
    assert scopework.seconds(trace, ("moe.experts",)) == 7.0
    assert scopework.seconds(trace, ("moe.experts",), "round_fn") == 3.0
    assert scopework.seconds(trace, ("moe.experts", "exchange.mix"), "round_fn") == 11.0
    assert scopework.seconds(trace, ("kda.scan",)) is None
    assert scopework.seconds(trace, ("experts",)) is None  # a whole name only


@pytest.mark.parametrize("metric", [
    m["name"] for m in bench.load_json(bench.ROOT / "BENCHMARK.json")["per_layer"]
    if m.get("workloads") == [CELL]])
def test_readers_report_nothing_where_there_is_nothing_to_read(metric, monkeypatch):
    """On a program without the scopes, the counters or the stage (the
    parent), and in a CPU rehearsal with no device trace."""
    from p2pfl_tpu.obs import trace as obs_trace

    monkeypatch.setattr(obs_trace, "counted", lambda: {})
    monkeypatch.setattr(obs_trace, "stage_seconds", lambda: {})
    cell = bench.Cell(CELL, True)
    reader = bench.load_module(bench.HERE / "readers" / f"{metric}.py", "r")
    unscoped = {"scope_s": {"(no scope)": 3.0, "jit(round_fn)/exchange.mix/dot": 1.0}}
    peak = bench.load_json(bench.HERE / "peaks.json")["TPU v5 lite"]
    for trace in (None, unscoped):
        ctx = {"cell": cell, "trace": trace, "rounds": 4, "evals": 2,
               "chips": 1, "peak": peak, "rows_per_node": 2}
        assert reader.read(ctx) is None
