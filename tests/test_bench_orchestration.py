"""bench.py orchestration contract (round 4): the driver parses the
LAST stdout line, so under ANY budget the bench must end with one
parseable JSON object carrying the required keys — round 3 lost every
number to a timeout precisely because this wasn't guaranteed."""

import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_zero_budget_still_emits_parseable_json():
    env = dict(os.environ, P2PFL_BENCH_BUDGET_S="0")
    res = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    # the headline phase was skipped, so there is no ``value``: the
    # envelope still parses, and the exit code says the run failed
    assert res.returncode == 1, res.stderr[-500:]
    last = res.stdout.strip().splitlines()[-1]
    out = json.loads(last)
    # driver contract keys
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in out, key
    assert out["value"] is None
    assert out["metric"] == "femnist_cnn_64node_ring_round_wall_clock"
    assert out["unit"] == "s/round"
    # with zero budget (t_end == t_start, remaining negative
    # everywhere), every phase is explicitly accounted as skipped
    assert set(out["skipped_phases"]) == {
        "headline", "cifar16", "cpu8", "socket24", "comm", "socket_mp",
        "obs", "obs_health", "robust", "elastic", "cross_device",
        "chaos", "aggd", "lora", "private", "devprof", "vit32"
    }
    # the provenance stamp (round 12) rides the envelope even at zero
    # budget — a regression report must always name its commit
    meta = out["meta"]
    assert set(meta) >= {"seed", "host", "ts", "git_sha", "jax"}


def test_robust_phase_dry_run_emits_variant_plan():
    """P2PFL_ROBUST_DRY=1: the robust phase must emit its variant plan
    as one parseable part without touching any accelerator — the cheap
    orchestration smoke for the round-8 robustness phase."""
    env = dict(os.environ, P2PFL_ROBUST_DRY="1")
    code = (f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
            "import bench; bench._phase_robust()\n")
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60,
                         env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-500:]
    sys.path.insert(0, str(REPO))
    import bench

    parts = [json.loads(line[len(bench._PART_TAG):])
             for line in res.stdout.splitlines()
             if line.startswith(bench._PART_TAG)]
    assert len(parts) == 1 and parts[0]["robust_dry"] is True
    assert set(parts[0]["robust_variants"]) == {
        "robust_acc_clean_fedavg", "robust_acc_signflip_fedavg",
        "robust_acc_signflip_krum", "robust_acc_signflip_trimmedmean",
        "robust_acc_signflip_repfedavg",
    }


def test_obs_phase_dry_run_emits_key_plan():
    """P2PFL_OBS_DRY=1: the obs phase must emit its planned key list
    as one parseable part without touching jax — the round-9 analog of
    the robust dry-run hook."""
    env = dict(os.environ, P2PFL_OBS_DRY="1")
    code = (f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
            "import bench; bench._phase_obs()\n")
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60,
                         env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-500:]
    sys.path.insert(0, str(REPO))
    import bench

    parts = [json.loads(line[len(bench._PART_TAG):])
             for line in res.stdout.splitlines()
             if line.startswith(bench._PART_TAG)]
    assert len(parts) == 1 and parts[0]["obs_dry"] is True
    planned = set(parts[0]["obs_keys"])
    assert {"obs_overhead_pct", "obs_round_s_untraced",
            "obs_round_s_traced", "obs_xla_recompiles",
            # round 18: the critical-path validation arm's keys ride
            # the same plan
            "critpath_wire_s_24node", "critpath_wait_s_24node",
            "critpath_sum_err_pct_24node"} <= planned
    # every planned key must be registered (and, via
    # check_bench_keys, documented)
    assert planned <= set(bench.BENCH_KEYS)


def test_comm_phase_dry_run_emits_key_plan():
    """P2PFL_COMM_DRY=1: the comm phase must emit its planned key list
    as one parseable part without touching jax — the round-10 analog
    of the obs dry-run hook."""
    env = dict(os.environ, P2PFL_COMM_DRY="1")
    code = (f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
            "import bench; bench._phase_comm()\n")
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60,
                         env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-500:]
    sys.path.insert(0, str(REPO))
    import bench

    parts = [json.loads(line[len(bench._PART_TAG):])
             for line in res.stdout.splitlines()
             if line.startswith(bench._PART_TAG)]
    assert len(parts) == 1 and parts[0]["comm_dry"] is True
    planned = set(parts[0]["comm_keys"])
    assert {"wire_payload_bytes_per_round", "wire_payload_reduction",
            "wire_bf16_round_s_24node_uncapped", "overlap_round_s",
            "overlap_rounds_to_80pct",
            "overlap_xla_recompiles"} <= planned
    assert planned <= set(bench.BENCH_KEYS)


def test_elastic_phase_dry_run_emits_key_plan():
    """P2PFL_ELASTIC_DRY=1: the elastic phase must emit its planned key
    list as one parseable part without touching jax — the round-11
    analog of the comm dry-run hook."""
    env = dict(os.environ, P2PFL_ELASTIC_DRY="1")
    code = (f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
            "import bench; bench._phase_elastic()\n")
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60,
                         env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-500:]
    sys.path.insert(0, str(REPO))
    import bench

    parts = [json.loads(line[len(bench._PART_TAG):])
             for line in res.stdout.splitlines()
             if line.startswith(bench._PART_TAG)]
    assert len(parts) == 1 and parts[0]["elastic_dry"] is True
    planned = set(parts[0]["elastic_keys"])
    assert {"elastic_sync_wall_s", "elastic_async_wall_s",
            "elastic_async_speedup", "elastic_churn",
            "elastic_spmd_rounds_to_target_weighted"} <= planned
    assert planned <= set(bench.BENCH_KEYS)


def test_obs_health_phase_dry_run_emits_key_plan():
    """P2PFL_HEALTH_DRY=1: the health phase must emit its planned key
    list as one parseable part without touching jax — the round-12
    analog of the elastic dry-run hook."""
    env = dict(os.environ, P2PFL_HEALTH_DRY="1")
    code = (f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
            "import bench; bench._phase_obs_health()\n")
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60,
                         env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-500:]
    sys.path.insert(0, str(REPO))
    import bench

    parts = [json.loads(line[len(bench._PART_TAG):])
             for line in res.stdout.splitlines()
             if line.startswith(bench._PART_TAG)]
    assert len(parts) == 1 and parts[0]["obs_health_dry"] is True
    planned = set(parts[0]["obs_health_keys"])
    assert {"obs_health_detect_dead_s", "obs_health_detect_stall_s",
            "obs_health_overhead_pct", "obs_health_round_s_on",
            "obs_health_round_s_off",
            "obs_health_flight_dump_bytes"} <= planned
    assert planned <= set(bench.BENCH_KEYS)


def test_cross_device_phase_dry_run_emits_key_plan():
    """P2PFL_CROSSDEV_DRY=1: the cross_device phase must emit its
    planned key list as one parseable part without touching jax — the
    round-13 analog of the obs_health dry-run hook."""
    env = dict(os.environ, P2PFL_CROSSDEV_DRY="1")
    code = (f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
            "import bench; bench._phase_cross_device()\n")
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60,
                         env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-500:]
    sys.path.insert(0, str(REPO))
    import bench

    parts = [json.loads(line[len(bench._PART_TAG):])
             for line in res.stdout.splitlines()
             if line.startswith(bench._PART_TAG)]
    assert len(parts) == 1 and parts[0]["crossdev_dry"] is True
    planned = set(parts[0]["crossdev_keys"])
    assert {"crossdev_round_s_10k", "crossdev_clients_per_s",
            "crossdev_cohort_scaling", "crossdev_rounds_to_target",
            "crossdev_xla_recompiles",
            # round 17: fused-accumulate A/B arm
            "crossdev_fused_round_s", "crossdev_unfused_round_s",
            "crossdev_fused_speedup"} <= planned
    assert planned <= set(bench.BENCH_KEYS)


def test_chaos_phase_dry_run_emits_key_plan():
    """P2PFL_CHAOS_DRY=1: the chaos phase must emit its planned key
    list as one parseable part without touching jax — the round-14
    analog of the obs_health dry-run hook."""
    env = dict(os.environ, P2PFL_CHAOS_DRY="1")
    code = (f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
            "import bench; bench._phase_chaos()\n")
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60,
                         env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-500:]
    sys.path.insert(0, str(REPO))
    import bench

    parts = [json.loads(line[len(bench._PART_TAG):])
             for line in res.stdout.splitlines()
             if line.startswith(bench._PART_TAG)]
    assert len(parts) == 1 and parts[0]["chaos_dry"] is True
    planned = set(parts[0]["chaos_keys"])
    assert {"chaos_recovery_s", "chaos_final_accuracy",
            "chaos_clean_accuracy", "chaos_accuracy_gap"} <= planned
    assert planned <= set(bench.BENCH_KEYS)


def test_aggd_phase_dry_run_emits_key_plan():
    """P2PFL_AGGD_DRY=1: the aggd phase must emit its planned key list
    as one parseable part without touching jax — the round-15 analog
    of the chaos dry-run hook."""
    env = dict(os.environ, P2PFL_AGGD_DRY="1")
    code = (f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
            "import bench; bench._phase_aggd()\n")
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60,
                         env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-500:]
    sys.path.insert(0, str(REPO))
    import bench

    parts = [json.loads(line[len(bench._PART_TAG):])
             for line in res.stdout.splitlines()
             if line.startswith(bench._PART_TAG)]
    assert len(parts) == 1 and parts[0]["aggd_dry"] is True
    planned = set(parts[0]["aggd_keys"])
    assert {"aggd_round_s_24node_uncapped",
            "aggd_inline_round_s_24node_uncapped",
            "aggd_loop_payload_touch_bytes", "aggd_speedup"} <= planned
    assert planned <= set(bench.BENCH_KEYS)


def test_lora_phase_dry_run_emits_key_plan():
    """P2PFL_LORA_DRY=1: the lora phase must emit its planned key list
    as one parseable part without touching jax — the round-19 analog
    of the aggd dry-run hook."""
    env = dict(os.environ, P2PFL_LORA_DRY="1")
    code = (f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
            "import bench; bench._phase_lora()\n")
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60,
                         env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-500:]
    sys.path.insert(0, str(REPO))
    import bench

    parts = [json.loads(line[len(bench._PART_TAG):])
             for line in res.stdout.splitlines()
             if line.startswith(bench._PART_TAG)]
    assert len(parts) == 1 and parts[0]["lora_dry"] is True
    planned = set(parts[0]["lora_keys"])
    assert {"lora_adapter_bytes_per_round", "lora_full_bytes_per_round",
            "lora_payload_reduction", "lora_krum_round_s",
            "lora_full_krum_round_s", "lora_final_accuracy",
            "lora_accuracy_gap", "lora_xla_recompiles"} <= planned
    assert planned <= set(bench.BENCH_KEYS)


def test_private_phase_dry_run_emits_key_plan():
    """P2PFL_PRIVATE_DRY=1: the private phase must emit its planned key
    list as one parseable part without touching jax — the round-21
    analog of the lora dry-run hook."""
    env = dict(os.environ, P2PFL_PRIVATE_DRY="1")
    code = (f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
            "import bench; bench._phase_private()\n")
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60,
                         env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-500:]
    sys.path.insert(0, str(REPO))
    import bench

    parts = [json.loads(line[len(bench._PART_TAG):])
             for line in res.stdout.splitlines()
             if line.startswith(bench._PART_TAG)]
    assert len(parts) == 1 and parts[0]["private_dry"] is True
    planned = set(parts[0]["private_keys"])
    assert {"private_acc_clean", "private_acc_nm03", "private_eps_nm03",
            "private_acc_nm06", "private_eps_nm06", "private_acc_nm10",
            "private_eps_nm10", "private_plain_round_s",
            "private_secagg_round_s",
            "private_secagg_overhead_pct"} <= planned
    assert planned <= set(bench.BENCH_KEYS)


def test_devprof_phase_dry_run_emits_key_plan():
    """P2PFL_DEVPROF_DRY=1: the devprof phase must emit its planned key
    list as one parseable part without touching jax — the round-22
    analog of the obs dry-run hook."""
    env = dict(os.environ, P2PFL_DEVPROF_DRY="1")
    code = (f"import sys; sys.path.insert(0, {str(REPO)!r})\n"
            "import bench; bench._phase_devprof()\n")
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60,
                         env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-500:]
    sys.path.insert(0, str(REPO))
    import bench

    parts = [json.loads(line[len(bench._PART_TAG):])
             for line in res.stdout.splitlines()
             if line.startswith(bench._PART_TAG)]
    assert len(parts) == 1 and parts[0]["devprof_dry"] is True
    planned = set(parts[0]["devprof_keys"])
    assert planned == set(bench._DEVPROF_KEYS)
    assert {"devprof_overhead_pct", "devprof_phase_sum_err_pct",
            "devprof_top_component", "devprof_mfu_live",
            "devprof_mfu_err_pct"} <= planned
    # every planned key must be registered (and, via
    # check_bench_keys, documented)
    assert planned <= set(bench.BENCH_KEYS)


def test_ab_interleaved_orders_runs_and_picks_min():
    """_ab_interleaved: strict A,B,A,B interleave, min-of-pairs per
    arm, None/keyless runs dropped at selection, on_run sees every
    run."""
    sys.path.insert(0, str(REPO))
    import bench

    calls = []
    a_results = iter([{"round_s": 3.0}, {"round_s": 2.0}])
    b_results = iter([None, {"round_s": 5.0}])

    def run_a():
        calls.append("a")
        return next(a_results)

    def run_b():
        calls.append("b")
        return next(b_results)

    seen = []
    best_a, best_b = bench._ab_interleaved(
        run_a, run_b, pairs=2,
        on_run=lambda tag, i, r: seen.append((tag, i)))
    assert calls == ["a", "b", "a", "b"]
    assert seen == [("a", 0), ("b", 0), ("a", 1), ("b", 1)]
    assert best_a == {"round_s": 2.0}
    assert best_b == {"round_s": 5.0}

    # an arm whose every run lacks the key selects None, not a crash
    best_a, best_b = bench._ab_interleaved(
        lambda: {"other": 1}, lambda: {"round_s": 1.0}, pairs=1)
    assert best_a is None and best_b == {"round_s": 1.0}


def test_bench_keys_registry_in_sync_with_docs():
    """scripts/check_bench_keys.py: every registered key documented in
    docs/perf.md, every literal emission key registered."""
    res = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "check_bench_keys.py")],
        capture_output=True, text=True, timeout=60, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr[-500:]
    assert res.stdout.startswith("ok:")


def test_stream_child_keeps_parts_from_failing_child():
    """A phase child that emits a part and THEN dies must still
    deliver the part (the monotone-artifact guarantee round 3's
    timeout loss motivated)."""
    import time as _time

    sys.path.insert(0, str(REPO))
    import bench

    parts = []
    err = bench._stream_child("_phase_selftest",
                              deadline=_time.monotonic() + 60,
                              on_part=parts.append)
    assert parts == [{"selftest_key": 41}]
    assert err is not None and "rc=" in err
