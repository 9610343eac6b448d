"""Multi-host DCN mode (SURVEY.md §7 phase 6): 2 localhost processes x
4 virtual CPU devices each, joined by jax.distributed into one 8-node
federation; one federated round must run and agree across processes."""

import json
import os
import re
import socket
import subprocess
import sys

import jax


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_dcn_federated_round(tmp_path):
    port = _free_port()
    env = dict(os.environ)
    # each process gets its own 4-device virtual CPU "host"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=4").strip()
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "p2pfl_tpu.parallel.dcn",
             "--coordinator", f"127.0.0.1:{port}",
             "--num-processes", "2", "--process-id", str(i),
             "--platform", "cpu", "--rounds", "1"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(2)
    ]
    results = []
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=360)
        outs.append(out)
        for line in out.splitlines():
            if line.startswith("P2PFL_DCN_RESULT "):
                results.append(json.loads(line[len("P2PFL_DCN_RESULT "):]))
    assert len(results) == 2, f"missing results; outputs:\n{outs[0]}\n{outs[1]}"
    for r in results:
        assert r["n_processes"] == 2
        assert r["n_nodes"] == 8  # 2 hosts x 4 devices, one node each
        assert r["rounds"] == 1
        assert 0.0 <= r["mean_accuracy"] <= 1.0
        # fully-connected DFL FedAvg: every node's params identical,
        # including across the process/DCN boundary
        assert r["cross_process_param_spread"] < 1e-5
    # both processes computed the same global metrics
    assert abs(results[0]["mean_loss"] - results[1]["mean_loss"]) < 1e-6


def test_two_process_dcn_full_scenario(tmp_path):
    """The REAL DCN mode (VERDICT r2 #4): a ring-SDFL-Krum Scenario —
    leadership rotation, robust aggregation, metrics logging, and a
    checkpoint — executed by 2 processes x 2 virtual devices over one
    global mesh."""
    from p2pfl_tpu.config.schema import (
        DataConfig,
        ProtocolConfig,
        ScenarioConfig,
        TrainingConfig,
    )

    cfg = ScenarioConfig(
        name="dcn-sdfl",
        federation="SDFL",
        topology="ring",
        n_nodes=4,
        data=DataConfig(dataset="mnist", samples_per_node=64),
        training=TrainingConfig(rounds=2, epochs_per_round=1,
                                learning_rate=0.05, eval_every=1),
        protocol=ProtocolConfig(),
        aggregator="krum",
        aggregator_kwargs={"f": 0, "m": 2},
        seed=3,
        log_dir=str(tmp_path / "logs"),
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_every=1,
    )
    config_path = tmp_path / "scenario.json"
    cfg.save(config_path)

    port = _free_port()
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=2"
    ).strip()
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "p2pfl_tpu.parallel.dcn",
             "--coordinator", f"127.0.0.1:{port}",
             "--num-processes", "2", "--process-id", str(i),
             "--platform", "cpu", "--config", str(config_path)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(2)
    ]
    results, outs = [], []
    for p in procs:
        out, _ = p.communicate(timeout=360)
        outs.append(out)
        for line in out.splitlines():
            if line.startswith("P2PFL_DCN_RESULT "):
                results.append(json.loads(line[len("P2PFL_DCN_RESULT "):]))
    assert len(results) == 2, f"missing results; outputs:\n{outs[0]}\n{outs[1]}"
    for r in results:
        assert r["n_processes"] == 2 and r["n_nodes"] == 4
        assert r["federation"] == "SDFL" and r["aggregator"] == "krum"
        assert r["rounds"] == 2
        assert 0.0 <= r["final_accuracy"] <= 1.0
    # the deterministic host trajectory (incl. SDFL leader rotation)
    # agreed across processes
    assert results[0]["leader"] == results[1]["leader"]
    assert results[0]["final_accuracy"] == results[1]["final_accuracy"]
    # process 0 wrote the scenario artifacts: metrics + both checkpoints
    assert (tmp_path / "logs" / "dcn-sdfl" / "metrics.jsonl").exists()
    ckpts = sorted((tmp_path / "ckpt").glob("round_*.ckpt.msgpack"))
    assert len(ckpts) == 2, ckpts

    # ---- multi-host RESUME: a fresh 2-process job restores the
    # round-2 checkpoint (gathered+written by proc 0, loaded by both)
    # and continues for another 2 rounds with the replayed SDFL
    # leadership trajectory
    port2 = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "p2pfl_tpu.parallel.dcn",
             "--coordinator", f"127.0.0.1:{port2}",
             "--num-processes", "2", "--process-id", str(i),
             "--platform", "cpu", "--config", str(config_path)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(2)
    ]
    results2, outs2 = [], []
    for p in procs:
        out, _ = p.communicate(timeout=360)
        outs2.append(out)
        for line in out.splitlines():
            if line.startswith("P2PFL_DCN_RESULT "):
                results2.append(json.loads(line[len("P2PFL_DCN_RESULT "):]))
    assert len(results2) == 2, (
        f"missing resume results; outputs:\n{outs2[0]}\n{outs2[1]}"
    )
    assert results2[0]["leader"] == results2[1]["leader"]
    rounds = sorted(
        int(p.name.split("_")[1].split(".")[0])
        for p in (tmp_path / "ckpt").glob("round_*.ckpt.msgpack")
    )
    assert rounds == [1, 2, 3, 4], rounds  # resumed past round 2


def test_four_process_dcn_scenario_unaligned(tmp_path):
    """VERDICT r4 #7: 4 localhost processes x 2 virtual devices = 8
    global devices, but a 6-node federation — MeshTransport's divisor
    rule builds the mesh from SIX of the eight devices, so host
    boundaries do NOT align with the node layout: processes 0-2 own
    two single-node devices each, process 3 owns ZERO mesh devices yet
    must still join every collective, the checkpoint barrier, and the
    resume. Exercises multi-process make_array_from_callback placement
    where some processes fill no shards."""
    from p2pfl_tpu.config.schema import (
        DataConfig,
        ProtocolConfig,
        ScenarioConfig,
        TrainingConfig,
    )

    cfg = ScenarioConfig(
        name="dcn-4proc",
        federation="DFL",
        topology="ring",
        n_nodes=6,
        data=DataConfig(dataset="mnist", samples_per_node=48),
        training=TrainingConfig(rounds=2, epochs_per_round=1,
                                learning_rate=0.05, eval_every=1),
        protocol=ProtocolConfig(),
        seed=5,
        log_dir=str(tmp_path / "logs"),
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_every=1,
    )
    config_path = tmp_path / "scenario.json"
    cfg.save(config_path)

    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=2"
    ).strip()

    def launch_job(port):
        procs = [
            subprocess.Popen(
                [sys.executable, "-m", "p2pfl_tpu.parallel.dcn",
                 "--coordinator", f"127.0.0.1:{port}",
                 "--num-processes", "4", "--process-id", str(i),
                 "--platform", "cpu", "--config", str(config_path)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True,
            )
            for i in range(4)
        ]
        results, outs = [], []
        for p in procs:
            out, _ = p.communicate(timeout=360)
            outs.append(out)
            for line in out.splitlines():
                if line.startswith("P2PFL_DCN_RESULT "):
                    results.append(json.loads(
                        line[len("P2PFL_DCN_RESULT "):]))
        assert len(results) == 4, (
            "missing results; outputs:\n" + "\n====\n".join(outs)
        )
        return results

    results = launch_job(_free_port())
    for r in results:
        assert r["n_processes"] == 4 and r["n_nodes"] == 6
        assert r["rounds"] == 2
        assert 0.0 <= r["final_accuracy"] <= 1.0
    # all four processes (including the meshless one) agree on the
    # globally-reduced trajectory
    assert len({r["final_accuracy"] for r in results}) == 1
    ckpts = sorted((tmp_path / "ckpt").glob("round_*.ckpt.msgpack"))
    assert len(ckpts) == 2, ckpts

    # ---- cross-host resume from the round-2 checkpoint ---------------
    results2 = launch_job(_free_port())
    assert len({r["final_accuracy"] for r in results2}) == 1
    rounds = sorted(
        int(p.name.split("_")[1].split(".")[0])
        for p in (tmp_path / "ckpt").glob("round_*.ckpt.msgpack")
    )
    assert rounds == [1, 2, 3, 4], rounds  # resumed past round 2


def test_fetch_global_branch_decided_from_process_identical_metadata(
        monkeypatch):
    """Regression (ADVICE r5 medium): with n_nodes <= devices-per-host
    the whole submesh lives on host 0, which sees a FULLY-ADDRESSABLE
    array. Deciding the early return from ``is_fully_addressable``
    (true only on host 0) made host 0 skip ``broadcast_one_to_all``
    while every other host entered it and blocked alone — a deadlock.
    The collective-entering branch must follow only process-identical
    metadata (process_count, device_set vs the global device list), so
    a shard-owning process still JOINS the broadcast.

    Single-process by construction: jax.process_count is stubbed to 2
    and the broadcast recorded, so the branch logic is pinned without
    a jax.distributed job."""
    import jax
    import numpy as np
    from jax.experimental import multihost_utils

    from p2pfl_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    calls = []
    monkeypatch.setattr(
        multihost_utils, "broadcast_one_to_all",
        lambda v: (calls.append("broadcast"), v)[1])

    # a 1-device submesh of the 8-device CI mesh: device_set is a
    # strict subset of jax.devices(), yet the array is fully
    # addressable here — exactly host 0's view of the trap shape
    m = mesh_mod.federation_mesh(n_devices=1)
    x = jax.device_put(np.arange(8.0), mesh_mod.stacked_sharding(m))
    assert x.is_fully_addressable
    assert len(x.sharding.device_set) < len(jax.devices())

    out = mesh_mod.fetch_global(x)
    assert calls == ["broadcast"]  # host 0 joined the collective
    np.testing.assert_array_equal(out, np.arange(8.0))

    # single process: no collectives at all, plain host copy
    monkeypatch.setattr(jax, "process_count", lambda: 1)
    calls.clear()
    np.testing.assert_array_equal(mesh_mod.fetch_global(x), np.arange(8.0))
    assert calls == []
