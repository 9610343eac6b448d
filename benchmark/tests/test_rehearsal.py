"""CPU rehearsal of the benchmark's command: every cell of
``BENCHMARK.json`` at toy sizes through ``--rehearse-cpu``, the last line
of standard output checked against the contract's keys. A rehearsal
prints under the device it ran on and fills no device metric."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
DEVICE_METRICS = {m["name"] for m in BENCH["per_layer"]
                  if m["source"] == "device_trace"}


def rehearse(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={cell['chips']}"))
    env.pop("BENCH_RUN", None)
    out = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", cell["name"],
         "--seed", "2147484001", "--seconds", "1", "--trace", str(trace),
         "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_rehearses(cell, trace):
    line, err = rehearse(cell, trace)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == cell["chips"]
    assert "memory_peak_bytes" in line["device"]
    group = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in BENCH[group]
               if cell["name"] in m.get("workloads", [cell["name"]])}
    assert line["metrics"], "no metric at all"
    for name, m in line["metrics"].items():
        assert name in allowed and m["unit"] == allowed[name]
        assert isinstance(m["value"], float)
    if trace:
        assert not DEVICE_METRICS & set(line["metrics"]), "a CPU run filled a device metric"
    else:
        assert set(line["metrics"]) == set(allowed)
    # every number compared is printed beside its limit, last on stderr
    tail = [l for l in err.strip().splitlines() if l.startswith("compared ")]
    assert len(tail) == len(line["compared"]) > 0
    for v in line["compared"].values():
        assert v["value"] <= v["limit"]


def test_refuses_without_an_accelerator():
    cell = BENCH["workloads"][0]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", cell["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode != 0 and out.stdout.strip() == ""
