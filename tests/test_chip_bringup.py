"""What the chip bring-up rests on, as far as a CPU can check it:
``chip_smoke.py`` refuses to run off the chip, the kernel gate measures
under an enclosing trace and cannot swallow a broken kernel, the
compile cache lands where it is told, and the launcher decides between
pinning and refusing without initialising a backend."""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from p2pfl_tpu.ops import pallas_gemm
from p2pfl_tpu.utils import compile_cache

REPO = pathlib.Path(__file__).resolve().parent.parent
_CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


# ---- chip_smoke.py ---------------------------------------------------------


def test_chip_smoke_refuses_without_a_tpu():
    res = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         capture_output=True, text=True, timeout=60,
                         env=_CPU_ENV, cwd=REPO)
    assert res.returncode != 0
    assert "'cpu'" in res.stderr and "tpu" in res.stderr
    assert res.stdout.strip() == ""  # no result line to misread


def test_chip_smoke_rehearsal_is_labelled():
    res = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--rehearse-cpu"],
        capture_output=True, text=True, timeout=300, env=_CPU_ENV,
        cwd=REPO)
    assert res.returncode == 0, res.stdout[-1500:] + res.stderr[-1500:]
    lines = res.stdout.strip().splitlines()
    assert "REHEARSAL" in lines[0]
    assert any(ln.startswith("[A] REHEARSAL") for ln in lines)
    # the last line is the result and holds exactly these keys (the
    # driver refuses anything more); the facts sit on the line before
    out = json.loads(lines[-1])
    assert set(out) == {"ok", "device"} and out["ok"] is True
    assert set(out["device"]) == {"platform", "kind", "count"}
    assert out["device"]["platform"] == "cpu"
    assert isinstance(out["device"]["kind"], str)
    assert type(out["device"]["count"]) is int
    assert lines[-2].startswith("facts: ")
    facts = json.loads(lines[-2][len("facts: "):])
    assert facts["rehearsal"] is True
    assert facts["spmd"]["n_nodes"] == 4
    assert list(facts)[-1] == "claim" and facts["claim"] is None


# ---- the kernel gate -------------------------------------------------------


@pytest.fixture
def tpu_gate(monkeypatch):
    """The gate as it behaves on a TPU backend (kernels still run
    through the interpreter: ``_interp`` asks jax, not the gate)."""
    monkeypatch.delenv(pallas_gemm.ENV_KNOB, raising=False)
    monkeypatch.setattr(pallas_gemm, "_backend", lambda: "tpu")
    pallas_gemm.clear_cache()
    pallas_gemm.set_nodes_hint(2)
    yield
    pallas_gemm.clear_cache()
    pallas_gemm.set_nodes_hint(1)


def _gated_under_jit():
    """A gate call site the way the round reaches it: inside
    jit(vmap(scan)), above the measurement threshold."""

    @jax.jit
    def f(x, w):
        def node(a, b):
            def step(c, _):
                pallas_gemm.choose("patches", (a.shape, b.shape), a.dtype)
                return c + jnp.sum(a @ b), None

            return jax.lax.scan(step, jnp.float32(0), None, length=2)[0]

        return jax.vmap(node)(x, w)

    x = jnp.ones((2, 40000, 25), jnp.bfloat16)
    w = jnp.ones((2, 25, 32), jnp.bfloat16)
    return f(x, w)


def test_gate_measures_under_an_enclosing_trace(tpu_gate):
    _gated_under_jit().block_until_ready()
    (rec,) = pallas_gemm.decisions().values()
    assert "error" not in rec and not rec["forced"]
    assert rec["pallas_ms"] >= 0.0 and rec["xla_ms"] >= 0.0
    assert rec["nodes_measured"] == 2  # far under the operand budget
    assert rec["measure_s"] > 0.0  # what deciding cost, beside the verdict
    assert rec["impl"] in ("pallas", "xla")


def test_gate_does_not_swallow_a_broken_kernel(tpu_gate, monkeypatch):
    def broken(*_a, **_k):
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(pallas_gemm, "patches_matmul", broken)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        _gated_under_jit()
    assert pallas_gemm.decisions() == {}  # "the kernel broke" is no decision


# ---- the compile cache helper ---------------------------------------------

_PRINT_CACHE = ("from p2pfl_tpu.utils import compile_cache; import jax; "
                "print(compile_cache.enable()); "
                "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "cc"))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path / "cc")
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_default_is_the_checkout_from_any_cwd(tmp_path):
    env = {k: v for k, v in _CPU_ENV.items() if k != compile_cache.ENV}
    env["PYTHONPATH"] = str(REPO)
    for cwd in (tmp_path, REPO / "tests"):
        res = subprocess.run([sys.executable, "-c", _PRINT_CACHE],
                             capture_output=True, text=True, timeout=60,
                             env=env, cwd=cwd)
        assert res.returncode == 0, res.stderr[-800:]
        assert res.stdout.split() == [str(REPO / ".jax_cache")] * 2


# ---- one process per chip --------------------------------------------------


def test_launcher_pins_one_child_per_chip_or_refuses(monkeypatch):
    from p2pfl_tpu.p2p import launch

    monkeypatch.setattr(launch, "_tpu_chips", lambda: 4)
    envs = launch._child_envs(4, 8, None)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    with pytest.raises(launch.ChipContention) as exc:
        launch._child_envs(8, 8, None)
    assert "--platform cpu" in str(exc.value)
    assert "--nodes-per-proc 2" in str(exc.value)
    # told their platform, or no TPU here: children inherit as before
    assert launch._child_envs(8, 8, "cpu") == [None] * 8
    monkeypatch.setattr(launch, "_tpu_chips", lambda: 0)
    assert launch._child_envs(8, 8, None) == [None] * 8


def test_launch_parent_never_initialises_a_backend(tmp_path):
    """Importing the launcher, loading a scenario and placing the
    compile cache must leave every backend untouched: a parent that
    holds the chip starves its children."""
    code = (
        "import sys\n"
        "import p2pfl_tpu.p2p.launch as L\n"
        "from p2pfl_tpu.config.schema import ScenarioConfig\n"
        "from p2pfl_tpu.utils import compile_cache\n"
        "cfg = ScenarioConfig(n_nodes=4)\n"
        "cfg.save(sys.argv[1]); ScenarioConfig.load(sys.argv[1])\n"
        "compile_cache.enable()\n"
        "import jax._src.xla_bridge as xb\n"
        "print('BACKENDS', sorted(xb._backends))\n"
    )
    res = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path / "s.json")],
        capture_output=True, text=True, timeout=120,
        env=dict(_CPU_ENV, PYTHONPATH=str(REPO)), cwd=tmp_path)
    assert res.returncode == 0, res.stderr[-800:]
    assert "BACKENDS []" in res.stdout
