"""Cross-device regime (round 13): K-of-N sampling, lazy partitions,
cohort-scan rounds.

The load-bearing gate is the parity test: the cohort-scan round at
cohort_size=1 with every client sampled must equal the existing dense
stacked round BIT-FOR-BIT (tolerance 0) — same training selection,
same FedAvg weights, same dot shape and reduction order. Everything
else (sampler determinism, fault composition, lazy partition law) is
host-side plumbing guarded here at unit scale.
"""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2pfl_tpu.config.schema import (
    CrossDeviceConfig,
    ModelConfig,
    ScenarioConfig,
)
from p2pfl_tpu.datasets.partition import (
    ClientPartition,
    dirichlet_partition,
    lazy_partition_indices,
)
from p2pfl_tpu.federation.sampling import sample_clients


def _mk_fns():
    from p2pfl_tpu.learning.learner import make_step_fns
    from p2pfl_tpu.models.base import build_model

    return make_step_fns(build_model(ModelConfig(model="mlp")),
                         batch_size=8)


# --------------------------------------------------------------------
# parity: cohort scan == dense stacked round, tolerance 0
# --------------------------------------------------------------------

def test_cohort_scan_parity_with_dense_round_bit_for_bit():
    """cohort_size=1, all N clients sampled, fully-connected mix: the
    cohort-scan program and the dense stacked round must agree on every
    param (and optimizer-state) leaf with tolerance 0, over multiple
    rounds — the ISSUE 10 acceptance gate."""
    from p2pfl_tpu.parallel.federated import (
        build_round_fn,
        build_round_fn_cross_device,
        init_federation,
    )

    n, s = 8, 16
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, s, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(n, s)).astype(np.int32)
    mask = np.ones((n, s), bool)
    sizes = np.full((n,), s, np.int32)

    fns = _mk_fns()
    dense = jax.jit(build_round_fn(fns, epochs=1))
    cross = jax.jit(build_round_fn_cross_device(fns, epochs=1))

    fed_d = init_federation(fns, jnp.asarray(x[0, :1]), n, seed=7)
    fed_c = init_federation(fns, jnp.asarray(x[0, :1]), n, seed=7)

    mix = np.ones((n, n), np.float32)
    adopt = np.arange(n, dtype=np.int32)
    trains = np.ones((n,), bool)

    for r in range(3):
        fed_d, _ = dense(fed_d, x, y, mask, sizes, mix, adopt, trains)
        fed_c, _ = cross(fed_c, x[None], y[None], mask[None],
                         sizes[None], np.ones((1, n), bool))
        for a, b in zip(jax.tree.leaves(fed_d.states.params),
                        jax.tree.leaves(fed_c.states.params)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                f"param leaf diverged at round {r}"
            )
        for a, b in zip(jax.tree.leaves(fed_d.states.opt_state),
                        jax.tree.leaves(fed_c.states.opt_state)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                f"opt leaf diverged at round {r}"
            )


def test_fused_vs_unfused_cohort_round_bit_for_bit():
    """Round-17 gate, same contract as the dense-parity gate above: the
    fused accumulate (single [1, d] carry row per leaf, weighted reduce
    in the fit epilogue) must equal the round-13 unfused reference
    ([n_slots, d] accumulator, full [n_slots, n_slots] dot) with
    tolerance 0 on every param AND optimizer-state leaf, over multiple
    rounds, with heterogeneous shard sizes and a dead cohort member in
    the mix."""
    from p2pfl_tpu.parallel.federated import (
        build_round_fn_cross_device,
        init_federation,
    )

    n, s, c = 4, 8, 3
    rng = np.random.default_rng(17)
    x = rng.normal(size=(c, n, s, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(c, n, s)).astype(np.int32)
    mask = np.ones((c, n, s), bool)
    # heterogeneous example weights + one dead client: the weighted
    # normalization and the keep/where epilogue are both in play
    sizes = rng.integers(1, s + 1, size=(c, n)).astype(np.int32)
    alive = np.ones((c, n), bool)
    alive[2, 1] = False

    fns = _mk_fns()
    fused = jax.jit(build_round_fn_cross_device(
        fns, epochs=1, fused_accumulate=True))
    unfused = jax.jit(build_round_fn_cross_device(
        fns, epochs=1, fused_accumulate=False))
    fed_f = init_federation(fns, jnp.asarray(x[0, 0, :1]), n, seed=5)
    fed_u = init_federation(fns, jnp.asarray(x[0, 0, :1]), n, seed=5)

    for r in range(3):
        fed_f, _ = fused(fed_f, x, y, mask, sizes, alive)
        fed_u, _ = unfused(fed_u, x, y, mask, sizes, alive)
        for a, b in zip(jax.tree.leaves(fed_f.states.params),
                        jax.tree.leaves(fed_u.states.params)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                f"param leaf diverged at round {r}"
            )
        for a, b in zip(jax.tree.leaves(fed_f.states.opt_state),
                        jax.tree.leaves(fed_u.states.opt_state)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                f"opt leaf diverged at round {r}"
            )


def test_fused_cohort_round_zero_recompiles_after_warmup():
    """Resampling clients every round never recompiles the fused
    program: after one warm-up invocation, rounds with freshly drawn
    cohorts (different data, sizes, liveness — same shapes) must hit
    the jit cache, mirroring the crossdev_xla_recompiles bench pin."""
    from p2pfl_tpu.obs import trace as obs_trace
    from p2pfl_tpu.parallel.federated import (
        build_round_fn_cross_device,
        init_federation,
    )

    assert obs_trace.install_xla_listener() is True
    n, s, c = 4, 8, 2
    rng = np.random.default_rng(23)

    def draw():
        x = rng.normal(size=(c, n, s, 28, 28, 1)).astype(np.float32)
        y = rng.integers(0, 10, size=(c, n, s)).astype(np.int32)
        mask = np.ones((c, n, s), bool)
        sizes = rng.integers(1, s + 1, size=(c, n)).astype(np.int32)
        alive = rng.random((c, n)) > 0.2
        alive[0, 0] = True  # never an all-dead round
        return x, y, mask, sizes, alive

    fns = _mk_fns()
    fused = jax.jit(build_round_fn_cross_device(
        fns, epochs=1, fused_accumulate=True))
    x, y, mask, sizes, alive = draw()
    fed = init_federation(fns, jnp.asarray(x[0, 0, :1]), n, seed=2)
    fed, _ = fused(fed, x, y, mask, sizes, alive)  # warm-up compile
    jax.block_until_ready(fed)

    obs_trace.reset_xla_counters()
    for _ in range(3):
        fed, _ = fused(fed, *draw())
    jax.block_until_ready(fed)
    assert obs_trace.xla_recompiles() == 0
    obs_trace.reset_xla_counters()


def test_cohort_scan_dead_client_zero_weight():
    """A dead cohort member neither trains nor contributes weight: the
    round with the member dead must equal the round where that member's
    weight is zeroed out entirely (its data rows are inert)."""
    from p2pfl_tpu.parallel.federated import (
        build_round_fn_cross_device,
        init_federation,
    )

    n, s, c = 4, 8, 2
    rng = np.random.default_rng(1)
    x = rng.normal(size=(c, n, s, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(c, n, s)).astype(np.int32)
    mask = np.ones((c, n, s), bool)
    sizes = np.full((c, n), s, np.int32)

    fns = _mk_fns()
    cross = jax.jit(build_round_fn_cross_device(fns, epochs=1))
    fed_a = init_federation(fns, jnp.asarray(x[0, 0, :1]), n, seed=3)
    fed_b = init_federation(fns, jnp.asarray(x[0, 0, :1]), n, seed=3)

    alive = np.ones((c, n), bool)
    alive[1, 2] = False  # cohort step 1, slot 2 is a dead client
    fed_a, _ = cross(fed_a, x, y, mask, sizes, alive)

    # arm b: same data but the dead member's size forced to 0 AND its
    # shard replaced by garbage — must not matter
    sizes_b = sizes.copy()
    sizes_b[1, 2] = 0
    x_b = x.copy()
    x_b[1, 2] = 999.0
    fed_b, _ = cross(fed_b, x_b, y, mask, sizes_b, alive)
    for a, b in zip(jax.tree.leaves(fed_a.states.params),
                    jax.tree.leaves(fed_b.states.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------
# sampler: determinism, no replacement, weighting
# --------------------------------------------------------------------

def test_sample_clients_deterministic_across_processes():
    """The (seed, round) key fully determines the draw — a separate
    interpreter must reproduce it exactly (restart/multi-process
    agreement without coordination)."""
    here = sample_clients(1000, 64, round_num=5, seed=42)
    code = (
        "import json\n"
        f"import sys; sys.path.insert(0, {str((__import__('pathlib').Path(__file__).resolve().parent.parent))!r})\n"
        "from p2pfl_tpu.federation.sampling import sample_clients\n"
        "print(json.dumps(sample_clients(1000, 64, round_num=5, "
        "seed=42).tolist()))\n"
    )
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr[-500:]
    there = json.loads(res.stdout.strip().splitlines()[-1])
    assert here.tolist() == there


def test_sample_clients_no_replacement_and_round_variation():
    for r in range(5):
        s = sample_clients(100, 60, round_num=r, seed=0)
        assert len(np.unique(s)) == 60  # no repeats within a round
        assert s.min() >= 0 and s.max() < 100
    a = sample_clients(100, 60, round_num=0, seed=0)
    b = sample_clients(100, 60, round_num=1, seed=0)
    assert not np.array_equal(a, b)  # rounds draw differently
    assert np.array_equal(a, sample_clients(100, 60, 0, seed=0))


def test_sample_clients_weighted_proportions():
    """Data-size weighting: over many rounds, a client with 4x the
    weight is drawn ~4x as often; zero-weight clients never appear."""
    n, k = 40, 8
    weights = np.ones(n)
    weights[0] = 0.0  # never drawn
    heavy = np.arange(1, 9)
    weights[heavy] = 4.0
    counts = np.zeros(n)
    rounds = 400
    for r in range(rounds):
        s = sample_clients(n, k, round_num=r, seed=9, weights=weights)
        counts[s] += 1
    assert counts[0] == 0
    light = np.setdiff1d(np.arange(1, n), heavy)
    ratio = counts[heavy].mean() / counts[light].mean()
    assert 2.5 < ratio < 6.0, ratio  # ~4x with sampling noise


def test_sample_clients_fail_loud():
    with pytest.raises(ValueError, match="cannot sample"):
        sample_clients(4, 5, round_num=0)
    with pytest.raises(ValueError, match="positive"):
        sample_clients(4, 3, 0, weights=np.array([1.0, 1.0, 0.0, 0.0]))
    with pytest.raises(ValueError, match="shape"):
        sample_clients(4, 2, 0, weights=np.ones(3))


# --------------------------------------------------------------------
# fault composition: sampled-but-dead drops from the cohort
# --------------------------------------------------------------------

def test_dead_client_drops_from_cohort_via_fault_event():
    """A FaultEvent crash on the virtual clock: the client is still
    SAMPLED (the draw stays reproducible from (seed, round) alone) but
    rides the cohort with alive=False — zero training gate, zero
    FedAvg weight."""
    from p2pfl_tpu.federation.scenario import CrossDeviceScenario

    cfg = ScenarioConfig.from_dict({
        "name": "crossdev-fault", "n_nodes": 4,
        "model": {"model": "mlp"},
        "data": {"dataset": "mnist", "synthetic_train": 1024,
                 "synthetic_test": 128, "batch_size": 16},
        "training": {"rounds": 2, "eval_every": 0},
        # eviction within the faulted round: one heartbeat period
        # advances past node_timeout_s of silence
        "protocol": {"heartbeat_period_s": 1.0, "node_timeout_s": 0.5},
        "cross_device": {"n_clients": 16, "clients_per_round": 16,
                         "cohort_size": 4, "seed": 1},
        "faults": [{"round": 0, "node": 3, "kind": "crash"},
                   {"round": 1, "node": 3, "kind": "recover"}],
    })
    sc = CrossDeviceScenario(cfg)
    res = sc.run(rounds=1)
    # K == N: every client (incl. the dead one) is in the round
    assert sorted(sc.last_sampled.tolist()) == list(range(16))
    dead_pos = sc.last_cohorts == 3
    assert dead_pos.sum() == 1
    assert not sc.last_cohort_alive[dead_pos].any()
    assert sc.last_cohort_alive[~dead_pos].all()
    # recover fault: next round the client rides alive again
    sc.run(rounds=1)
    assert sc.last_cohort_alive.all()
    assert res.rounds_run == 1
    sc.close()


# --------------------------------------------------------------------
# config plumbing
# --------------------------------------------------------------------

def test_cross_device_config_validation():
    cd = CrossDeviceConfig(n_clients=1000, clients_per_round=64,
                           cohort_size=8)
    assert cd.active and cd.n_slots == 8
    assert not CrossDeviceConfig().active
    with pytest.raises(ValueError, match="cohort_size"):
        CrossDeviceConfig(n_clients=100, clients_per_round=10,
                          cohort_size=3)
    with pytest.raises(ValueError, match="sampling"):
        CrossDeviceConfig(n_clients=100, clients_per_round=10,
                          cohort_size=5, sampling="magic")
    with pytest.raises(ValueError, match="clients_per_round"):
        CrossDeviceConfig(n_clients=10, clients_per_round=20,
                          cohort_size=2)
    # round 20 knobs: shard divisibility, prefetch enum, axis exclusion
    assert CrossDeviceConfig(n_clients=100, clients_per_round=16,
                             cohort_size=4, cohort_shards=2).active
    with pytest.raises(ValueError, match="cohort_shards"):
        CrossDeviceConfig(n_clients=100, clients_per_round=10,
                          cohort_size=5, cohort_shards=3)
    with pytest.raises(ValueError, match="prefetch"):
        CrossDeviceConfig(n_clients=100, clients_per_round=10,
                          cohort_size=5, prefetch="magic")
    with pytest.raises(ValueError, match="does not compose"):
        CrossDeviceConfig(n_clients=100, clients_per_round=16,
                          cohort_size=4, cohort_shards=2,
                          prefetch="stream")


def test_scenario_classes_fail_loud_on_wrong_regime():
    from p2pfl_tpu.federation.scenario import (
        CrossDeviceScenario,
        Scenario,
    )

    cd_cfg = ScenarioConfig.from_dict({
        "name": "x", "n_nodes": 4,
        "cross_device": {"n_clients": 64, "clients_per_round": 8,
                         "cohort_size": 2},
    })
    with pytest.raises(ValueError, match="CrossDeviceScenario"):
        Scenario(cd_cfg)
    with pytest.raises(ValueError, match="n_clients"):
        CrossDeviceScenario(ScenarioConfig(name="y", n_nodes=4))


# --------------------------------------------------------------------
# lazy partitions + cross-device data
# --------------------------------------------------------------------

def test_lazy_partition_iid_coverage_disjoint():
    labels = np.random.default_rng(0).integers(0, 10, 1000)
    part = lazy_partition_indices(labels, 50, scheme="iid", seed=3)
    assert isinstance(part, ClientPartition)
    assert part.n_clients == 50
    assert (part.sizes() == 20).all()
    seen = np.concatenate([part.client_indices(i) for i in range(50)])
    assert len(np.unique(seen)) == len(seen)  # disjoint
    # deterministic in seed
    again = lazy_partition_indices(labels, 50, scheme="iid", seed=3)
    assert np.array_equal(part.order, again.order)


def test_lazy_partition_dirichlet_large_n():
    """The vectorized assignment path at cross-device width: full
    coverage, disjoint shards, min_per_client respected, seeded."""
    labels = np.random.default_rng(1).integers(0, 10, 8000)
    part = lazy_partition_indices(labels, 600, scheme="dirichlet",
                                  seed=5, alpha=0.5)
    assert part.n_clients == 600
    assert part.sizes().min() >= 1
    assert part.sizes().sum() == 8000
    all_idx = np.sort(part.order)
    assert np.array_equal(all_idx, np.arange(8000))
    again = lazy_partition_indices(labels, 600, scheme="dirichlet",
                                   seed=5, alpha=0.5)
    assert np.array_equal(part.order, again.order)
    assert np.array_equal(part.offsets, again.offsets)


def test_lazy_partition_dirichlet_sparse_regime_repairs():
    """10k clients on a 60k-sample dataset (the README quickstart
    shape): ~6 samples/client means no redraw can ever give every node
    the floor — the vectorized path must repair the draw instead of
    exhausting its budget, and still raise when the floor is
    arithmetically infeasible."""
    labels = np.random.default_rng(3).integers(0, 10, 60_000)
    part = lazy_partition_indices(labels, 10_000, scheme="dirichlet",
                                  seed=0, alpha=0.5)
    sizes = part.sizes()
    assert sizes.min() >= 1
    assert sizes.sum() == 60_000
    assert np.array_equal(np.sort(part.order), np.arange(60_000))
    again = lazy_partition_indices(labels, 10_000, scheme="dirichlet",
                                   seed=0, alpha=0.5)
    assert np.array_equal(part.order, again.order)
    # Repair moves only surplus: the distribution stays non-IID.
    assert sizes.max() > 3 * sizes.mean()
    with pytest.raises(RuntimeError, match="at least"):
        lazy_partition_indices(labels[:4000], 10_000, scheme="dirichlet",
                               seed=0, alpha=0.5)


def test_dirichlet_partition_vectorized_path_matches_law():
    """n_nodes >= 512 takes the vectorized path: every node covered,
    every sample assigned exactly once, deterministic in seed. (The
    small-N path keeps the legacy draw order byte-for-byte — its
    outputs are pinned by the existing dataset tests.)"""
    labels = np.random.default_rng(2).integers(0, 10, 6000)
    parts = dirichlet_partition(labels, 512, alpha=0.5, seed=11)
    assert len(parts) == 512
    assert min(len(p) for p in parts) >= 2
    seen = np.sort(np.concatenate(parts))
    assert np.array_equal(seen, np.arange(6000))
    again = dirichlet_partition(labels, 512, alpha=0.5, seed=11)
    for a, b in zip(parts, again):
        assert np.array_equal(a, b)


# --------------------------------------------------------------------
# round 20: sharded cohort scan + streamed client state
# --------------------------------------------------------------------

_ULPS = 4.0  # float32 ulp of a leaf's largest magnitude; docstring below


def test_sharded_scan_parity_and_zero_recompiles():
    """ISSUE 18 acceptance gate: the shard_map arm (cohort chunks
    mapped over the cohorts mesh axis) must equal the single-device
    scan of the SAME chunked schedule — params AND optimizer state —
    and neither arm may recompile after warm-up under per-round
    resampling. Runs in a subprocess with 4 forced host devices (the
    flag only takes effect pre-jax-init).

    The two arms are two differently compiled programs, and XLA fuses
    (and contracts multiply-adds in) the same body differently inside a
    ``shard_map`` shard than at top level: docs/perf.md §19.1 concedes
    about 1 ulp between them. So the comparison is to a written
    tolerance, not to bit equality: every leaf within ``_ULPS`` float32
    ulp of the leaf's largest magnitude (elementwise ulp would be
    meaningless for entries that cancel to near zero). Read on this
    jax/XLA:CPU over three seeds and six rounds: parameters at most
    1.15 ulp, optimizer state 0. The faults the pin is for read, on the
    same scale, 2.5e6 ulp or more: a chunk dropped (parameters 2.5e6),
    the chunks in reverse order (optimizer state 1.2e7), momentum
    threaded across chunks (``cohort_shards=2``: parameters 5.0e6)."""
    import os

    code = r"""
import os, json
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4").strip()
import jax, numpy as np, jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")
import sys; sys.path.insert(0, %r)
from p2pfl_tpu.config.schema import ModelConfig
from p2pfl_tpu.learning.learner import make_step_fns
from p2pfl_tpu.models.base import build_model
from p2pfl_tpu.obs import trace as obs_trace
from p2pfl_tpu.parallel.federated import (build_round_fn_cross_device,
                                          init_federation)
from p2pfl_tpu.parallel.mesh import cohort_shard_mesh

assert jax.device_count() == 4
fns = make_step_fns(build_model(ModelConfig(model="mlp")), batch_size=8)
n, s, c = 4, 8, 4  # c divisible by the 4 shards
rng = np.random.default_rng(18)

def draw():
    x = rng.normal(size=(c, n, s, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(c, n, s)).astype(np.int32)
    mask = np.ones((c, n, s), bool)
    sizes = rng.integers(1, s + 1, size=(c, n)).astype(np.int32)
    alive = rng.random((c, n)) > 0.2
    alive[0, 0] = True
    return x, y, mask, sizes, alive

single = jax.jit(build_round_fn_cross_device(fns, epochs=1,
                                             cohort_shards=4))
sharded = jax.jit(build_round_fn_cross_device(
    fns, epochs=1, cohort_shards=4, cohort_mesh=cohort_shard_mesh(4)))
x0 = draw()[0]
fed_a = init_federation(fns, jnp.asarray(x0[0, 0, :1]), n, seed=18)
fed_b = init_federation(fns, jnp.asarray(x0[0, 0, :1]), n, seed=18)

def to_host(fed):
    # normalize feedback placement: the mesh arm's outputs are
    # mesh-sharded, and feeding them straight back would retrace the
    # jit as a different-layout SPMD program (the scenario manages
    # placement through its transport; here the gate is the round
    # FUNCTION, so every call gets host arrays = one program)
    return jax.tree.map(
        lambda t: np.asarray(t) if hasattr(t, "shape") else t, fed)

def gap_ulps(ta, tb):
    worst = 0.0
    for a, b in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype.kind != "f":
            assert np.array_equal(a, b)
            continue
        assert a.dtype == np.float32, a.dtype
        scale = float(np.abs(a).max()) * float(np.finfo(np.float32).eps)
        d = float(np.abs(a.astype(np.float64) - b.astype(np.float64)).max())
        if d:
            worst = max(worst, d / scale)
    return worst

assert obs_trace.install_xla_listener() is True
params_gap = opt_gap = 0.0
for r in range(3):
    batch = draw()
    fed_a, la = single(fed_a, *batch)
    fed_b, lb = sharded(fed_b, *batch)
    if r == 0:  # warm-up round compiled both arms; count from here
        jax.block_until_ready((fed_a, fed_b))
        obs_trace.reset_xla_counters()
    params_gap = max(params_gap, gap_ulps(fed_a.states.params,
                                          fed_b.states.params))
    opt_gap = max(opt_gap, gap_ulps(fed_a.states.opt_state,
                                    fed_b.states.opt_state))
    fed_a, fed_b = to_host(fed_a), to_host(fed_b)
print("VERDICT " + json.dumps({
    "params_gap_ulps": params_gap, "opt_gap_ulps": opt_gap,
    "recompiles": obs_trace.xla_recompiles()}))
""" % (str(__import__("pathlib").Path(__file__).resolve().parent.parent),)
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # the child pins cpu itself
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    verdict = next(json.loads(ln[len("VERDICT "):])
                   for ln in res.stdout.splitlines()
                   if ln.startswith("VERDICT "))
    assert verdict["params_gap_ulps"] <= _ULPS, verdict
    assert verdict["opt_gap_ulps"] <= _ULPS, verdict
    assert verdict["recompiles"] == 0, verdict


def test_sharded_chunked_dead_client_zero_weight():
    """Dead-client invariance survives sharding: with cohort_shards=2
    (the chunked schedule every mesh arm is bit-equal to), a dead
    cohort member's data is inert — zeroing its size and garbaging its
    shard changes nothing."""
    from p2pfl_tpu.parallel.federated import (
        build_round_fn_cross_device,
        init_federation,
    )

    n, s, c = 4, 8, 2
    rng = np.random.default_rng(7)
    x = rng.normal(size=(c, n, s, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(c, n, s)).astype(np.int32)
    mask = np.ones((c, n, s), bool)
    sizes = np.full((c, n), s, np.int32)

    fns = _mk_fns()
    cross = jax.jit(build_round_fn_cross_device(fns, epochs=1,
                                                cohort_shards=2))
    fed_a = init_federation(fns, jnp.asarray(x[0, 0, :1]), n, seed=3)
    fed_b = init_federation(fns, jnp.asarray(x[0, 0, :1]), n, seed=3)

    alive = np.ones((c, n), bool)
    alive[1, 2] = False  # second chunk's cohort, slot 2 dead
    fed_a, _ = cross(fed_a, x, y, mask, sizes, alive)

    sizes_b = sizes.copy()
    sizes_b[1, 2] = 0
    x_b = x.copy()
    x_b[1, 2] = 999.0
    fed_b, _ = cross(fed_b, x_b, y, mask, sizes_b, alive)
    for a, b in zip(jax.tree.leaves(fed_a.states.params),
                    jax.tree.leaves(fed_b.states.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_sample_cohorts_prefetch_order_deterministic():
    """The streamed driver's prefetch order IS the cohort order, and
    that order is a pure function of (seed, round): same key, same
    cohorts; different round, different draw; and the cohort matrix is
    exactly the flat K-draw reshaped row-major (cohort t = the t-th
    consecutive slot-block), so host gather order never drifts from
    the compiled schedule."""
    from p2pfl_tpu.federation.sampling import sample_cohorts

    sampled, cohorts = sample_cohorts(1000, 64, 8, round_num=5, seed=42)
    again_s, again_c = sample_cohorts(1000, 64, 8, round_num=5, seed=42)
    assert np.array_equal(sampled, again_s)
    assert np.array_equal(cohorts, again_c)
    assert cohorts.shape == (8, 8)
    assert np.array_equal(cohorts.reshape(-1), sampled)
    # the flat draw is the round-13 sampler verbatim — resampling
    # changes the draw (and therefore the prefetch order) per round
    assert np.array_equal(sampled,
                          sample_clients(1000, 64, round_num=5, seed=42))
    other, _ = sample_cohorts(1000, 64, 8, round_num=6, seed=42)
    assert not np.array_equal(sampled, other)
    with pytest.raises(ValueError, match="cohort_size"):
        sample_cohorts(1000, 64, 7, round_num=0, seed=0)


def test_cohort_batch_buffer_reuse_identical_values():
    """cohort_batch(out=...) into a dirty reused buffer materializes
    the same values as a fresh allocation — the streamed double buffer
    cannot change round math."""
    from p2pfl_tpu.config.schema import DataConfig
    from p2pfl_tpu.datasets.data import CrossDeviceData

    data = CrossDeviceData.make(
        DataConfig(dataset="mnist", synthetic_train=2048,
                   synthetic_test=128, samples_per_node=16),
        n_clients=64,
    )
    ids_a = np.array([3, 17, 41, 60])
    ids_b = np.array([5, 5, 2, 63])
    fresh_a = data.cohort_batch(ids_a)
    fresh_b = data.cohort_batch(ids_b)
    bufs = data.cohort_buffers(4)
    bufs[0][:] = 123.0  # dirty the buffer: stale rows must be erased
    bufs[1][:] = 9
    bufs[2][:] = True
    bufs[3][:] = 99
    reused_a = data.cohort_batch(ids_a, out=bufs)
    for f, r in zip(fresh_a, reused_a):
        assert np.array_equal(f, r)
    reused_b = data.cohort_batch(ids_b, out=bufs)  # second fill, same buffer
    for f, r in zip(fresh_b, reused_b):
        assert np.array_equal(f, r)
    assert reused_b[0] is bufs[0]  # in place, not a copy
    # O(1) size lookup agrees with the materialized mask
    assert np.array_equal(data.cohort_sizes(ids_b),
                          reused_b[2].sum(axis=1).astype(np.int32))


def test_streamed_round_parity_with_materialized():
    """prefetch="stream" is a data-movement change, not a math change:
    the streamed scenario must match the materialize-everything
    scenario bit-for-bit on every param leaf at every round, under
    per-round resampling and a mid-run fault."""
    from p2pfl_tpu.federation.scenario import CrossDeviceScenario

    def cfg(prefetch):
        return ScenarioConfig.from_dict({
            "name": f"crossdev-{prefetch}", "n_nodes": 4,
            "model": {"model": "mlp"},
            "data": {"dataset": "mnist", "synthetic_train": 1024,
                     "synthetic_test": 128, "batch_size": 16,
                     "samples_per_node": 8},
            "training": {"rounds": 2, "eval_every": 0},
            "cross_device": {"n_clients": 100, "clients_per_round": 16,
                             "cohort_size": 4, "seed": 1,
                             "prefetch": prefetch},
            "faults": [{"round": 1, "node": 2, "kind": "crash"}],
        })

    sc_off = CrossDeviceScenario(cfg("off"))
    sc_on = CrossDeviceScenario(cfg("stream"))
    for _ in range(2):
        sc_off.run(rounds=1)
        sc_on.run(rounds=1)
        for a, b in zip(jax.tree.leaves(sc_off.fed.states.params),
                        jax.tree.leaves(sc_on.fed.states.params)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    # the streamed driver published its throughput + prefetch gauges
    assert sc_on.crossdev_last.get("crossdev_prefetch_mb") is not None
    assert sc_on.crossdev_last.get("crossdev_prefetch_stall_s") is not None
    sc_off.close()
    sc_on.close()


@pytest.mark.slowtier
def test_sgd_accum_routed_scan_parity():
    """With the Pallas gate forced on, the fused accumulate routes the
    per-leaf FedAvg partial sum through pallas_gemm.sgd_accum (null
    step, acc+weight only). The routed round must match the unfused
    gemm reference to float32 tolerance (the reduction is reassociated,
    so this is allclose, not bit-equal — the bit-equal contract is the
    XLA-routed path, pinned above), and the gate must have recorded
    pallas decisions for sgd_accum. Subprocess: the choose() cache is
    process-wide, so the forced knob needs a fresh interpreter.

    slowtier (~4s fresh-interpreter compile): the routed kernel's
    numerics have fast op-level pins (test_pallas_gemm.py's
    test_sgd_accum_update_parity / test_sgd_accum_fused_accumulate_
    parity), and the fused-vs-unfused ROUND parity is pinned bit-equal
    on the XLA path above; this composition re-proof runs on the
    P2PFL_SLOW_TESTS=1 tier."""
    import os

    code = r"""
import os, json
import jax, numpy as np, jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")
import sys; sys.path.insert(0, %r)
from p2pfl_tpu.config.schema import ModelConfig
from p2pfl_tpu.learning.learner import make_step_fns
from p2pfl_tpu.models.base import build_model
from p2pfl_tpu.ops import pallas_gemm
from p2pfl_tpu.parallel.federated import (build_round_fn_cross_device,
                                          init_federation)

fns = make_step_fns(build_model(ModelConfig(model="mlp")), batch_size=8)
n, s, c = 4, 8, 3
rng = np.random.default_rng(21)
x = rng.normal(size=(c, n, s, 28, 28, 1)).astype(np.float32)
y = rng.integers(0, 10, size=(c, n, s)).astype(np.int32)
mask = np.ones((c, n, s), bool)
sizes = rng.integers(1, s + 1, size=(c, n)).astype(np.int32)
alive = np.ones((c, n), bool)
alive[2, 1] = False

fused = jax.jit(build_round_fn_cross_device(fns, epochs=1,
                                            fused_accumulate=True))
unfused = jax.jit(build_round_fn_cross_device(fns, epochs=1,
                                              fused_accumulate=False))
fed_f = init_federation(fns, jnp.asarray(x[0, 0, :1]), n, seed=5)
fed_u = init_federation(fns, jnp.asarray(x[0, 0, :1]), n, seed=5)
# parity is judged after ONE round: the reassociated reduction is a
# ~1-ulp effect there, while further rounds amplify it through the
# training dynamics (same float, different trajectory)
fed_f, _ = fused(fed_f, x, y, mask, sizes, alive)
fed_u, _ = unfused(fed_u, x, y, mask, sizes, alive)
max_diff, ok = 0.0, True
for a, b in zip(jax.tree.leaves(fed_f.states.params),
                jax.tree.leaves(fed_u.states.params)):
    a, b = np.asarray(a), np.asarray(b)
    max_diff = max(max_diff, float(np.abs(a - b).max()))
    ok &= bool(np.allclose(a, b, rtol=1e-5, atol=1e-6))
fed_f, _ = fused(fed_f, x, y, mask, sizes, alive)  # second round runs clean
dec = {k: v for k, v in pallas_gemm.decisions().items()
       if k.startswith("sgd_accum")}
print("VERDICT " + json.dumps({
    "ok": ok, "max_diff": max_diff,
    "pallas_routed": any(v.get("impl") == "pallas" for v in dec.values()),
    "n_decisions": len(dec)}))
""" % (str(__import__("pathlib").Path(__file__).resolve().parent.parent),)
    env = dict(os.environ)
    env["P2PFL_PALLAS_GEMM"] = "on"  # forced: interpret-mode on CPU
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    verdict = next(json.loads(ln[len("VERDICT "):])
                   for ln in res.stdout.splitlines()
                   if ln.startswith("VERDICT "))
    assert verdict["pallas_routed"], verdict  # the gate actually fired
    assert verdict["ok"], f"pallas-routed accumulate drifted: {verdict}"


@pytest.mark.slowtier
def test_streamed_100k_peak_rss_bounded():
    """The N=100k streamed acceptance gate: a round completes at
    100,000 virtual clients while the host materializes exactly TWO
    cohort buffers (identity-stable across rounds), and peak RSS stays
    flat once warm — the residency bound that makes N=100k-1M a
    config choice, not a memory budget. Subprocess: ru_maxrss is a
    process-lifetime high-water mark, so the gate needs a fresh
    interpreter. Slow tier (~40s: four 100k-client streamed rounds);
    the two-buffer residency mechanism itself is covered fast by
    test_streamed_round_parity_with_materialized and
    test_cohort_batch_buffer_reuse_identical_values."""
    import os

    code = r"""
import json, resource
import jax
jax.config.update("jax_platforms", "cpu")
import sys; sys.path.insert(0, %r)
from p2pfl_tpu.config.schema import (CrossDeviceConfig, DataConfig,
                                     ScenarioConfig, TrainingConfig)
from p2pfl_tpu.federation.scenario import CrossDeviceScenario

cfg = ScenarioConfig(
    name="crossdev100k", n_nodes=4,
    data=DataConfig(dataset="mnist", synthetic_train=100_000,
                    synthetic_test=1000, batch_size=32),
    training=TrainingConfig(rounds=4, epochs_per_round=1,
                            learning_rate=0.1, eval_every=0),
    cross_device=CrossDeviceConfig(
        n_clients=100_000, clients_per_round=256, cohort_size=32,
        sampling="uniform", seed=0, prefetch="stream"),
    seed=0,
)
sc = CrossDeviceScenario(cfg)
sc.run(rounds=1)  # warm-up: compile + allocate the double buffer
rss_warm_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
bufs_before = [id(a) for a in sc._stream_bufs[0]] + [id(a) for a in sc._stream_bufs[1]]
sc.run(rounds=3)  # streamed rounds: residency must not grow
bufs_after = [id(a) for a in sc._stream_bufs[0]] + [id(a) for a in sc._stream_bufs[1]]
rss_peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
print("VERDICT " + json.dumps({
    "n_bufs": len(sc._stream_bufs),
    "bufs_stable": bufs_before == bufs_after,
    "growth_mb": round((rss_peak_kb - rss_warm_kb) / 1024, 1),
    "round_done": True}))
sc.close()
""" % (str(__import__("pathlib").Path(__file__).resolve().parent.parent),)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    verdict = next(json.loads(ln[len("VERDICT "):])
                   for ln in res.stdout.splitlines()
                   if ln.startswith("VERDICT "))
    assert verdict["n_bufs"] == 2, verdict  # exactly two cohorts resident
    assert verdict["bufs_stable"], verdict  # reused, never reallocated
    # warm steady state: streamed rounds add no per-round residency
    # (measured 0.0 on the dev box; 128 MB absorbs allocator noise)
    assert verdict["growth_mb"] <= 128.0, verdict


def test_cross_device_data_cohort_batch_shapes_and_determinism():
    from p2pfl_tpu.config.schema import DataConfig
    from p2pfl_tpu.datasets.data import CrossDeviceData

    data = CrossDeviceData.make(
        DataConfig(dataset="mnist", synthetic_train=2048,
                   synthetic_test=128, samples_per_node=16),
        n_clients=64,
    )
    assert data.n_clients == 64
    assert data.shard_size == 16
    ids = np.array([3, 17, 3, 60])
    x, y, mask, sizes = data.cohort_batch(ids)
    assert x.shape == (4, 16) + data.input_shape
    assert y.shape == mask.shape == (4, 16)
    assert sizes.shape == (4,)
    assert (sizes <= 16).all() and (sizes > 0).all()
    assert (mask.sum(axis=1) == sizes).all()
    # same client id materializes identically (seeded shuffle)
    assert np.array_equal(x[0], x[2]) and np.array_equal(y[0], y[2])
    # client_sizes caps at the fixed shard size
    assert (data.client_sizes <= data.shard_size).all()
