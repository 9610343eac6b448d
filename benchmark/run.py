"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run = one process = one cell of ``BENCHMARK.json``. The cell's
configuration file, traffic file, limits file, reference module, FLOP
count and readers are found by the names in ``BENCHMARK.json`` and in
the configuration file; nothing here names a cell or a model, so a new
one comes as new files and entries (``PERF.md``, "Adding a
configuration").

Set-up builds the cell's ``ScenarioConfig`` from those files, builds
the program's ``Scenario``, hands it weights and row-order keys made
from ``--seed`` (the reference makes the same), and drives it through
its first rounds with ``Scenario.run()``: that compiles (or loads) the
round and evaluation programs and yields what ``correct`` compares. The
window is ONE more ``Scenario.run(rounds=R)`` on the same object and
``E - 1`` further ``Scenario.evaluate()`` calls, timed from outside. The
plain reference runs after the window, once the peak memory is read and
the program's state is freed.

``--rehearse-cpu`` drives the same path at toy sizes on any backend and
prints under the device name it ran on; it fills no device metric.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import functools
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import statistics
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(HERE))


class BenchFailure(Exception):
    pass


# --------------------------------------------------------------------------
# data files


def load_json(path):
    return json.loads(pathlib.Path(path).read_text())


def merged(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """Everything ``BENCHMARK.json`` and the data files say of one cell.
    ``home`` holds the files found by name (``traffic/``, ``cells/``,
    ``reference/``, ``counts/``, ``readers/``); the tests keep a
    benchmark of fixture files in a home of their own."""

    def __init__(self, workload: str, rehearse: bool,
                 bench=ROOT / "BENCHMARK.json", home=HERE):
        self.bench = load_json(bench)
        self.home = home = pathlib.Path(home)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise BenchFailure(f"no workload {workload!r}; have {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = self.entry["chips"]
        cfg_entry = next(c for c in self.bench["configs"]
                         if c["name"] == self.entry["config"])
        self.config = load_json(ROOT / cfg_entry["file"])
        self.traffic = load_json(home / "traffic" / f"{self.entry['traffic']}.json")
        limits = load_json(home / "cells" / f"{workload}.json")
        # the CPU's own bf16 arithmetic reads some norms further off than
        # the chip's does: a rehearsal may carry limits of its own
        self.limits = merged(limits["limits"], limits.get(
            "rehearse_limits", {}) if rehearse else {})
        self.rehearse = rehearse
        scen = merged(self.config["scenario"], self.traffic["scenario"])
        if rehearse:
            scen = merged(scen, self.config.get("rehearse", {}).get("scenario", {}))
            toy = self.traffic.get("rehearse", {})
            scen = merged(scen, toy.get("scenario", {}))
            self.traffic = merged(self.traffic, toy.get("traffic", {}))
        self.scenario = scen
        self.n_nodes = scen["n_nodes"]

    def metrics(self, group):
        """Metric entries of ``group`` that this cell reports."""
        return [m for m in self.bench[group]
                if self.name in m.get("workloads", [self.name])]

    def scenario_config(self, seed: int):
        from p2pfl_tpu.config.schema import ScenarioConfig

        d = merged(self.scenario, {
            "name": self.name, "seed": seed,
            "data": {"seed": seed, "synthetic_train":
                     self.n_nodes * self.config["synthetic_train_per_node"]},
            "training": {"rounds": self.traffic["followed_rounds"]},
        })
        return ScenarioConfig.from_dict(d)

    def reference_spec(self):
        prec = self.config["precision"]
        ref = merged(self.config["reference"], self.traffic["reference"])
        ref.update(
            n_nodes=self.n_nodes,
            batch_size=self.scenario["data"]["batch_size"],
            epochs=self.scenario["training"]["epochs_per_round"],
            param_dtype=prec["param_dtype"], moment_dtype=prec["moment_dtype"])
        return ref

    def reference_model(self):
        return load_module(
            self.home / "reference" / f"{self.config['reference']['module']}.py",
            "bench_ref_model")


# --------------------------------------------------------------------------
# the program under test, driven from outside


def seed_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path)


def named_leaves(tree, param_map, shapes, lead=0):
    """The program's ``tree`` by the reference's names: the leaves, the
    tree's structure and each leaf's name in ``param_map``; a leaf whose
    shape past its ``lead`` axes is not the reference's is refused."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = [param_map[path_str(p)] for p, _ in flat]
    for (p, leaf), name in zip(flat, names):
        if tuple(leaf.shape[lead:]) != tuple(shapes[name]):
            raise BenchFailure(
                f"{path_str(p)} is {leaf.shape[lead:]}, reference {name} "
                f"is {shapes[name]}")
    return [leaf for _, leaf in flat], treedef, names


class Driven:
    """The program's Scenario with the benchmark's probes on it."""

    def __init__(self, cell: Cell, seed: int):
        import jax
        import jax.numpy as jnp
        import numpy as np

        import p2pfl_tpu.federation as federation
        from p2pfl_tpu.federation.events import Events

        self.cell, self.Events = cell, Events
        self.key = seed_key(seed)
        cfg = cell.scenario_config(seed % 2147483647)
        t0 = time.monotonic()
        self.sc = getattr(federation, cell.traffic["scenario_class"])(cfg)
        sc = self.sc
        #: seconds in the program's constructor: data synthesis, placement,
        #: ``init_federation``, the kernel gate's measurements
        self.scenario_s = time.monotonic() - t0

        # ---- weights and row-order keys from the seed, in one jitted call
        model = cell.reference_model()
        leaves, treedef, names = named_leaves(
            sc.fed.states.params, cell.config["param_map"], model.SHAPES, lead=1)
        self.order = sorted(range(len(names)), key=lambda i: names[i])
        n = cell.n_nodes

        def make(key):
            ref = model.init(key)
            return [jnp.broadcast_to(ref[nm].astype(l.dtype)[None], l.shape)
                    for nm, l in zip(names, leaves)]

        rng0 = sc.fed.states.rng
        out = jax.jit(
            lambda key: (make(key), jax.random.split(
                jax.random.fold_in(key, 1), n).astype(rng0.dtype)),
            out_shardings=([l.sharding for l in leaves], rng0.sharding),
        )(self.key)
        if out[1].shape != rng0.shape:
            raise BenchFailure(f"rng state is {rng0.shape}, made {out[1].shape}")
        sc.fed = sc.fed.replace(states=sc.fed.states.replace(
            params=jax.tree_util.tree_unflatten(treedef, out[0]), rng=out[1]))

        # ---- what the reference will be given: the inputs, on the host
        x, y, smask, nsamp = sc.dataset.stacked()
        self.inputs = dict(key=self.key, rngs=np.asarray(out[1]),
                           x=x, y=y, mask=smask, n_samples=nsamp,
                           x_test=np.asarray(sc.dataset.x_test),
                           y_test=np.asarray(sc.dataset.y_test))
        # ---- the frozen part, where the configuration states one: the
        # program's own arrays, once, on the device, in their stored type
        frozen = cell.config.get("frozen")
        if frozen:
            held, _, held_names = named_leaves(
                functools.reduce(getattr, frozen["from"].split("."), sc),
                frozen["param_map"], model.FROZEN_SHAPES)
            self.inputs["frozen"] = dict(zip(held_names, held))

        # ---- probes
        def norms(tree_leaves):
            rows = [jnp.sqrt(jnp.sum(jnp.square(
                tree_leaves[i].astype(jnp.float32)).reshape(n, -1), axis=1))
                for i in self.order]
            return jnp.stack(rows)

        moment_attr = cell.config["first_moment"]
        self._moment_norms = jax.jit(lambda opt_state: norms(
            jax.tree_util.tree_leaves(getattr(opt_state[0], moment_attr))))
        self._change_norms = jax.jit(lambda params, key: norms([
            a.astype(jnp.float32) - b.astype(jnp.float32) for a, b in zip(
                jax.tree_util.tree_leaves(params), make(key))]))
        self.seen: dict = {}
        self.follow_until = None
        self.spans = False
        self._open: list = []
        self.eval_times: list[tuple[float, float]] = []
        self.eval_out: list[dict] = []
        inner = sc.evaluate

        def timed_evaluate():
            with self._span("bench.evaluate"):
                t0 = time.monotonic()
                out = inner()
                self.eval_times.append((t0, time.monotonic()))
            self.eval_out.append(out)
            return out

        sc.evaluate = timed_evaluate
        sc.add_observer(self._on_event)

    # spans go into the profiler's own trace, on the device's clock
    def _span(self, name):
        import contextlib

        import jax

        return jax.profiler.TraceAnnotation(name) if self.spans \
            else contextlib.nullcontext()

    def _enter(self, name):
        if self.spans:
            span = self._span(name)
            span.__enter__()
            self._open.append(span)

    def _exit(self):
        if self._open:
            self._open.pop().__exit__(None, None, None)

    def _on_event(self, event, payload):
        import numpy as np

        E = self.Events
        if event is E.ROUND_STARTED:
            self._enter("bench.round")
        elif event is E.AGGREGATION_FINISHED:
            self._exit()
            self._enter("bench.post_round")
        elif event is E.ROUND_FINISHED:
            self._exit()
            if self.follow_until is not None:
                r = payload["round"]
                if r == 0:
                    self.seen["moment"] = np.asarray(self._moment_norms(
                        self.sc.fed.states.opt_state))
                if r == self.follow_until - 1:
                    self.seen["change"] = np.asarray(self._change_norms(
                        self.sc.fed.states.params, self.key))

    def losses(self, result, first_round, rounds):
        import numpy as np

        out = np.full((rounds, self.cell.n_nodes), np.nan)
        for row in result.history:
            r = row.get("round")
            if "Train/loss" in row and row.get("node") is not None \
                    and r is not None and first_round <= r < first_round + rounds:
                out[r - first_round, row["node"]] = row["Train/loss"]
        return out

    def follow(self):
        """The first rounds, through the window's own call: compiles, and
        records what ``correct`` compares."""
        import numpy as np

        w = self.cell.traffic["followed_rounds"]
        # the evaluation pass on the seed's own weights: the forward pass
        # alone, before any step can amplify a rounding
        self.seen["eval0_loss"] = np.asarray(self.sc.evaluate()["per_node_loss"])
        self.follow_until = w
        res = self.sc.run(rounds=w)
        self.follow_until = None
        self.seen["loss"] = self.losses(res, 0, w)
        self.seen["eval_loss"] = np.asarray(self.eval_out[-1]["per_node_loss"])
        res.history.clear()
        self.eval_times.clear()
        self.eval_out.clear()
        return res.round_times_s

    def release(self):
        """Close the program and drop its state and compiled programs, so
        that the reference has the device to itself. Returns what the
        comparison needs: the program's readings and the inputs (a frozen
        part among them stays on the device: the reference computes over
        the same arrays)."""
        import jax

        self.sc.close()
        self.sc = None
        jax.clear_caches()
        return self.seen, self.inputs


# --------------------------------------------------------------------------
# correctness


def reference_readings(cell: Cell, inputs, eval_nodes, q=None, fault=None):
    """The plain reference (or, with ``q``/``fault``, the control or a
    planted fault) over the same inputs."""
    from reference.federation import Federation

    fed = Federation(cell.reference_model(), cell.reference_spec(),
                     q=q, fault=fault, chips=cell.chips)
    return fed.follow(eval_nodes=eval_nodes,
                      rounds=cell.traffic["followed_rounds"], **inputs)


def eval_nodes_of(cell: Cell, seed: int):
    import numpy as np

    k = min(cell.traffic["ref_eval_nodes"], cell.n_nodes)
    return np.sort(np.random.default_rng(seed).choice(
        cell.n_nodes, size=k, replace=False))


# --------------------------------------------------------------------------
# one run


def device_facts(rehearse: bool, chips: int):
    import jax

    devs = jax.devices()
    if not rehearse:
        if jax.default_backend() != "tpu":
            raise BenchFailure(f"no accelerator: backend is {jax.default_backend()}")
        if len(devs) < chips:
            raise BenchFailure(f"cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes():
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return max(peaks) if peaks else 0


def cache_entries(path):
    try:
        return len([f for f in os.listdir(path) if not f.startswith(".")])
    except FileNotFoundError:
        return 0


def run_cell(args, sabotage=None, cell=None) -> dict:
    """Drive one cell; returns the result line as a dict. ``sabotage``
    and ``cell`` are for the tests under ``benchmark/tests``: the first
    is called with the built ``Driven`` before its first round, to break
    the timed path; the second is a ``Cell`` built from fixture files."""
    cell = cell or Cell(args.workload, args.rehearse_cpu)

    import jax

    from p2pfl_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    t_imported = time.monotonic()
    device = device_facts(cell.rehearse, cell.chips)
    t_device = time.monotonic()

    from p2pfl_tpu.obs import trace as obs_trace
    from p2pfl_tpu.ops import pallas_gemm

    import check
    import tracereduce

    obs_trace.install_xla_listener()
    obs_trace.reset_xla_counters()
    entries0 = cache_entries(cache_dir)

    # ---------------- set-up
    t_built = time.monotonic()
    driven = Driven(cell, args.seed)
    if sabotage is not None:
        sabotage(driven)
    sc = driven.sc
    t_seeded = time.monotonic()
    warm_times = driven.follow()
    setup_parts = {  # where set-up goes, for PERF.md's list of what only
        # the program can shorten
        "imports_s": t_imported - T_START, "device_init_s": t_device - t_imported,
        "program_imports_s": t_built - t_device,
        "scenario_s": driven.scenario_s,
        "seed_and_probes_s": t_seeded - t_built - driven.scenario_s,
        "follow_s": time.monotonic() - t_seeded,
    }
    warm_round_s = statistics.median(warm_times[1:] or warm_times)
    counters = {
        "xla_compile_s": obs_trace.xla_compile_seconds(),
        "cache_misses": cache_entries(cache_dir) - entries0,
        "pallas_picked": sum(1 for d in pallas_gemm.decisions().values()
                             if d.get("impl") == "pallas"),
    }
    traffic = cell.traffic
    span_s = min(args.seconds, traffic["trace_seconds"]) if args.trace \
        else args.seconds
    rounds = max(traffic["min_rounds"], round(span_s / warm_round_s))
    evals = traffic["trace_evals"] if args.trace else traffic["evals"]
    first_round = traffic["followed_rounds"]
    trace_dir = ROOT / ".bench_trace" / f"{cell.name}-{args.seed}"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        driven.spans = True
    obs_trace.reset_xla_counters()
    gc_pauses: list[tuple[int, float]] = []  # the interpreter's collections
    gc_t0 = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_t0[0] = time.monotonic()
        else:
            gc_pauses.append((info["generation"], time.monotonic() - gc_t0[0]))

    gc.callbacks.append(on_gc)
    setup_s = time.monotonic() - T_START

    # ---------------- the window
    with driven._span(tracereduce.WINDOW):
        with driven._span("bench.part.rounds"):
            t_enter = time.monotonic()
            result = sc.run(rounds=rounds)
        # run() ends with the one evaluation it always makes; its start
        # closes the rounds
        t_rounds_end = driven.eval_times[0][0]
        with driven._span("bench.part.evaluations"):
            for _ in range(evals - 1):
                sc.evaluate()
        jax.block_until_ready(sc.fed.states.params)
    t_close = time.monotonic()
    gc.callbacks.remove(on_gc)
    recompiles = obs_trace.xla_recompiles()
    if args.trace:
        jax.profiler.stop_trace()
        driven.spans = False
    round_s = (t_rounds_end - t_enter) / rounds
    eval_times = [b - a for a, b in driven.eval_times]
    eval_s = sum(eval_times) / len(eval_times)
    window_losses = driven.losses(result, first_round, rounds)
    round_times = list(result.round_times_s)
    memory_peak = peak_bytes()
    device["memory_peak_bytes"] = memory_peak
    if recompiles:
        raise BenchFailure(f"{recompiles} XLA compiles inside the window")

    import numpy as np

    failed = int(np.sum(~np.isfinite(window_losses).all(axis=1)))
    failed += sum(1 for ev in driven.eval_out
                  if not np.isfinite(ev["per_node_loss"]).all())

    # ---------------- free the program, then the plain reference
    del sc, result
    seen, inputs = driven.release()
    del driven
    eval_nodes = eval_nodes_of(cell, args.seed)
    t_ref = time.monotonic()
    ref = reference_readings(cell, inputs, eval_nodes)
    reference_s = time.monotonic() - t_ref
    where: dict = {}
    compared = check.compare(seen, ref, eval_nodes, cell.limits, where)
    correct = all(v <= lim for v, lim in compared.values()) and failed == 0

    # ---------------- metrics
    metrics = {}
    breakdown = None
    if not args.trace:
        values = {"round_s": round_s, "eval_s": eval_s, "setup_s": setup_s}
        for m in cell.metrics("end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        reduced = tracereduce.reduce(
            tracereduce.load(tracereduce.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is None and not cell.rehearse:
            raise BenchFailure("the trace holds no device operation")
        peaks = load_json(HERE / "peaks.json")
        if not cell.rehearse and device["kind"] not in peaks:
            raise BenchFailure(f"no peak for device kind {device['kind']!r}")
        ctx = {
            "cell": cell, "trace": reduced, "counters": counters,
            "rounds": rounds, "evals": evals, "round_times_s": round_times,
            "rounds_wall_s": t_rounds_end - t_enter,
            "window_losses": window_losses, "first_round": first_round,
            "memory_peak_bytes": memory_peak, "chips": cell.chips,
            "peak": peaks.get(device["kind"]),
            "flops": load_module(HERE / "flops.py", "bench_flops"),
            "rows_per_node": int(inputs["x"].shape[1]),
        }
        for m in cell.metrics("per_layer"):
            reader = load_module(cell.home / "readers" / f"{m['name']}.py",
                                 "bench_reader")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {
                "device_ops": [[n[:120], t] for n, t in reduced["device_ops"]],
                "idle_gaps": [[n, t] for n, t in reduced["idle_gaps"]],
            }

    line = {
        "correct": bool(correct), "attempted": rounds + evals, "failed": failed,
        "metrics": metrics, "device": device,
    }
    if breakdown:
        line["breakdown"] = breakdown
    line["facts"] = {
        "workload": cell.name, "seed": args.seed, "rounds": rounds,
        "evals": evals, "warm_round_s": warm_round_s,
        "window_s": t_close - t_enter, "reference_s": reference_s,
        "eval_times_s": eval_times, "slowest_round_s": max(round_times),
        "slowest_round": round_times.index(max(round_times)),
        "gc_in_window": {"collections": len(gc_pauses),
                         "oldest_generation": sum(g == 2 for g, _ in gc_pauses),
                         "total_s": sum(t for _, t in gc_pauses),
                         "longest_s": max((t for _, t in gc_pauses), default=0.0)},
        "setup_parts": setup_parts,
        "rehearsal": cell.rehearse, "worst_at": where, **counters,
    }
    line["compared"] = {k: {"value": float(v), "limit": lim}
                        for k, (v, lim) in compared.items()}
    return line


def build_parser():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="toy sizes on any backend; prints under that device's name")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    try:
        line = run_cell(args)
    except BenchFailure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    for k, v in line["compared"].items():
        print(f"compared {k}: {v['value']:.6g} (limit {v['limit']:.6g})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
