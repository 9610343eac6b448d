"""Device seconds of a program's named scopes out of a reduced trace,
and a scope's share of its roofline. A scope's name is found inside a
component of an op's path as JAX leaves it there: bare on the way
forward, wrapped (``transpose(jvp(moe.experts))``) on the way back."""

import importlib.util
import re


def seconds(trace, names, program=None):
    """Self seconds of the ops whose scope path names one of ``names``,
    in the compiled program ``program`` (``round_fn``, ``eval_fn``) or
    in any; ``None`` where no op does."""
    named = re.compile(
        r"(?:^|[/(])(?:" + "|".join(map(re.escape, names)) + r")(?:[/)]|$)")
    hits = [t for path, t in trace["scope_s"].items()
            if named.search(path)
            and (program is None or f"jit({program})" in path.split("/"))]
    return sum(hits) if hits else None


def per_round(ctx, *names):
    if ctx["trace"] is None:
        return None
    got = seconds(ctx["trace"], names, "round_fn")
    return None if got is None else got / ctx["rounds"]


def roofline_share(ctx, scope, also=()):
    """100 x the least seconds the chip could take for the scope's
    required work in the traced window (the larger of FLOPs over the
    peak and bytes over the bandwidth; the rounds train, the evaluations
    run forward) over the scope's device seconds there. ``also``: names
    under which the compiler files ops of the scope whose own name it
    drops (XLA:TPU's grouped matmul comes back as ``ragged-dot-none``,
    with no name stack)."""
    if ctx["trace"] is None or ctx["peak"] is None:
        return None
    took = seconds(ctx["trace"], (scope,) + tuple(also))
    if not took:
        return None
    cell = ctx["cell"]
    spec = importlib.util.spec_from_file_location(
        "bench_count", cell.home / "counts" / f"{cell.config['flops']}.py")
    count = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(count)
    work = count.scope_work(cell.config, cell.scenario)[scope]
    z = count.sizes(cell.config, cell.scenario)
    scen = cell.scenario
    step_tokens = scen["n_nodes"] * scen["data"]["batch_size"] * z["T"]
    steps = max(ctx["rows_per_node"] // scen["data"]["batch_size"], 1) \
        * scen["training"]["epochs_per_round"]
    test_rows = scen["data"]["synthetic_test"]
    tokens = {"train": ctx["rounds"] * steps * step_tokens,
              "forward": ctx["evals"] * scen["n_nodes"] * test_rows * z["T"]}
    least = sum(
        tokens[phase] * max(flops / ctx["peak"]["bf16_flops_per_s"],
                            byts / ctx["peak"]["hbm_bytes_per_s"])
        for phase, (flops, byts) in work.items())
    return 100.0 * least / (took * ctx["chips"])
