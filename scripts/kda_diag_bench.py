"""The delta rule's diagonal sub-blocks, the Pallas kernels of
``ops/kda_diag.py`` against the XLA form of ``models/ling.py``
``_diag_pairs``, on the chip, at the shapes the Kimi Linear cell hands
them: one block of 4 heads as ``head_blocks`` calls it, 256 chunks of 64
positions of 128 channels.

Two programs a form: the forward over ``f32[256, 2, 4, 64, 128]`` (the
two nodes folded as the batch), and the value and gradient to ``q``,
``k`` and ``G`` under a ``vmap`` over 2 nodes of ``f32[256, 1, 4, 64,
128]``. A training step of the cell runs the forward three times and the
way back once (``step`` = 2 x forward + value and gradient). Two clocks:
``*_ms`` is the host's, the median of ``--reps`` calls after two warm-up
calls, each ended by ``block_until_ready`` (a call's dispatch and the
layout copies of what it returns included: about 1 ms a call on the
v5e); ``*_device_ms`` is the device's, the ops' durations in a profiler
trace of ``--reps`` calls (``benchmark/tracereduce.py``) over the calls,
with ``*_ops`` the five longest. The kernel's blocks and gradients are
compared with the XLA form's (largest difference over the largest
value). One JSON line a measurement on stdout and in
``kda_diag_bench.jsonl`` of the repo's output directory for chip runs.

    python scripts/kda_diag_bench.py                          # on a TPU
    python scripts/kda_diag_bench.py --interpret --chunks 2   # on the CPU
"""

from __future__ import annotations

import argparse
import collections
import json
import pathlib
import statistics
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import tracereduce  # noqa: E402

from p2pfl_tpu.models import ling  # noqa: E402
from p2pfl_tpu.ops import kda_diag  # noqa: E402

SUB = 16


def timed(f, args, reps):
    jax.block_until_ready(f(*args))  # compile
    jax.block_until_ready(f(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append(1e3 * (time.perf_counter() - t))
    return statistics.median(times)


def device_ms(f, args, reps):
    """Device milliseconds a call, and the five longest ops', from a
    profiler trace of ``reps`` calls."""
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(reps):
            jax.block_until_ready(f(*args))
        jax.profiler.stop_trace()
        events = tracereduce.load(tracereduce.find_xplane(d))
    ops = collections.Counter()
    for plane in events["device"].values():
        for name, _, seconds, _ in plane:
            ops[name.split(" ")[0]] += 1e3 * seconds / reps
    return sum(ops.values()), dict(ops.most_common(5))


def kernel_pairs(interpret):
    """``_diag_pairs``' kernel form (interpreted where asked)."""
    @jax.custom_vjp
    def pairs(q, k, G):
        return kda_diag.forward(q, k, G, sub=SUB, interpret=interpret)

    pairs.defvjp(lambda q, k, G: (pairs(q, k, G), (q, k, G)),
                 lambda kept, g: kda_diag.backward(
                     *kept, *g, sub=SUB, interpret=interpret))
    return pairs


def programs(pairs):
    def loss(q, k, G, wa, wb):
        a, b = pairs(q, k, G)
        return jnp.sum(a * wa) + jnp.sum(b * wb)

    fwd = jax.jit(pairs)
    vjp = jax.jit(jax.vmap(jax.value_and_grad(loss, argnums=(0, 1, 2))))
    return fwd, vjp


def inputs(chunks, nodes, heads, C, K, key):
    """Unit keys, scaled unit queries, running sums of decays of up to -10
    a step: ``[nodes, chunks, 1, heads, C, K]``."""
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    shape = (nodes, chunks, 1, heads, C, K)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], shape)) * K ** -0.5
    k = unit(jax.random.normal(ks[1], shape))
    G = jnp.cumsum(-10.0 * jax.random.uniform(ks[2], shape), axis=-2)
    blocks = (nodes, chunks, 1, heads, C // SUB, SUB, SUB)
    wa, wb = (jax.random.normal(x, blocks) for x in ks[3:])
    return q, k, G, wa, wb


def rel(got, want):
    return [float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]


def measure(pairs, folded, args, reps, trace):
    fwd, vjp = programs(pairs)
    rec = {"fwd_ms": timed(fwd, folded, reps),
           "vjp_ms": timed(vjp, args, reps)}
    rec["step_ms"] = 2 * rec["fwd_ms"] + rec["vjp_ms"]
    if trace:
        rec["fwd_device_ms"], rec["fwd_ops"] = device_ms(fwd, folded, reps)
        rec["vjp_device_ms"], rec["vjp_ops"] = device_ms(vjp, args, reps)
        rec["step_device_ms"] = 2 * rec["fwd_device_ms"] + rec["vjp_device_ms"]
    return rec, fwd(*folded), vjp(*args)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunks", type=int, default=256)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--interpret", action="store_true",
                    help="the kernels through Pallas's interpreter (CPU)")
    args = ap.parse_args()
    out = pathlib.Path("chiprun_out")
    out.mkdir(exist_ok=True)
    with open(out / "kda_diag_bench.jsonl", "w") as log:
        run(args, log)


def run(args, log):
    def say(**rec):
        line = json.dumps(rec)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    dev = jax.devices()[0]
    say(device=dev.platform, kind=dev.device_kind)
    trace = dev.platform == "tpu"
    q, k, G, wa, wb = inputs(args.chunks, 2, 4, 64, 128, 0)
    # the forward sees the nodes folded as the batch: [chunks, 2, 4, C, K]
    folded = [jnp.moveaxis(x, 0, 1).reshape((args.chunks, 2) + x.shape[3:])
              for x in (q, k, G)]
    xla = lambda q, k, G: ling._diag_pairs(q, k, G, SUB, False)
    base, want_fwd, want_vjp = measure(xla, folded, (q, k, G, wa, wb),
                                       args.reps, trace)
    say(form="xla", **base)
    rec, got_fwd, got_vjp = measure(kernel_pairs(args.interpret), folded,
                                    (q, k, G, wa, wb), args.reps, trace)
    ratio = {f"{x}_x": base[x] / rec[x] for x in ("step_ms", "step_device_ms")
             if x in rec}
    say(form="kernel", **ratio, fwd_rel_of_max=rel(got_fwd, want_fwd),
        vjp_rel_of_max=rel(got_vjp, want_vjp), **rec)


if __name__ == "__main__":
    main()
