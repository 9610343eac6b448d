"""Attention's fused kernel against the tiled form, on the chip, at each
language-model cell's own shapes (``PERF.md`` section 5, "Since PR 44").

For every caller of ``models/ling.py`` ``causal_attention`` (Laguna's
window and full layers, LFM2's attention, Ling's latent attention,
Qwen3-Next's gated attention at heads of 256): the
forward pass and the value and gradient to ``q``, ``k`` and ``v``, under
the nodes' ``vmap`` and inside the caller's scope, of the tiled form
(block and tile 256) and of the kernel at each candidate ``(block,
tile)``; milliseconds are the median of ``--reps`` calls, each ended by
``block_until_ready``. The kernel's output and gradients at the size
``kernel_tiles`` picks are compared with the tiled form's. One JSON line
a measurement on stdout and in ``chiprun_out/attention_bench.jsonl``.

    chiprun -- python scripts/attention_bench.py [--only swa,gqa,...]
"""

from __future__ import annotations

import argparse
import functools
import json
import pathlib
import statistics
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from p2pfl_tpu.models import ling  # noqa: E402
from p2pfl_tpu.ops.flash_attention import flash_attention  # noqa: E402

BF16 = jnp.bfloat16
#: name: (nodes, T, query heads, key heads, D, Dv, window, scope, out type,
#: candidate (block, tile) of the kernel)
SHAPES = {
    "swa": (4, 8192, 72, 8, 128, 128, 512, "swa.attn", BF16,
            [(128, 256), (256, 256), (512, 256), (256, 512), (512, 512)]),
    "gqa": (4, 8192, 48, 8, 128, 128, None, "gqa.attn", BF16,
            [(256, 512), (512, 512), (256, 1024), (512, 1024),
             (1024, 1024)]),
    "lfm2": (16, 2048, 32, 8, 64, 64, None, "gqa.attn", BF16,
             [(256, 512), (512, 512), (512, 1024), (1024, 1024),
              (1024, 2048)]),
    "mla": (8, 4096, 32, 32, 192, 128, None, "mla.attn", jnp.float32,
            [(256, 512), (512, 512), (512, 1024), (1024, 1024),
             (1024, 2048), (2048, 1024)]),
    "gattn": (4, 8192, 16, 2, 256, 256, None, "gattn.attn", BF16,
              [(256, 512), (512, 512), (512, 1024), (1024, 1024)]),
}


def timed(f, args, reps):
    jax.block_until_ready(f(*args))  # compile
    jax.block_until_ready(f(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append(1e3 * (time.perf_counter() - t))
    return statistics.median(times)


def programs(attend, scope, w):
    def loss(q, k, v, w):
        with jax.named_scope(scope):
            return jnp.sum(w * attend(q, k, v).astype(jnp.float32))

    fwd = jax.jit(jax.vmap(lambda q, k, v: _scoped(attend, scope, q, k, v)))
    vjp = jax.jit(jax.vmap(jax.value_and_grad(loss, argnums=(0, 1, 2))))
    return fwd, vjp


def _scoped(attend, scope, q, k, v):
    with jax.named_scope(scope):
        return attend(q, k, v)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=",".join(SHAPES))
    ap.add_argument("--sizes", default="",
                    help="only these block x tile, e.g. 512x512,512x256")
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()
    out = pathlib.Path("chiprun_out")
    out.mkdir(exist_ok=True)
    log = open(out / "attention_bench.jsonl", "w")

    def say(**rec):
        line = json.dumps(rec)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    dev = jax.devices()[0]
    say(device=dev.platform, kind=dev.device_kind)
    for name in args.only.split(","):
        n, T, H, G, D, Dv, window, scope, out_dtype, sizes = SHAPES[name]
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (n, 1, T, H, D), BF16)
        k = jax.random.normal(ks[1], (n, 1, T, G, D), BF16)
        v = jax.random.normal(ks[2], (n, 1, T, G, Dv), BF16)
        w = jax.random.normal(ks[3], (n, 1, T, H, Dv), jnp.float32)
        common = dict(window=window, scope=scope, out_dtype=out_dtype)
        tiled = functools.partial(ling._tiled, scale=D ** -0.5, block=256,
                                  tile=256, group=H // G, **common)
        fwd, vjp = programs(lambda q, k, v: tiled(q, k, v), scope, w)
        base = {"fwd_ms": timed(fwd, (q, k, v), args.reps),
                "vjp_ms": timed(vjp, (q, k, v, w), args.reps)}
        say(shape=name, form="tiled", block=256, tile=256, **base)
        want = vjp(q, k, v, w)
        picked = ling.kernel_tiles(T, D)
        if args.sizes:
            sizes = [s for s in sizes if "%dx%d" % s in args.sizes.split(",")]
        for block, tile in sizes:
            fused = functools.partial(flash_attention, scale=D ** -0.5,
                                      block=block, tile=tile, **common)
            fwd, vjp = programs(fused, scope, w)
            rec = {"fwd_ms": timed(fwd, (q, k, v), args.reps),
                   "vjp_ms": timed(vjp, (q, k, v, w), args.reps)}
            if (block, tile) == picked:
                got = vjp(q, k, v, w)
                rec["value_rel"] = float(jnp.max(jnp.abs(got[0] - want[0])
                                                 / jnp.abs(want[0])))
                rec["grad_rel_of_max"] = [
                    float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                          - b.astype(jnp.float32)))
                          / jnp.max(jnp.abs(b.astype(jnp.float32))))
                    for a, b in zip(got[1], want[1])]
            say(shape=name, form="kernel", block=block, tile=tile,
                fwd_x=base["fwd_ms"] / rec["fwd_ms"],
                vjp_x=base["vjp_ms"] / rec["vjp_ms"], **rec)


if __name__ == "__main__":
    main()
