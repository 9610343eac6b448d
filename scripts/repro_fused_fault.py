"""Round-4 bisection of the fused-trajectory TPU fault (VERDICT r3 #3).

The failing shape (bench round 3): ViT round program AND its eval
fused into ONE fori_loop dispatch, with {remat, scan_layers} on (and,
historically, the flash kernel — removed in round 6, docs/perf.md
§5b; the fault reproduced with and without it), vmapped over nodes —
intermittently faults the TPU worker; every piece is clean standalone
(scripts/repro_vit_fault.py). This script
builds exactly that fused shape, minimised, with every suspected
ingredient toggleable, so single fresh-process runs can name the
crashing combination:

    python scripts/repro_fused_fault.py \
        --remat 1 --scan 1 --eval 1 \
        --layers 2 --nodes 32 --batch 64 --rounds 20 --trips 3

Exit code 0 prints CLEAN; a worker fault kills the process (the
caller observes the non-zero rc).
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import optax


def main() -> int:
    ap = argparse.ArgumentParser()
    for flag, default in (("remat", 1), ("scan", 1),
                          ("eval", 1), ("layers", 2), ("nodes", 32),
                          ("batch", 64), ("rounds", 20), ("trips", 3)):
        ap.add_argument(f"--{flag}", type=int, default=default)
    args = ap.parse_args()

    from p2pfl_tpu.models import get_model

    model = get_model("vit-tiny", remat=bool(args.remat),
                      scan_layers=bool(args.scan),
                      depth=args.layers)
    n, bsz = args.nodes, args.batch
    key = jax.random.PRNGKey(0)
    x1 = jnp.zeros((1, 32, 32, 3), jnp.float32)
    rngs = jax.random.split(key, n)
    params = jax.jit(jax.vmap(lambda r: model.init(r, x1)))(rngs)
    tx = optax.adam(1e-3)
    opt = jax.jit(jax.vmap(tx.init))(params)

    kx, ky, kt = jax.random.split(key, 3)
    x = jax.random.normal(kx, (n, bsz, 32, 32, 3), jnp.float32)
    y = jax.random.randint(ky, (n, bsz), 0, 10)
    xt = jax.random.normal(kt, (512, 32, 32, 3), jnp.float32)
    yt = jax.random.randint(ky, (512,), 0, 10)

    def per_node(p, o, xb, yb):
        def loss(pp):
            return optax.softmax_cross_entropy_with_integer_labels(
                model.apply(pp, xb), yb).mean()
        l, g = jax.value_and_grad(loss)(p)
        up, o2 = tx.update(g, o, p)
        return optax.apply_updates(p, up), o2, l

    def eval_node(p):
        logits = model.apply(p, xt)
        return jnp.mean(jnp.argmax(logits, -1) == yt)

    @jax.jit
    def trajectory(params, opt, length):
        def body(r, carry):
            params, opt, accs = carry
            params, opt, _ = jax.vmap(per_node)(params, opt, x, y)
            if args.eval:
                accs = accs.at[r].set(jnp.mean(jax.vmap(eval_node)(params)))
            return params, opt, accs

        accs = jnp.zeros((args.rounds,), jnp.float32)
        return jax.lax.fori_loop(0, length, body, (params, opt, accs))

    t0 = time.monotonic()
    for trip in range(args.trips):
        params, opt, accs = trajectory(params, opt, args.rounds)
        s = float(jnp.sum(accs))
        print(f"trip {trip} ok sum={s:.3f} "
              f"({time.monotonic() - t0:.0f}s)", flush=True)
    print(f"CLEAN remat={args.remat} scan={args.scan} "
          f"eval={args.eval} layers={args.layers} nodes={args.nodes} "
          f"batch={args.batch} rounds={args.rounds}x{args.trips} "
          f"({time.monotonic() - t0:.0f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
