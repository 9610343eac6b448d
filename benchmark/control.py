"""Readings for the limits of ``cells/<cell>.json`` — not part of a
benchmark run. On the chip, at the cell's own size, for each seed in one
process: the program's first rounds against the reference (the lower
readings), the control against the reference (the reference computed in
the configuration's ``control_precision``, put in the program's place),
and each named fault planted in the reference. Beside each set of
readings stands what the cell's limits make of it (``*_fails``: the
numbers over their limit; the control and every fault have to fail one,
the program none).

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 \
        [--faults half_batch,no_exchange] [--control-seeds N]

One JSON line per seed on standard output and appended to
``chiprun_out/control-<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench  # also puts the repo's root on sys.path


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--faults", default="half_batch")
    p.add_argument("--control-seeds", type=int, default=None,
                   help="the control and the faults on the first N seeds only")
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args(argv)

    cell = bench.Cell(args.workload, args.rehearse_cpu)
    import numpy as np

    from p2pfl_tpu.utils import compile_cache

    compile_cache.enable()
    bench.device_facts(cell.rehearse, cell.chips)
    import check

    out_dir = bench.ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    faults = [f for f in args.faults.split(",") if f]
    seeds = [int(s) for s in args.seeds.split(",")]
    with_control = seeds if args.control_seeds is None \
        else seeds[: args.control_seeds]
    for seed in seeds:
        t0 = time.monotonic()
        driven = bench.Driven(cell, seed)
        driven.follow()
        seen, inputs = driven.release()
        del driven
        t1 = time.monotonic()
        nodes = bench.eval_nodes_of(cell, seed)
        ref = bench.reference_readings(cell, inputs, nodes)
        t2 = time.monotonic()
        where: dict = {}
        line = {"workload": cell.name, "seed": seed,
                "program_s": t1 - t0, "reference_s": t2 - t1,
                "program": check.gaps(seen, ref, nodes, where),
                "worst_at": where}
        every = np.arange(len(nodes))

        def put_in_place(name, **how):
            """The reference, altered, in the program's place: its
            readings, and what the cell's limits make of them."""
            got = bench.reference_readings(cell, inputs, nodes, **how)
            by_leaf: dict = {}
            line[name] = check.gaps(got, ref, every, by_leaf)
            line[name + "_by_leaf"] = {
                k: by_leaf[k] for k in ("moment_by_leaf", "change_by_leaf")}
            held = check.compare(got, ref, every, cell.limits)
            line[name + "_fails"] = sorted(
                k for k, (v, lim) in held.items() if not v <= lim)

        held = check.compare(seen, ref, nodes, cell.limits)
        line["program_fails"] = sorted(
            k for k, (v, lim) in held.items() if not v <= lim)
        if seed in with_control:
            put_in_place("control_" + cell.config["control_precision"],
                         q=cell.config["control_precision"])
            for fault in faults:
                put_in_place("fault_" + fault, fault=fault)
        line["total_s"] = time.monotonic() - t0
        text = json.dumps(line)
        print(text, flush=True)
        with open(out_dir / f"control-{cell.name}.jsonl", "a") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
