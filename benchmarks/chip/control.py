#!/usr/bin/env python3
"""Read the control's number at a cell's own size, on the chip.

    python3 benchmarks/chip/control.py --workload alexnet.sat --seeds 1,2,3

For each seed it makes the cell's weights and image pool as a run does,
puts the reference in the program's place at lower precisions, and
prints the number a run compares (max over pool images of
max|y - ref| / max|ref|, ``netref.rel_err``) for each, against the
highest-precision reference. ``fp8`` is the control a cell's limit must
fail; ``bf16`` is the precision the chip's default already gives the
program's fp32 dots, shown beside it. Not part of a benchmark run: it
sets the upper reading a limit is chosen below, once.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import benchspec

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    cell = benchspec.resolve(benchspec.load_benchmark(ROOT), args.workload,
                             ROOT)
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    import netref

    enable_compile_cache(ROOT)
    net = cell.config["net"]
    for seed in (int(s) for s in args.seeds.split(",")):
        params = netref.make_params(net, netref.key_from_seed(seed))
        pool = netref.make_pool(net, cell.traffic["pool"],
                                np.random.default_rng([seed, 0]))
        ref = netref.reference_outputs(params, pool, net)
        row = {"workload": args.workload, "seed": seed}
        for precision in ("bf16", "fp8"):
            ys = netref.reference_outputs(params, pool, net, precision)
            row[precision] = netref.rel_err(ys, ref)
        row["limit"] = cell.config["check"]["max_rel_err"]
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
