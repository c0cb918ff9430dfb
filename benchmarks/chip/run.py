#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator and print its result.

    python3 benchmarks/chip/run.py --workload alexnet.sat --seed 7 \\
        --seconds 10 --trace 0

The cell, its configuration and its traffic mix are read from
``BENCHMARK.json`` at the checkout's root (see ``benchspec``). With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window. The last line of standard output is one JSON object; the last
lines of standard error are the correctness checks, each number beside
its limit.

It exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for: a speed is only ever measured on the chip.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))

import benchspec  # noqa: E402


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="where a traced run keeps its trace (default: "
                         "benchmarks/chip/.trace/<workload>)")
    args = ap.parse_args(argv)

    cell = benchspec.resolve(benchspec.load_benchmark(ROOT), args.workload,
                             ROOT)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"run.py: no TPU (JAX found {devices[0].platform!r}); "
            f"nothing is measured off the chip")
        return 2
    if len(devices) < cell.chips:
        log(f"run.py: {args.workload} needs {cell.chips} chips, JAX found "
            f"{len(devices)}")
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    import servecell

    cache = enable_compile_cache(ROOT)
    log(f"device {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{cache}")
    trace_dir = None
    if args.trace:
        trace_dir = args.trace_dir or os.path.join(HERE, ".trace",
                                                   args.workload)
        servecell.clear_dir(trace_dir)
    result = servecell.run_cell(cell, args.seed, args.seconds,
                                t_start=T_START, trace_dir=trace_dir,
                                log=log)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    log(f"correct {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
