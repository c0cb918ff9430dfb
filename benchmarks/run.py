"""Benchmark harness: one entry per paper table/figure + the roofline.

Prints ``name,us_per_call,derived`` CSV (derived = the headline number the
paper reports for that artifact). Roofline rows appear when dry-run
artifacts exist under results/dryrun. Executable benchmarks
(``occam_stap``, ``occam_serve``, ``occam_async``) drive the staged
deployment API (``repro.occam``: plan -> place -> compile -> run /
serve) — the batch pipeline, the continuous serving session, and the
async continuous-batching engine respectively.

    PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import tables  # noqa: E402

BENCHES = [
    ("table2_partitions", tables.table2_partitions,
     "total spans across 8 nets"),
    ("table3_misses", tables.table3_misses,
     "mean normalized miss (paper ~0.05)"),
    ("table4_traffic", tables.table4_traffic,
     "geomean traffic reduction (paper 21x)"),
    ("fig7_capacity", tables.fig7_capacity,
     "mean filter fraction of capacity (paper: most)"),
    ("fig8_speedup", tables.fig8_speedup,
     "geomean speedup vs base (paper 2.06x)"),
    ("fig9_energy", tables.fig9_energy,
     "mean energy saving (paper 0.33)"),
    ("cache_sensitivity", tables.cache_sensitivity,
     "traffic ratio 3MB/6MB (>1 per paper §V-B2)"),
    ("occam_span_engine", tables.occam_span_engine,
     "compiled-engine speedup vs interpreted streaming (floor 10x)"),
    ("stap_example", tables.stap_example,
     "sim/paper throughput ratio (1.0 = exact)"),
]


def _occam_stap():
    # imported lazily: the benchmark re-runs itself in a subprocess with
    # the emulated-device XLA flags and parses results/BENCH_stap.json
    from benchmarks.occam_stap import occam_stap

    return occam_stap()


def _occam_serve():
    # serving-session benchmark (Deployment.serve): steady throughput vs
    # the ring-of-rounds prediction + the one-compile guarantee; runs in
    # a flagged subprocess, parses results/BENCH_serve.json
    from benchmarks.occam_serve import occam_serve

    return occam_serve()


def _occam_async():
    # async continuous-batching engine (occam.serve.AsyncEngine):
    # saturated throughput vs the steady-tick prediction + Poisson p99
    # sweep; runs in a flagged subprocess, parses results/BENCH_async.json
    from benchmarks.occam_async import occam_async

    return occam_async()


def _occam_autoplan():
    # fleet-aware planning frontier (occam.autoplan): frontier best ==
    # exhaustive capacity x placement enumeration, memoized DP sweep vs
    # naive per-capacity re-runs; writes results/BENCH_autoplan.json
    from benchmarks.occam_autoplan import occam_autoplan

    return occam_autoplan()


BENCHES.append(
    ("occam_stap", _occam_stap,
     "STAP pipeline throughput measured/predicted (1.0 = exact)"))
BENCHES.append(
    ("occam_serve", _occam_serve,
     "serving session throughput measured/predicted (1.0 = exact)"))
BENCHES.append(
    ("occam_async", _occam_async,
     "async engine throughput measured/predicted (1.0 = exact)"))
def _occam_calibrate():
    # measured-cost planning (occam.calibrate + Frontier.rescore): fit a
    # CostModel from isolated stage/hop timings, re-score the frontier,
    # compare analytic vs calibrated prediction error against measured
    # steady serving; runs in a flagged subprocess, writes
    # results/BENCH_calibrate.json
    from benchmarks.occam_calibrate import occam_calibrate

    return occam_calibrate()


def _occam_quant():
    # quantized-span planning + execution (occam.quant): byte-denominated
    # DP moves the cut and shrinks boundary traffic; byte-exact
    # model==machine on the emulated mesh; bounded int8 accuracy cost;
    # runs in a flagged subprocess, writes results/BENCH_quant.json
    from benchmarks.occam_quant import occam_quant

    return occam_quant()


BENCHES.append(
    ("occam_autoplan", _occam_autoplan,
     "memoized DP-sweep speedup vs naive (frontier == exhaustive best)"))
BENCHES.append(
    ("occam_calibrate", _occam_calibrate,
     "calibrated-over-analytic prediction-error improvement (>1 = helped)"))
BENCHES.append(
    ("occam_quant", _occam_quant,
     "int8-over-fp32 off-chip byte reduction (>1 = quantization pays)"))


# entries whose pipelines run on an emulated CPU mesh in a child process
# (benchmarks.emulated); on an accelerator this process holds the chip, so
# they are skipped rather than started
EMULATED = {"occam_stap", "occam_serve", "occam_async", "occam_calibrate",
            "occam_quant"}


def main() -> None:
    from benchmarks.emulated import N_DEVICES, on_cpu

    cpu = on_cpu()
    print("name,us_per_call,derived")
    for name, fn, _note in BENCHES:
        if name in EMULATED and not cpu:
            print(f"{name}: skipped, it emulates a {N_DEVICES}-device CPU "
                  f"mesh", file=sys.stderr)
            continue
        t0 = time.perf_counter()
        _rows, derived = fn()
        us = (time.perf_counter() - t0) * 1e6
        print(f"{name},{us:.0f},{derived:.4g}")

    # every results/BENCH_*.json carries an audit stamp: the executable
    # benchmarks embed the verdict of the plan they measured when they
    # write; artifacts from before the auditor get an explicit
    # "unaudited" marker rather than a silently absent key
    from benchmarks.audit_stamp import backfill

    stamped = backfill(os.path.join(os.path.dirname(__file__), "..",
                                    "results"))
    for path in stamped:
        print(f"audit: stamped pre-audit artifact "
              f"{os.path.basename(path)} as unaudited", file=sys.stderr)

    # roofline (from dry-run artifacts, when present)
    from benchmarks import roofline

    for mesh in ("16x16", "2x16x16"):
        t0 = time.perf_counter()
        rows = roofline.load_rows(mesh=mesh)
        us = (time.perf_counter() - t0) * 1e6
        if rows:
            worst = min(rows, key=lambda r: r["roofline_fraction"])
            mean_frac = sum(r["roofline_fraction"] for r in rows) / len(rows)
            print(f"roofline_{mesh},{us:.0f},{mean_frac:.4g}")
            print(f"roofline_{mesh}_cells,{us:.0f},{len(rows)}")
            print(f"roofline_{mesh}_worst,{us:.0f},"
                  f"{worst['roofline_fraction']:.4g}")


if __name__ == "__main__":
    main()
