"""Quantized-span planning and execution benchmark (``occam.quant``).

Two claims, measured:

1. **Planning** — dtype is a real planning axis, not a post-hoc scale
   factor. For each zoo net, the byte-denominated DP under the ``int8``
   policy (int8 activations/boundaries, fp32 weights) must move strictly
   fewer boundary bytes per image than the fp32 plan of the same fleet
   AND grow at least one fitted span (the 4x-smaller closures change the
   argmin, not just the objective's unit).
2. **Execution** — model == machine holds in *bytes*: a quantized
   deployment's measured byte traffic equals the plan's byte-denominated
   prediction exactly (emulated mesh), and the quantized outputs stay
   within a bounded tolerance of the fp32 reference (the accuracy cost
   the frontier's ``quant_cost`` axis trades against).

The headline is the int8-over-fp32 off-chip byte reduction on the
largest zoo net measured.

Writes machine-readable results to ``results/BENCH_quant.json``:

    PYTHONPATH=src python -m benchmarks.occam_quant   # direct
    PYTHONPATH=src python -m benchmarks.run           # via harness
"""
from __future__ import annotations

import json
import os

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_OUT = os.path.join(_ROOT, "results", "BENCH_quant.json")

# planning sweep: zoo nets at a capacity where fp32 needs many spans
ZOO_NETS = ("alexnet", "resnet18", "vggnet")
ZOO_CAPACITY = 400_000
POLICIES = ("fp32", "bf16", "int8")

# execution case: small enough to pipeline on emulated CPU devices
HW = 16
CAPACITY = 6000
BATCH = 6
INT8_TOLERANCE = 0.25   # max |int8 - fp32| on vgg_mini activations

# every BENCH_quant.json must carry these (schema gate for the
# fast-tier test in tests/test_quant.py)
REQUIRED_KEYS = (
    "audit", "zoo_capacity_elems", "policies", "zoo", "execution",
    "bytes_reduction_int8", "span_growth_nets",
)


def validate_doc(doc: dict) -> None:
    """Schema gate: raise if ``doc`` is not a BENCH_quant document."""
    missing = [k for k in REQUIRED_KEYS if k not in doc]
    if missing:
        raise ValueError(f"BENCH_quant doc missing keys: {missing}")
    if doc["bytes_reduction_int8"] <= 1.0:
        raise ValueError("int8 must strictly reduce off-chip bytes")
    if not doc["span_growth_nets"]:
        raise ValueError("int8 must grow a fitted span on >= 1 zoo net")
    for row in doc["zoo"]:
        for k in ("net", "policy", "n_spans", "boundaries",
                  "offchip_bytes_per_image", "boundary_bytes_per_image"):
            if k not in row:
                raise ValueError(f"zoo row missing {k!r}")
    ex = doc["execution"]
    for k in ("net", "matches_prediction_bytes", "payload_bytes_per_elem",
              "link_bytes_ratio_int8", "max_abs_err_int8",
              "tolerance"):
        if k not in ex:
            raise ValueError(f"execution block missing {k!r}")
    if not ex["matches_prediction_bytes"]:
        raise ValueError("byte-denominated model==machine must hold")
    if ex["max_abs_err_int8"] > ex["tolerance"]:
        raise ValueError("int8 accuracy cost exceeded tolerance")


def _span_lens(net, boundaries) -> list:
    cuts = [0] + list(boundaries) + [net.n_layers]
    return [b - a for a, b in zip(cuts[:-1], cuts[1:])]


def zoo_rows(nets=ZOO_NETS, capacity: int = ZOO_CAPACITY) -> list:
    """Per (net, policy): the byte-denominated plan's shape and traffic."""
    from repro import occam
    from repro.models.zoo import get_network

    rows = []
    for name in nets:
        net = get_network(name)
        for pol in POLICIES:
            plan = occam.plan(net, capacity, dtype_policy=pol)
            pred = plan.predicted
            rows.append({
                "net": name,
                "policy": pol,
                "n_spans": plan.n_spans,
                "boundaries": list(plan.boundaries),
                "span_lens": _span_lens(net, plan.boundaries),
                "offchip_bytes_per_image": pred.offchip_bytes,
                "boundary_bytes_per_image": pred.boundary_bytes,
            })
    return rows


def _vgg(hw: int = HW):
    from repro.core.graph import chain

    C, P = "conv", "pool"
    specs = [(C, 3, 1, 1, 8), (C, 3, 1, 1, 8), (P, 2, 2, 0, 0),
             (C, 3, 1, 1, 16), (C, 3, 1, 1, 16), (P, 2, 2, 0, 0),
             (C, 3, 1, 1, 16)]
    return chain("vgg_mini", specs, in_h=hw, in_w=hw, in_ch=3)


def execution_row() -> dict:
    """Run fp32 and int8 plans of the same net on the emulated mesh:
    byte-exact traffic accounting, link-byte reduction, accuracy cost."""
    import jax
    import numpy as np

    from repro import occam
    from repro.models import cnn

    net = _vgg()
    params = cnn.init_params(jax.random.PRNGKey(0), net)
    xs = jax.random.normal(jax.random.PRNGKey(1), (BATCH, HW, HW, 3)) * 0.5

    deps, reports, ys = {}, {}, {}
    for pol in ("fp32", "int8"):
        plan = occam.plan(net, CAPACITY, batch=BATCH, dtype_policy=pol)
        dep = plan.place(chips=plan.n_spans).compile(interpret=True)
        # the two plans may compile onto different-sized meshes; compare
        # on the host
        ys[pol] = np.asarray(dep.run(params, xs))
        deps[pol] = dep
        reports[pol] = dep.report()
    err = float(np.max(np.abs(ys["int8"] - ys["fp32"])))
    pipe = {pol: deps[pol].pipeline(BATCH).report() for pol in deps}
    from benchmarks.audit_stamp import audit_verdict

    return {
        "audit": audit_verdict(deps["fp32"], deps["int8"]),
        "net": net.name,
        "capacity_elems": CAPACITY,
        "matches_prediction_bytes": bool(
            reports["int8"].matches_prediction_bytes
            and reports["fp32"].matches_prediction_bytes),
        "payload_bytes_per_elem": pipe["int8"]["payload_bytes_per_elem"],
        "link_bytes_per_image_fp32": pipe["fp32"]["link_bytes_per_image"],
        "link_bytes_per_image_int8": pipe["int8"]["link_bytes_per_image"],
        "link_bytes_ratio_int8": (
            pipe["int8"]["link_bytes_per_image"]
            / max(pipe["fp32"]["link_bytes_per_image"], 1e-9)),
        "max_abs_err_int8": err,
        "tolerance": INT8_TOLERANCE,
    }


def quant_measurement() -> dict:
    """One in-process measurement (devices must already be available)."""
    zoo = zoo_rows()
    by = {(r["net"], r["policy"]): r for r in zoo}
    growth = []
    reductions = []
    for name in ZOO_NETS:
        f32, i8 = by[(name, "fp32")], by[(name, "int8")]
        reductions.append(f32["offchip_bytes_per_image"]
                          / max(i8["offchip_bytes_per_image"], 1e-9))
        pairs = zip(i8["span_lens"], f32["span_lens"])
        if any(a > b for a, b in pairs) or \
                i8["n_spans"] < f32["n_spans"]:
            growth.append(name)
    execution = execution_row()
    return {
        "audit": execution.pop("audit"),
        "zoo_capacity_elems": ZOO_CAPACITY,
        "policies": list(POLICIES),
        "zoo": zoo,
        "execution": execution,
        "bytes_reduction_int8": round(max(reductions), 3),
        "span_growth_nets": growth,
    }


def occam_quant():
    """Harness entry (``benchmarks.run``): spawn the flagged subprocess
    and report the int8-over-fp32 off-chip byte reduction."""
    from benchmarks.emulated import run_child

    row = run_child("benchmarks.occam_quant", _OUT)
    validate_doc(row)
    return [row], row["bytes_reduction_int8"]


def main() -> None:
    row = quant_measurement()
    validate_doc(row)
    os.makedirs(os.path.dirname(_OUT), exist_ok=True)
    with open(_OUT, "w") as f:
        json.dump(row, f, indent=2)
    print(json.dumps(row, indent=2))


if __name__ == "__main__":
    from benchmarks.emulated import launch

    launch("benchmarks.occam_quant", main)
