"""A whole run of the harness on the CPU at a tiny size, skipping only the
look for a chip: a sound run comes out correct, and each fault a cell
can have, planted underneath the timed path, comes out not correct: an
answer altered where it is produced, the reference served at the
precision below the configuration's, span kernels in interpret mode, a
plan other than the configuration's. The command itself refuses to
measure where there is no TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    # the same emulated device count as the repository's test suite
    os.environ["XLA_FLAGS"] = (
        f"{_flags} --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

import benchspec  # noqa: E402
import servecell  # noqa: E402

BENCH = benchspec.load_benchmark()

ONE_CHIP = {"name": "tiny", "in_h": 16, "in_w": 16, "in_ch": 3,
            "layers": [["conv", 3, 1, 1, 8], ["pool", 2, 2, 0, 8],
                       ["conv", 3, 1, 1, 16]],
            "residual_edges": []}
ONE_CHIP_ROUTES = [[0, 3, "pallas"]]


@pytest.fixture(autouse=True)
def cpu_kernels(monkeypatch):
    """The CPU runs the span kernels in interpret mode, its emulation."""
    monkeypatch.setattr(servecell, "KERNEL_INTERPRET", True)


def _cell(**mix_over):
    like = benchspec.resolve(BENCH, "alexnet.sat")
    mix = dict(like.traffic, pool=8, clients=4, **mix_over)
    cfg = {"name": "tiny", "net": ONE_CHIP,
           "fleet": {"chips": 1, "vmem_elems": 1 << 20}, "objective": None,
           "round_batch": 8, "routes": ONE_CHIP_ROUTES,
           "dtype": like.config["dtype"], "check": like.config["check"]}
    return benchspec.Cell("alexnet.sat", 1, cfg, mix, like.end_to_end,
                          like.per_layer)


def _run(cell=None, seed=2**31 + 17, **kw):
    return servecell.run_cell(cell or _cell(), seed, 1.0,
                              t_start=time.perf_counter(),
                              log=lambda *a: None, **kw)


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] > 10 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    assert set(r["metrics"]) == {"images_per_s", "setup_s"}
    assert list(r)[-1] == "checks"
    assert r["device"]["count"] == jax.device_count()
    for name in ("routes_not_as_configured", "dtypes_not_as_configured",
                 "kernels_in_wrong_mode", "pallas_spans_not_lowered"):
        assert r["checks"][name]["value"] == 0
    assert r["host"]["longest_completion_gap_ms"] > 0


def _lend_peaks(monkeypatch):
    """The peaks table knows no CPU: lend it the chip's row."""
    import devicepeaks

    monkeypatch.setitem(devicepeaks.PEAKS, jax.devices()[0].device_kind,
                        devicepeaks.PEAKS["TPU v5 lite"])


def test_traced_run_reads_its_per_layer_metrics(tmp_path, monkeypatch):
    """A --trace 1 run: host-clock and counter metrics from the measured
    window, the device trace from a short window of its own after it,
    with the harness's spans placed on the trace's clock. The CPU has no
    TPU plane, so the device-trace metrics find nothing to read."""
    _lend_peaks(monkeypatch)
    r = _run(seed=5, trace_dir=str(tmp_path))
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"step_mfu", "plan_offchip_bytes_per_image"}
    assert r["device"]["window_s"] == pytest.approx(1.0, abs=0.5)
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert r["trace"]["rate_ratio"] > 0
    reduced = json.loads((tmp_path / "reduced.json").read_text())
    names = {n for n, *_ in reduced["host"]}
    assert {"bench.window", "bench.submit", "bench.materialise"} <= names
    (lo, hi), = [(s, e) for n, s, e in reduced["host"]
                 if n == "bench.window"]
    assert 0 < lo < hi


@pytest.mark.parametrize("least_ratio,withheld", [(0.0, False),
                                                  (1e9, True)])
def test_slowed_trace_withholds_device_metrics(tmp_path, monkeypatch,
                                               least_ratio, withheld):
    """A traced window that served more slowly than the measured one
    reports no device-trace metric; the others stay."""
    _lend_peaks(monkeypatch)
    monkeypatch.setattr(servecell, "TRACE_MIN_RATE_RATIO", least_ratio)
    monkeypatch.setattr(benchspec, "metric_reader",
                        lambda name, root=None: lambda run: 1.0)
    r = _run(seed=6, trace_dir=str(tmp_path))
    assert r["trace"]["device_metrics_withheld"] is withheld
    device = {m["name"] for m in BENCH["per_layer"]
              if m["source"] == "device_trace"}
    assert device
    want = {m["name"] for m in BENCH["per_layer"]}
    assert set(r["metrics"]) == (want - device if withheld else want)


def test_altered_answer_is_not_correct(monkeypatch):
    """One lane of every round's output is changed where it is produced."""
    from repro.occam import deploy

    deliver = deploy.Session._deliver

    def altered(self, segs, lanes):
        return deliver(self, segs, lanes.at[0].add(1.0))

    monkeypatch.setattr(deploy.Session, "_deliver", altered)
    r = _run()
    assert not r["correct"]
    assert r["checks"]["max_rel_err"]["value"] > \
        r["checks"]["max_rel_err"]["limit"]


@pytest.mark.parametrize("precision", ["highest", "fp8"])
def test_reference_in_the_programs_place(monkeypatch, precision):
    """The control: the benchmark's reference served in the program's
    place, through the engine, the window and the comparison. At full
    precision it reads under the limit; with fp8 operands, the step
    below the configuration's, ``correct`` comes out false."""
    import jax.numpy as jnp

    import netref
    from repro.occam import deploy

    def step(self, round_batch):
        fn = jax.vmap(lambda p, x: netref.forward(p, x, ONE_CHIP, precision),
                      in_axes=(None, 0))
        return jax.jit(lambda p, xs: fn(p, xs.astype(jnp.float32))), \
            {"lowerings": 0}

    monkeypatch.setattr(deploy.Deployment, "_serve_step", step)
    r = _run()
    err = r["checks"]["max_rel_err"]
    if precision == "fp8":
        assert not r["correct"]
        assert err["value"] > err["limit"]
    else:
        assert err["value"] <= err["limit"]


def test_interpreted_kernels_are_not_correct(monkeypatch):
    """Served outputs can be right while the span kernels ran in the
    CPU's interpret mode, which a chip never serves from."""
    monkeypatch.setattr(servecell, "KERNEL_INTERPRET", False)
    r = _run()
    assert not r["correct"]
    assert r["checks"]["kernels_in_wrong_mode"]["value"] > 0
    assert r["checks"]["max_rel_err"]["value"] <= \
        r["checks"]["max_rel_err"]["limit"]


def _tiny_plan(**kw):
    from repro import occam
    from repro.core.graph import net_from_dict

    return occam.plan(net_from_dict(ONE_CHIP), 1 << 20, **kw)


@pytest.mark.parametrize("case,failing", [
    ("sound", None),
    ("bf16_plan", "dtypes_not_as_configured"),
    ("other_spans", "routes_not_as_configured"),
    ("other_mode", "kernels_in_wrong_mode"),
    ("never_lowered", "pallas_spans_not_lowered"),
])
def test_plan_checks(case, failing):
    """What was deployed against what the configuration states."""
    config = {"routes": ONE_CHIP_ROUTES, "dtype": "float32"}
    plan = _tiny_plan(dtype_policy="bf16") if case == "bf16_plan" else _tiny_plan()
    lowerings = [("tiny", 0, 3, True)]
    if case == "other_spans":
        config["routes"] = [[0, 2, "pallas"], [2, 3, "pallas"]]
    elif case == "other_mode":
        lowerings.append(("tiny", 0, 3, False))
    elif case == "never_lowered":
        lowerings = [("other", 0, 3, True)]
    checks = servecell.plan_checks(plan, config, lowerings)
    bad = {k for k, c in checks.items() if c["value"] > c["limit"]}
    assert bad == ({failing} if failing else set())


def _command(cwd, workload="alexnet.sat"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    out = _command(benchspec.ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def test_command_refuses_in_a_bare_checkout(tmp_path):
    """Only BENCHMARK.json and the benchmark's own files: no result."""
    shutil.copy(os.path.join(benchspec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(benchspec.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    out = _command(tmp_path)
    assert out.returncode != 0
    lines = out.stdout.strip().splitlines()
    assert not lines or "correct" not in json.loads(lines[-1])
