"""The benchmark's layout: every cell resolves to its files by name, and a
configuration, mix or metric added as files and entries is picked up
with no code edit. The traffic generator is deterministic per seed."""
import json
import os
import shutil

import pytest

import benchspec
import loadgen

BENCH = benchspec.load_benchmark()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = benchspec.resolve(BENCH, cell)
    assert c.config["net"]["layers"]
    assert c.traffic["loop"] == "closed" and c.traffic["source"]
    assert c.config["routes"] and c.config["dtype"] == "float32"
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(benchspec.metric_reader(m["name"]))


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert f.startswith("benchmarks/chip/")
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_configs_are_the_zoo_nets():
    """The configuration files hold the published chains the repository's
    zoo defines, so the cells run the paper's networks."""
    from repro.core.graph import net_from_dict, net_to_dict
    from repro.models import zoo

    by_name = {c["name"]: benchspec._load_json(
        os.path.join(benchspec.ROOT, c["file"])) for c in BENCH["configs"]}
    want = {"alexnet": zoo.alexnet(), "vgg19": zoo.vggnet()}
    for name, net in want.items():
        assert net_to_dict(net_from_dict(by_name[name]["net"])) == \
            net_to_dict(net)


def test_added_files_are_picked_up_with_no_code_edit(tmp_path):
    """A new configuration, mix and metric: files and entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(benchspec.HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    before = {p: open(os.path.join(benchspec.HERE, p), "rb").read()
              for p in os.listdir(benchspec.HERE) if p.endswith(".py")}
    bench = json.loads(json.dumps(BENCH))
    here = root / "benchmarks" / "chip"
    cfg = json.loads((here / "configs" / "alexnet.json").read_text())
    cfg["name"] = "zfnet"
    (here / "configs" / "zfnet.json").write_text(json.dumps(cfg))
    mix = json.loads((here / "traffic" / "sat.json").read_text())
    mix["clients"] = 4
    (here / "traffic" / "bulk4.json").write_text(json.dumps(mix))
    (here / "metrics" / "tenant_skew.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["configs"].append({"name": "zfnet", "source": "x",
                             "file": "benchmarks/chip/configs/zfnet.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "zfnet.bulk4", "config": "zfnet",
                               "traffic": "bulk4", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "tenant_skew", "unit": "%",
                               "better": "lower", "source": "host_clock",
                               "layer": "admission and packing",
                               "moves": "images_per_s",
                               "workloads": ["zfnet.bulk4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = benchspec.resolve(benchspec.load_benchmark(str(root)),
                             "zfnet.bulk4", str(root))
    assert cell.config["name"] == "zfnet"
    assert cell.traffic["clients"] == 4
    assert [m["name"] for m in cell.per_layer] == ["tenant_skew"]
    assert {m["name"] for m in cell.end_to_end} == {"images_per_s",
                                                   "setup_s"}
    assert benchspec.metric_reader("tenant_skew", str(root))(None) == 42.0
    after = {p: open(os.path.join(benchspec.HERE, p), "rb").read()
             for p in os.listdir(benchspec.HERE) if p.endswith(".py")}
    assert before == after


# --------------------------------------------------------------------------
# Traffic
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 2**31 + 11, 2**40 + 3])
def test_closed_clients_are_deterministic_per_seed(seed):
    sat = benchspec._load_json(os.path.join(benchspec.HERE, "traffic",
                                            "sat.json"))
    a = loadgen.ClosedClient(sat, seed, 3)
    b = loadgen.ClosedClient(sat, seed, 3)
    c = loadgen.ClosedClient(sat, seed, 4)
    ra = [a.next() for _ in range(20)]
    assert ra == [b.next() for _ in range(20)]
    assert ra != [c.next() for _ in range(20)]
    assert all(len(r.pool_idx) == 8 for r in ra)
