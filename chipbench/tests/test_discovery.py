"""A new configuration, traffic mix and per-layer metric are found by
name from new files alone: the test adds three files to a copy of the
benchmark and changes no file that is there."""
import json
import os
import shutil

from chipbench import harness

from conftest import tiny_checkout

REPO = harness.ROOT


def _copy(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return root


def test_new_files_are_found(tmp_path):
    root = _copy(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    cfg = json.loads((root / "chipbench/configs/susy-sv512.json").read_text())
    cfg.update(name="susy-sv256", budget=256)
    (root / "chipbench/configs/susy-sv256.json").write_text(json.dumps(cfg))
    (root / "chipbench/traffic/bursty.json").write_text(json.dumps(
        {"why": "test", "protocol": {"kind": "dynamic", "delta": 0.5,
                                     "mini_batch": 1}, "pool": 2}))
    (root / "chipbench/metrics/syncs_per_round.py").write_text(
        "def read(r):\n    return r.syncs / r.rounds\n")
    # BENCHMARK.json is the one file such a change edits
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "susy-sv256", "source": "x",
                             "file": "chipbench/configs/susy-sv256.json",
                             "reduced": ["stream"], "why": "test"})
    bench["workloads"].append({"name": "susy-sv256.bursty", "config": "susy-sv256",
                               "traffic": "bursty", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "syncs_per_round", "unit": "1",
                               "better": "lower", "source": "program_counter",
                               "layer": "engine step",
                               "moves": "learner_rounds_per_s",
                               "workloads": ["susy-sv256.bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.resolve("susy-sv256.bursty", str(root))
    assert cell.cfg["budget"] == 256
    assert cell.traffic["protocol"]["delta"] == 0.5
    names = [m["name"] for m in cell.per_layer]
    assert "syncs_per_round" in names and "rff_step_roofline" not in names
    assert "device_idle_share" in names
    read = harness.load_reader("syncs_per_round", str(root))

    class R:
        syncs, rounds = 3, 12
    assert read(R) == 0.25
    assert harness.system_module(cell.cfg).__name__ == "chipbench.systems.sv"
    assert harness.reference_module(cell.cfg).__name__ == "chipbench.references.sv"

    changed = [p for p, b in before.items()
               if p.name != "BENCHMARK.json" and p.read_bytes() != b]
    assert changed == []


def test_every_cell_resolves(tmp_path):
    """Every cell of BENCHMARK.json, and every held-back one, finds its
    files."""
    root = tiny_checkout(tmp_path / "checkout")
    cells = {w["name"] for w in harness.load_benchmark()["workloads"]}
    bench = harness.load_benchmark(root)
    assert cells < {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        cell = harness.resolve(w["name"], root)
        assert cell.cfg["chips"] == w["chips"]
        for m in cell.per_layer:
            harness.load_reader(m["name"])
        harness.system_module(cell.cfg)
        harness.reference_module(cell.cfg)
