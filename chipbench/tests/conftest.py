import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: CPU-sized stand-ins for the cells' configurations
TINY = {"rounds": 60, "learners": 4, "budget": 16, "num_features": 64}

HELD_BACK = os.path.join(REPO, "chipbench", "tests", "data", "held_back.json")


def with_held_back(bench: dict) -> dict:
    """BENCHMARK.json's entries together with those of the cells it leaves
    out until they have run on the chip (``data/held_back.json``)."""
    with open(HELD_BACK) as f:
        held = json.load(f)
    out = {k: list(v) if isinstance(v, list) else v for k, v in bench.items()}
    for key in ("configs", "workloads", "per_layer"):
        out[key] = out[key] + held[key]
    out["per_layer"] = [dict(m, workloads=m["workloads"] + held["per_layer_workloads"]
                             .get(m["name"], [])) if "workloads" in m else m
                        for m in out["per_layer"]]
    return out


def tiny_checkout(dest, learners=4):
    """A copy of the benchmark, the held-back cells added, with every
    configuration cut to ``TINY``."""
    dest = str(dest)
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = with_held_back(json.load(f))
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    shutil.copytree(os.path.join(REPO, "chipbench"), os.path.join(dest, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    conf = os.path.join(dest, "chipbench", "configs")
    for name in os.listdir(conf):
        path = os.path.join(conf, name)
        with open(path) as f:
            cfg = json.load(f)
        for k, v in TINY.items():
            if k in cfg:
                cfg[k] = v
        cfg["learners"] = learners
        limits = cfg["limits"]
        if "min_compared_rounds" in limits:
            limits["min_compared_rounds"] = min(limits["min_compared_rounds"],
                                                cfg["rounds"] // 4)
        with open(path, "w") as f:
            json.dump(cfg, f)
    return dest


@pytest.fixture
def tiny(tmp_path):
    return tiny_checkout(tmp_path / "checkout")
