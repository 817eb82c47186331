"""Fixtures for the benchmark's own tests (``bench/``, run here on the CPU).

``tiny_root`` is a scratch checkout root: ``BENCHMARK.json`` and the
benchmark's data files copied from the repository, plus two cells added
the way a later change adds them — new files (a configuration, a plan, a
traffic mix) and new entries in ``BENCHMARK.json``, with no file that is
there edited.
"""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_TRAFFIC = {"loop": "closed", "clients": 1, "batch": 4, "pool": 2,
                "warm_calls": 1, "check_calls": 2, "ref_block": 4,
                "trace_calls": 2}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def make_root(dst, configs=("tiny_resnet", "tiny_mobilenet")):
    """A checkout root holding the benchmark plus tiny cells ``<cfg>.b4``."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(dst, "bench", "workloads", "tiny_b4.json"),
              "w") as f:
        json.dump(TINY_TRAFFIC, f)
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        bm = json.load(f)
    for name in configs:
        for suffix in (".json", ".plan.json"):
            shutil.copy(os.path.join(DATA, name + suffix),
                        os.path.join(dst, "bench", "configs"))
        bm["configs"].append({"name": name, "source": "test",
                              "file": f"bench/configs/{name}.json",
                              "reduced": [], "why": "test"})
        cell = f"{name}.b4"
        bm["workloads"].append({"name": cell, "config": name,
                                "traffic": "tiny_b4", "chips": 1,
                                "why": "test"})
        for m in bm["per_layer"] + bm["end_to_end"]:
            if "images_per_s" in (m["name"], m.get("moves")):
                m["workloads"].append(cell)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return str(dst)


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
