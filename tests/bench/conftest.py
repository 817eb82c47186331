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


FAMILY_CELL = "tiny_refine.steps2"


def make_family_root(dst):
    """A checkout root holding the benchmark plus a cell of a family that
    is not a CNN (``data/refine.py``, two forwards a call), added as a
    later change would add it: a family module, a configuration, a plan
    and a traffic mix as new files, and new entries in BENCHMARK.json."""
    make_root(dst, configs=())
    bench = os.path.join(dst, "bench")
    shutil.copy(os.path.join(DATA, "refine.py"), bench)
    for name in ("tiny_refine.json", "tiny_refine.plan.json"):
        shutil.copy(os.path.join(DATA, name), os.path.join(bench, "configs"))
    shutil.copy(os.path.join(DATA, "tiny_steps2.json"),
                os.path.join(bench, "workloads"))
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny_refine", "source": "test",
                          "file": "bench/configs/tiny_refine.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": FAMILY_CELL, "config": "tiny_refine",
                            "traffic": "tiny_steps2", "chips": 1,
                            "why": "test"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in ("images_per_s", "mfu"):
            m["workloads"].append(FAMILY_CELL)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return str(dst)


@pytest.fixture
def family_root(tmp_path, monkeypatch):
    """:func:`make_family_root`, with its ``bench/`` searched for family
    modules after the repository's, as a checkout's own ``bench/`` is."""
    import bench
    root = make_family_root(tmp_path)
    monkeypatch.setattr(bench, "__path__",
                        [*bench.__path__, os.path.join(root, "bench")])
    yield root
    sys.modules.pop("bench.refine", None)
