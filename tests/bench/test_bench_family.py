"""A family that is not a CNN joins the benchmark as new files only.

``data/refine.py`` applies a residual MLP step ``steps`` times a call, as
a sampler applies its denoiser; ``conftest.make_family_root`` adds it, its
configuration, plan and traffic (``steps: 2``) as a later change would,
and the harness runs it on the CPU through the family protocol of
``bench/__init__.py``.  No number these runs print is a device
measurement.
"""
import importlib
import json
import os
import time

import pytest

from bench import harness

from conftest import CPU, FAMILY_CELL, ROOT


def _run(root, trace=False, seed=2**33 + 9):
    return harness.run(harness.Registry(root), FAMILY_CELL, seed, 0.2, trace,
                       time.perf_counter(), dict(CPU))


def test_family_joins_with_no_existing_file_edited(family_root):
    for sub, _, files in os.walk(os.path.join(ROOT, "bench")):
        if "__pycache__" in sub:
            continue
        for name in files:
            src = os.path.join(sub, name)
            dst = os.path.join(family_root, os.path.relpath(src, ROOT))
            with open(src, "rb") as a, open(dst, "rb") as b:
                assert a.read() == b.read(), src
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        old = json.load(f)
    with open(os.path.join(family_root, "BENCHMARK.json")) as f:
        new = json.load(f)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(new[key]) >= len(old[key])
        for a, b in zip(old[key], new[key]):
            assert {k: v for k, v in b.items() if k != "workloads"} == \
                {k: v for k, v in a.items() if k != "workloads"}
            assert b.get("workloads", [])[:len(a.get("workloads", []))] \
                == a.get("workloads", [])
    assert not os.path.exists(os.path.join(ROOT, "bench", "refine.py"))


def test_family_keeps_the_contract(family_root):
    """The pinned work is the family's count, the configuration is the
    family's network, file and entry agree, and a call is two forwards."""
    reg = harness.Registry(family_root)
    cfg = reg.config("tiny_refine")
    fam = importlib.import_module(f"bench.{cfg['family']}")
    assert cfg["family"] == "refine" and fam.__name__ == "bench.refine"
    assert cfg["work"] == fam.work(cfg, cfg["plan_json"])
    fam.check_config(cfg)
    (entry,) = [c for c in reg.bench["configs"] if c["name"] == "tiny_refine"]
    assert cfg["reduced"] == entry["reduced"]
    traffic = reg.traffic(reg.cell(FAMILY_CELL)["traffic"])
    assert fam.forwards(cfg, traffic) == traffic["steps"] == 2
    assert [m["name"] for m in reg.metrics("per_layer", FAMILY_CELL)] == [
        "mfu"]


def test_family_run_is_correct(family_root):
    r = _run(family_root)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"images_per_s", "setup_s"}
    chk = r["checks"]["max_rel_err"]
    assert chk["value"] < chk["limit"]


def test_family_traced_run_reads_two_forwards(family_root, monkeypatch):
    """The traced run hands the family's forwards to the readers: MFU
    counts the network's work twice a call."""
    from bench import readers
    seen = []

    def mfu(ctx):
        seen.append(ctx.forwards)
        return 1.0
    monkeypatch.setattr(readers, "mfu", mfu)
    path = os.path.join(family_root, "bench", "peaks.json")
    with open(path) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    with open(path, "w") as f:
        json.dump(peaks, f)
    r = _run(family_root, trace=True)
    assert r["correct"] is True
    assert seen == [2] and r["metrics"]["mfu"]["value"] == 1.0
    assert r["device"]["window_s"] > 0


def test_a_step_left_out_is_not_correct(family_root):
    """A sampler that makes one step where the traffic asks for two gives
    answers the reference does not."""
    import bench.refine as fam
    build = fam.build

    def one_step(cfg, params, plan_text, workdir, clock=None, *, traffic):
        return build(cfg, params, plan_text, workdir, clock,
                     traffic={**traffic, "steps": 1})
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fam, "build", one_step)
        r = _run(family_root)
    assert r["correct"] is False and r["failed"] >= 1
