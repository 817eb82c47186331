"""The harness driven end to end on the CPU, at toy sizes.

``harness.run`` is what ``bench/run.py`` calls once it has found its
chips; here it is called directly, with the device named as the CPU, on
the tiny cells that ``conftest.make_root`` adds as new files.  No number
these runs print is a device measurement.
"""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness

from conftest import CPU, ROOT


def _run(root, cell, trace=False, seed=2**33 + 5):
    reg = harness.Registry(root)
    return harness.run(reg, cell, seed, 0.2, trace, time.perf_counter(),
                       dict(CPU))


@pytest.mark.parametrize("cell", ["tiny_resnet.b4", "tiny_mobilenet.b4"])
def test_run_prints_the_contract_line(tiny_root, cell):
    r = _run(tiny_root, cell)
    assert list(r) == ["correct", "attempted", "failed", "metrics",
                       "device", "checks"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"images_per_s", "setup_s"}
    for m in r["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert r["device"] == CPU
    chk = r["checks"]["max_rel_err"]
    assert chk["value"] < chk["limit"]
    json.dumps(r)


def test_latency_cell_reports_its_tail_and_its_mean(tiny_root):
    """A cell listed under the latency metrics reports the 95th percentile
    of its calls and the window's length over its calls."""
    bm_path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(bm_path) as f:
        bm = json.load(f)
    for m in bm["end_to_end"]:
        if m["name"] in ("latency_ms_p95", "latency_ms_mean"):
            m["workloads"].append("tiny_resnet.b4")
        elif m["name"] == "images_per_s":
            m["workloads"].remove("tiny_resnet.b4")
    with open(bm_path, "w") as f:
        json.dump(bm, f)
    r = _run(tiny_root, "tiny_resnet.b4")
    assert r["correct"] is True
    assert set(r["metrics"]) == {"latency_ms_p95", "latency_ms_mean",
                                 "setup_s"}
    mean = r["metrics"]["latency_ms_mean"]
    assert mean["unit"] == "ms"
    # the window closes with the first call to end 0.2 s after its start
    assert mean["value"] * r["attempted"] >= 200
    assert r["metrics"]["latency_ms_p95"]["value"] > 0


def test_traced_run_reads_the_added_metric(tiny_root):
    """A per-layer metric is added as one new reader file and an entry in
    BENCHMARK.json.  A CPU trace has no TPU plane: the readers of device
    time find nothing and their metrics are left out, never reported 0."""
    with open(os.path.join(tiny_root, "bench", "metrics",
                           "tiny.calls.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx.calls)\n")
    bm_path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(bm_path) as f:
        bm = json.load(f)
    bm["per_layer"].append({"name": "tiny.calls", "unit": "calls",
                            "better": "higher", "source": "host_clock",
                            "layer": "harness", "moves": "images_per_s",
                            "workloads": ["tiny_resnet.b4"]})
    with open(bm_path, "w") as f:
        json.dump(bm, f)
    with open(os.path.join(tiny_root, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    with open(os.path.join(tiny_root, "bench", "peaks.json"), "w") as f:
        json.dump(peaks, f)
    r = _run(tiny_root, "tiny_resnet.b4", trace=True)
    assert r["correct"] is True
    assert r["metrics"]["tiny.calls"]["value"] == 2.0
    assert "mfu" in r["metrics"]
    for name in ("merged_conv_roofline", "depthwise_conv_roofline",
                 "executor.outside_kernel_share", "device.idle_share"):
        assert name not in r["metrics"]
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert r["device"]["window_s"] > 0
    assert list(r)[-1] == "checks"


def test_new_cell_edits_no_existing_file(tiny_root):
    """The tiny cells were added without changing any file the benchmark
    already had (BENCHMARK.json only gained entries)."""
    for sub, _, files in os.walk(os.path.join(ROOT, "bench")):
        if "__pycache__" in sub:
            continue
        for name in files:
            src = os.path.join(sub, name)
            dst = os.path.join(tiny_root, os.path.relpath(src, ROOT))
            with open(src, "rb") as a, open(dst, "rb") as b:
                assert a.read() == b.read(), src
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        old = json.load(f)
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        new = json.load(f)
    for key in ("configs", "workloads"):
        assert new[key][:len(old[key])] == old[key]
    reg = harness.Registry(tiny_root)
    assert reg.traffic(reg.cell("tiny_mobilenet.b4")["traffic"])["batch"] == 4


def _bench_run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu", **(env_extra or {})})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "resnet34.offline_b128", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _bench_run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_alone_does_not_run(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files (no
    program) exits non-zero and prints no result."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench_run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr
