"""BENCHMARK.json and the files it names: shape, names, and pinned data.

No test here runs a model: they read the benchmark's data and check that
every cell can be found by name, that names and units keep to the
characters the benchmark's readers accept, and that the numbers pinned in
the configuration files still follow from the stored plans.
"""
import importlib
import json
import os
import re

import pytest

from bench import harness

ROOT = harness.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BM = json.load(_f)
CELLS = [w["name"] for w in BM["workloads"]]
CONFIGS = [c["name"] for c in BM["configs"]]
METRICS = BM["end_to_end"] + BM["per_layer"]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_size():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= BM["run_seconds"] <= 51 and isinstance(BM["run_seconds"], int)


def test_paths_and_command():
    assert 1 <= len(BM["paths"]) <= 16
    for p in BM["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p) and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= len(BM["command"]) <= 32
    for word in BM["command"]:
        assert _line(word) and not word.startswith("/")
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in BM["paths"])


def test_check_fits_with_24_cells():
    """A full check makes 2 + 14 × cells runs of run_seconds + 60 s, and
    2 × 90 s of compiling per cell, with 1200 s spare, in 43200 s."""
    n = 24
    total = (2 + 14 * n) * (BM["run_seconds"] + 60) + n * 180 + 1200
    assert total <= 43200


@pytest.mark.parametrize("entry", BM["configs"] + BM["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    for key in ("why", "layer", "source"):
        if key in entry:
            assert _line(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES


def test_names_unique():
    for group in (BM["configs"], BM["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_end_to_end_metrics():
    names = {m["name"]: m for m in BM["end_to_end"]}
    assert set(names) == {"images_per_s", "latency_ms_p95",
                          "latency_ms_mean", "setup_s"}
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    # a cell is offline (throughput) or online (latency), never both;
    # latency is that of single-image calls, its tail beside its mean over
    # the whole window, which a stall of the window moves
    online = names["latency_ms_p95"]["workloads"]
    assert not set(online) & set(names["images_per_s"]["workloads"])
    assert names["latency_ms_mean"]["workloads"] == online
    reg = harness.Registry(ROOT)
    for cell in online:
        assert reg.traffic(reg.cell(cell)["traffic"])["batch"] == 1


@pytest.mark.parametrize("m", BM["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(m):
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert set(m["workloads"]) <= set(CELLS)
    for cell in m["workloads"]:
        reported = [e["name"] for e in harness.Registry(ROOT).metrics(
            "end_to_end", cell)]
        assert m["moves"] in reported
    assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                       m["name"] + ".py"))
    if "_roofline" in m["name"]:
        assert m["unit"] == "%" and m["layer"] == "kernels"


@pytest.mark.parametrize("cell", BM["workloads"], ids=lambda c: c["name"])
def test_cell_names_known_files(cell):
    """Each cell names a known configuration, a traffic file the generator
    reads, and a chip count; it reports set-up, another end-to-end metric
    and a per-layer metric."""
    reg = harness.Registry(ROOT)
    assert cell["config"] in CONFIGS and cell["chips"] in (1, 4)
    cfg = reg.config(cell["config"])
    assert cfg["name"] == cell["config"]
    t = reg.traffic(cell["traffic"])
    assert t["loop"] == "closed" and t["clients"] == 1
    for key in ("batch", "pool", "check_calls", "ref_block", "trace_calls"):
        assert isinstance(t[key], int) and t[key] > 0
    e2e = [m["name"] for m in reg.metrics("end_to_end", cell["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert reg.metrics("per_layer", cell["name"])
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}


def test_four_chip_cells_at_most_half():
    assert sum(w["chips"] == 4 for w in BM["workloads"]) <= max(
        1, len(BM["workloads"]) // 2)


@pytest.mark.parametrize("entry", BM["configs"], ids=lambda e: e["name"])
def test_config_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert any(entry["file"].startswith(p + "/") for p in BM["paths"])
    files = [c["file"] for c in BM["configs"]]
    assert files.count(entry["file"]) == 1
    cfg = harness.Registry(ROOT).config(entry["name"])
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert len(entry["reduced"]) <= 16
    assert all(NAME.match(k) for k in entry["reduced"])


def _family(cfg):
    return importlib.import_module(f"bench.{cfg['family']}")


@pytest.mark.parametrize("name", CONFIGS)
def test_pinned_work_follows_from_plan(name):
    """``work`` in the configuration file is what the family counts from
    the stored plan (for CNNs ``bench.cost.work``, at unpadded shapes)."""
    cfg = harness.Registry(ROOT).config(name)
    assert cfg["work"] == _family(cfg).work(cfg, cfg["plan_json"])


@pytest.mark.parametrize("name", CONFIGS)
def test_config_layers_are_the_programs(name):
    """The configuration's layers are the program's network and its stored
    plan is one the program accepts (the family's ``check_config``); the
    plan was made by the program from tables timed on the chip."""
    cfg = harness.Registry(ROOT).config(name)
    _family(cfg).check_config(cfg)
    prov = cfg["plan_json"]["provenance"]
    assert prov["predicted_speedup"] > 1 and "wallclock" in prov["command"]


def test_cnn_family_is_todays_counts_and_checks():
    """For CNNs the family's work is ``bench.cost.work``, and its check
    refuses a plan over another number of layers."""
    from bench import cnn, cost

    cfg = harness.Registry(ROOT).config("resnet34")
    assert cnn.work(cfg, cfg["plan_json"]) == cost.work(cfg, cfg["plan_json"])
    short = json.loads(cfg["plan_text"])
    short["num_layers"] -= 1
    with pytest.raises(ValueError):
        cnn.check_config({**cfg, "plan_text": json.dumps(short)})


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        harness.peak_of("TPU v99")
    peak = harness.peak_of("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12 and peak["bytes_per_s"] == 819e9
