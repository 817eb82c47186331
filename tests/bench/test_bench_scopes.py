"""Unit and role scopes of device ops, and host spans on the device's
clock (``bench/scopes.py``), as the harness hands them to the readers.

The synthetic cases plant what each function should find.  The CPU runs
drive the harness's traced window on a tiny cell: a CPU trace has no TPU
plane, so only the host spans are read.  The last tests read a small
trace recorded on the chip with the program's names in it.
"""
import json
import os
import time

import pytest

from bench import harness, readers, scopes, traces

from conftest import CPU

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _ctx(**kw):
    """A metric context with only the program's names filled in."""
    return harness.MetricContext(trace=None, work={}, peak={}, batch=1,
                                 calls=1, images_per_s=0.0, chips=1, **kw)


@pytest.mark.parametrize("op_name,want", [
    ("jit(<lambda>)/unit03/lane_pad/jit(_pad)/pad", ("unit03", "lane_pad")),
    ("jit(<lambda>)/unit12/kernel/merged_conv/pallas_call",
     ("unit12", "kernel")),
    ("jit(<lambda>)/unit00/epilogue/jit(clip)/min", ("unit00", "epilogue")),
    ("jit(<lambda>)/unit04/pad", ("unit04", "other")),
    ("jit(<lambda>)/head/dot_general", ("head", "head")),
    ("batch", ("", "other")),
    ("", ("", "other")),
    ("jit(f)/unit01/relayout/transpose;jit(f)/unit01/relayout/reshape",
     ("unit01", "relayout")),
    ("jit(f)/unit02/x/add;jit(f)/unit03/pad/jit(_pad)/pad",
     ("unit02", "mixed")),
    ("jit(f)/unit05/crop/slice;jit(f)/unit05/epilogue/jit(relu)/max",
     ("unit05", "mixed")),
    ("jit(f)/unit00/pad/jit(_pad)/pad;jit(f)/unit00/relayout/transpose",
     ("unit00", "layout")),
    ("batch;jit(f)/unit01/epilogue/add", ("unit01", "epilogue")),
    ("jit(f)/unit19/epilogue/jit(relu)/max;jit(f)/head/reduce_sum",
     ("unit19", "mixed")),
])
def test_scope_of(op_name, want):
    assert scopes.scope_of(op_name) == want


def test_op_names_from_compiled_text():
    text = (
        '  %pad.6 = f32[7,7,128,64]{3,2,1,0} pad(%copy.10, %c), '
        'padding=0_0x0_0x0_125x0_0, metadata={op_name="jit(f)/unit00/'
        'weight_prep/jit(_pad)/pad" stack_frame_id=3}\n'
        '  ROOT %merged_conv.1 = f32[8,128]{1,0} custom-call(%pad.0), '
        'custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/'
        'unit00/kernel/merged_conv/pallas_call"}, backend_config={}\n'
        '  %copy-start = (f32[64]) copy-start(%p)\n')
    assert scopes.op_names(text) == {
        "pad.6": "jit(f)/unit00/weight_prep/jit(_pad)/pad",
        "merged_conv.1": "jit(f)/unit00/kernel/merged_conv/pallas_call"}


FUSED = """\
%fused_computation (param_0.2: f32[8,8]) -> f32[8,5] {
  %param_0.2 = f32[8,8]{1,0} parameter(0)
  %slice.0 = f32[8,5]{1,0} slice(%param_0.2), slice={[0:8], [0:5]}, \
metadata={op_name="jit(f)/unit00/crop/slice" stack_frame_id=4}
  %constant.4 = f32[] constant(0), metadata={op_name="jit(f)/unit03/\
lane_pad/jit(_pad)/pad"}
  %broadcast.5 = f32[8,5]{1,0:T(8,128)} broadcast(%constant.4), \
dimensions={}, metadata={op_name="jit(f)/unit02/pad/jit(_pad)/pad"}
  ROOT %max.4 = f32[8,5]{1,0} maximum(%slice.0, %broadcast.5), \
metadata={op_name="jit(f)/unit00/epilogue/jit(relu)/max"}
}

%outer_computation (param_0.3: f32[8,8]) -> f32[8,5] {
  %param_0.3 = f32[8,8]{1,0} parameter(0)
  ROOT %inner = f32[8,5]{1,0} fusion(%param_0.3), kind=kLoop, \
calls=%fused_computation
}

ENTRY %main.2 (x.1: f32[8,8]) -> f32[8,5] {
  %x.1 = f32[8,8]{1,0} parameter(0), metadata={op_name="batch"}
  %copy.1 = f32[8,8]{0,1} copy(%x.1), metadata={op_name="batch"}
  ROOT %slice_maximum_fusion = f32[8,5]{1,0} fusion(%copy.1), \
kind=kLoop, calls=%outer_computation, metadata={op_name="jit(f)/unit00/\
epilogue/jit(relu)/max"}
}
"""


def test_a_fusion_is_named_by_the_ops_inside_it():
    """A crop fused into the activation (here through a nested fusion)
    carries both roles, so it is ``mixed``, not ``epilogue``.  The
    constant and its broadcast, which CSE left with another unit's pad
    names, do no work and are passed over."""
    names = scopes.op_names(FUSED)
    assert names["slice_maximum_fusion"] == (
        "jit(f)/unit00/epilogue/jit(relu)/max;jit(f)/unit00/crop/slice")
    assert names["copy.1"] == "batch"
    assert scopes.scope_of(names["slice_maximum_fusion"]) == (
        "unit00", "mixed")
    assert scopes.scope_of(names["copy.1"]) == ("", "other")


def _record():
    """One chip, two calls of a 10 µs kernel, a 4 µs lane pad and a 2 µs
    unscoped input copy; modules cover each call (times in ns)."""
    ops, modules = [], []
    for t0 in (0, 40_000):
        ops += [["copy.1", t0, 2_000], ["pad.1", t0 + 2_000, 4_000],
                ["merged_conv.1", t0 + 6_000, 10_000]]
        modules.append(["jit_f", t0, 16_000])
    return {"devices": [{"plane": "/device:TPU:0", "ops": ops,
                         "modules": modules}], "custom_calls": {}}


NAMES = {"pad.1": "jit(f)/unit00/lane_pad/jit(_pad)/pad",
         "merged_conv.1": "jit(f)/unit00/kernel/merged_conv/pallas_call",
         "copy.1": "batch"}


def test_role_seconds_and_shares():
    r = scopes.role_seconds(_record(), NAMES)
    assert r.op_s == pytest.approx(32e-6)
    assert r.by_role == {"other": pytest.approx(4e-6),
                         "lane_pad": pytest.approx(8e-6),
                         "kernel": pytest.approx(20e-6)}
    assert r.by_unit["kernel"] == {"unit00": pytest.approx(20e-6)}
    assert r.other_ops == [["copy.1", pytest.approx(4e-6)]]
    assert readers.layout_share(_ctx(roles=r)) == pytest.approx(25.0)
    assert readers.epilogue_share(_ctx(roles=r)) == pytest.approx(0.0)
    assert r.share([scopes.MIXED]) == pytest.approx(0.0)
    (line,) = [ln for ln in scopes.role_table(r, calls=2)
               if ln.startswith("lane_pad")]
    assert "0.0040 ms/call" in line and "unit00 0.0040" in line


def test_mixed_and_layout_fusions_count_apart():
    """A fusion of layout roles counts as layout; one of a crop and the
    activation counts in neither share."""
    names = dict(NAMES, **{
        "pad.1": "jit(f)/unit00/lane_pad/jit(_pad)/pad;"
                 "jit(f)/unit00/relayout/transpose",
        "copy.1": "jit(f)/unit00/crop/slice;jit(f)/unit00/epilogue/max"})
    r = scopes.role_seconds(_record(), names)
    assert r.by_role == {"layout": pytest.approx(8e-6),
                         "mixed": pytest.approx(4e-6),
                         "kernel": pytest.approx(20e-6)}
    assert readers.layout_share(_ctx(roles=r)) == pytest.approx(25.0)
    assert r.share([scopes.MIXED]) == pytest.approx(12.5)
    assert readers.epilogue_share(_ctx(roles=r)) == pytest.approx(0.0)
    assert any(ln.startswith("mixed") for ln in scopes.role_table(r, 2))


def test_no_ops_read_nothing():
    r = scopes.role_seconds({"devices": []}, {})
    for ctx in (_ctx(roles=r), _ctx()):
        assert readers.layout_share(ctx) is None
        assert readers.epilogue_share(ctx) is None
    assert r.share([scopes.MIXED]) is None


def _host(offset, dispatch=3_000, sync_tail=2_000, calls=(0, 40_000)):
    """Host spans for the calls of :func:`_record`, ``offset`` ns ahead of
    the device clock: each call's executor.apply starts ``dispatch`` ns
    before its program, and its bench.sync returns ``sync_tail`` ns after
    the program ends."""
    h = {scopes.APPLY: [], scopes.ISSUE: [], scopes.SYNC: []}
    for t0 in calls:
        a = t0 + offset - dispatch
        h[scopes.ISSUE].append([a - 500, dispatch + 1_000])
        h[scopes.APPLY].append([a, dispatch + 200])
        h[scopes.SYNC].append([a + dispatch + 600,
                               16_000 + sync_tail - 600])
    return h


@pytest.mark.parametrize("offset", [0, 734_000, -2_500_000, 10**15])
def test_align_recovers_a_planted_offset(offset):
    h = _host(offset)
    mods = [[s, d] for _, s, d in _record()["devices"][0]["modules"]]
    al = scopes.align(h[scopes.APPLY], h[scopes.SYNC], mods)
    # apply leads each program by 3 µs, sync trails it by 2 µs: δ lies in
    # [offset - 3 µs, offset + 2 µs]
    assert al.lo_ns == pytest.approx(offset - 3_000)
    assert al.hi_ns == pytest.approx(offset + 2_000)
    assert al.width_ns == pytest.approx(5_000)
    assert al.offset_ns == pytest.approx(offset - 500)


def test_align_gives_none_on_an_empty_interval_or_unpaired_calls():
    h = _host(0)
    mods = [[s, d] for _, s, d in _record()["devices"][0]["modules"]]
    # the second call's clocks slipped by 10 µs: no single offset fits
    h[scopes.APPLY][1][0] += 10_000
    h[scopes.SYNC][1][0] -= 10_000
    assert scopes.align(h[scopes.APPLY], h[scopes.SYNC], mods) is None
    h = _host(0)
    assert scopes.align(h[scopes.APPLY][:1], h[scopes.SYNC], mods) is None
    assert scopes.align([], [], []) is None


def test_dispatch_ms_is_the_median_apply_span():
    h = _host(0)
    h[scopes.APPLY].append([90_000, 9_000])
    assert readers.dispatch_ms(_ctx(host=h)) == pytest.approx(3.2e-3)
    assert readers.dispatch_ms(_ctx(host={scopes.APPLY: []})) is None
    assert readers.dispatch_ms(_ctx()) is None


def test_text_proto_round_trip_keeps_what_the_reductions_read():
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "resnet34_online_b1.trace.pbtxt")) as f:
        pd = ProfileData.from_text_proto(f.read())
    again = ProfileData.from_text_proto(scopes.to_text_proto(pd))
    a, b = traces.extract(pd), traces.extract(again)
    assert a["custom_calls"] == b["custom_calls"]
    (da,), (db,) = a["devices"], b["devices"]
    assert [o[0] for o in da["ops"]] == [o[0] for o in db["ops"]]
    for x, y in zip(da["ops"] + da["modules"], db["ops"] + db["modules"]):
        assert x[1:] == pytest.approx(y[1:], abs=1e-3)
    ha, hb = a["host"], b["host"]
    assert len(ha[scopes.ISSUE]) == len(hb[scopes.ISSUE]) == 2
    for x, y in zip(ha[scopes.ISSUE] + ha[scopes.SYNC],
                    hb[scopes.ISSUE] + hb[scopes.SYNC]):
        assert x == pytest.approx(y, abs=1e-3)


def test_traced_window_on_the_cpu_reads_the_host_spans(tiny_root,
                                                       tmp_path):
    """The harness's traced window at a toy size: the host spans are
    there, the device readings are not (no TPU plane), the window traced
    no new program, and the recorded window reads back."""
    import glob
    import shutil
    import tempfile

    from jax.profiler import ProfileData
    cell = harness.Cell(harness.Registry(tiny_root), "tiny_resnet.b4",
                        2**33 + 5)
    traced = dict(cell.ex.traces)
    assert traced == {("apply", (4, 16, 16, 3)): 1}
    tdir = tempfile.mkdtemp(dir=tmp_path)
    try:
        hlo, path, tw = cell.traced_window(3, tdir)
        pd = ProfileData.from_file(path)
        rec = traces.extract(pd)
        scopes.write_fixture(str(tmp_path), "tiny", pd, rec, hlo, tw, 3,
                             "cpu")
    finally:
        shutil.rmtree(tdir)
    assert dict(cell.ex.traces) == traced
    ctx = harness.MetricContext.from_trace(
        rec, tw, traces.kernel_kinds(hlo), scopes.op_names(hlo),
        work={}, peak={}, batch=4, calls=3, images_per_s=1.0, chips=1)
    assert len(rec["host"][scopes.APPLY]) == 3
    assert readers.dispatch_ms(ctx) > 0
    assert ctx.alignment is None
    assert readers.layout_share(ctx) is None
    (txt,) = glob.glob(str(tmp_path / "tiny.trace.pbtxt"))
    with open(txt) as f:
        host = traces.extract(ProfileData.from_text_proto(f.read()))["host"]
    assert len(host[scopes.APPLY]) == len(host[scopes.SYNC]) == 3
    with open(tmp_path / "tiny.window.json") as f:
        assert json.load(f)["calls"] == 3


def test_traced_run_reports_the_dispatch_span(tiny_root):
    """A cell listed under ``executor.dispatch_ms.online`` reports it from
    the program's ``executor.apply`` span; the readers of device time
    find no TPU plane here and leave their metrics out."""
    bm_path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(bm_path) as f:
        bm = json.load(f)
    for m in bm["per_layer"]:
        if m["name"] in ("executor.dispatch_ms.online",
                         "executor.layout_share"):
            m["workloads"].append("tiny_resnet.b4")
    with open(bm_path, "w") as f:
        json.dump(bm, f)
    path = os.path.join(tiny_root, "bench", "peaks.json")
    with open(path) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    with open(path, "w") as f:
        json.dump(peaks, f)
    r = harness.run(harness.Registry(tiny_root), "tiny_resnet.b4", 2**33 + 5,
                    0.2, True, time.perf_counter(), dict(CPU))
    assert r["correct"] is True
    assert 0 < r["metrics"]["executor.dispatch_ms.online"]["value"]
    assert r["metrics"]["executor.dispatch_ms.online"]["unit"] == "ms"
    assert "executor.layout_share" not in r["metrics"]


# -- the chip trace with the program's names ---------------------------------

@pytest.fixture(scope="module")
def scoped():
    """``data/resnet34_online_b1_scoped.*``: two calls of
    ``resnet34.online_b1`` recorded on a TPU v5 lite with the executor's
    scopes, named kernels and ``executor.apply`` span, trimmed by
    ``scopes.to_text_proto``; the window file holds the host-clock window,
    the Pallas call lines and each traced op's ``op_name``."""
    from jax.profiler import ProfileData
    base = os.path.join(DATA, "resnet34_online_b1_scoped")
    with open(base + ".trace.pbtxt") as f:
        pd = ProfileData.from_text_proto(f.read())
    with open(base + ".window.json") as f:
        win = json.load(f)
    rec = traces.extract(pd)
    return rec, rec["host"], win


def test_scoped_trace_names_every_kernel(scoped):
    """The kernels are found by their names, not their operands, and still
    line up one for one with the plan's kernel units."""
    rec, _, win = scoped
    kinds = traces.kernel_kinds(win["custom_calls"])
    assert kinds and all(name.split(".")[0] == kind
                         for name, kind in kinds.items())
    for name, text in rec["custom_calls"].items():
        assert traces.kernel_kind(name, "") == kinds[name]
        assert scopes.scope_of(win["op_names"][name])[1] == "kernel"
    r = traces.reduce(rec, win["window_s"], kinds)
    cfg = harness.Registry().config("resnet34")
    assert [k for k, _ in r.kernel_order] == [
        u["kernel"] for u in cfg["work"]["units"] if u["kernel"]]


def test_scoped_trace_roles(scoped):
    """Every traced op outside the entry's own copies carries its unit and
    role; the stem (unit00) holds most of the kernel time, and its crop,
    fused into the activation, reads ``mixed``."""
    rec, host, win = scoped
    roles = scopes.role_seconds(rec, win["op_names"])
    assert set(roles.by_role) <= set(scopes.ROLES) | {
        "layout", "mixed", "head", "other"}
    assert {"kernel", "relayout", "epilogue", "mixed", "head"} <= set(
        roles.by_role)
    assert "unit00" in roles.by_unit["mixed"]
    assert roles.share(("other",)) < 5
    stem = roles.by_unit["kernel"]["unit00"]
    assert stem > 0.5 * roles.by_role["kernel"]
    ctx = _scoped_ctx(rec, win)
    layout, epilogue = readers.layout_share(ctx), readers.epilogue_share(ctx)
    assert 0 < layout < 100 and 0 < epilogue < 100
    assert 0 < ctx.roles.share([scopes.MIXED]) < epilogue
    assert all(n.startswith(("merged_conv", "depthwise_conv"))
               for n in rec["custom_calls"])


def test_scoped_trace_alignment(scoped):
    """After alignment each call's program lies between the host entering
    its executor.apply and leaving its bench.sync; the context carries the
    same alignment."""
    rec, host, win = scoped
    (dev,) = rec["devices"]
    mods = [[s, d] for _, s, d in dev["modules"]]
    al = scopes.align(host[scopes.APPLY], host[scopes.SYNC], mods)
    assert al is not None and 0 < al.width_ns < 2e6
    for (a, _), (s, sd), (m, md) in zip(host[scopes.APPLY],
                                        host[scopes.SYNC], mods):
        assert a <= m + al.offset_ns <= m + md + al.offset_ns <= s + sd
    ctx = _scoped_ctx(rec, win)
    assert ctx.alignment == al
    assert 0 < readers.dispatch_ms(ctx) < 1.0


def _scoped_ctx(rec, win):
    cfg = harness.Registry().config("resnet34")
    return harness.MetricContext.from_trace(
        rec, win["window_s"], traces.kernel_kinds(win["custom_calls"]),
        win["op_names"], work=cfg["work"],
        peak=harness.peak_of("TPU v5 lite"), batch=1, calls=win["calls"],
        images_per_s=500.0, chips=1)


def test_scoped_trace_reads_every_online_metric(scoped):
    """Every per-layer metric of the online cells reads a number from the
    recorded window, shares and rooflines within (0, 100]."""
    rec, _, win = scoped
    reg = harness.Registry()
    ctx = _scoped_ctx(rec, win)
    for m in reg.metrics("per_layer", "resnet34.online_b1"):
        v = reg.reader(m["name"])(ctx)
        assert v is not None and v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100, (m["name"], v)
