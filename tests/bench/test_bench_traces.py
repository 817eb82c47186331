"""The trace reduction (``bench/traces.py``) and the per-layer readers.

``data/resnet34_online_b1.trace.pbtxt`` is a trace of two calls of the
``resnet34.online_b1`` cell recorded on a TPU v5 lite, trimmed to the
planes and lines the reduction reads (XLA op names other than the Pallas
calls cut to 100 characters); ``data/resnet34_online_b1.window.json``
holds the host-clock window of those two calls and the compiled
forward's Pallas call lines.
"""
import json
import os

import pytest

from bench import cost, harness, readers, traces

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _op(name, start, dur):
    return [name, float(start), float(dur)]


def _record():
    """Two calls on one chip: a 10 µs kernel, a 4 µs op, a 1 µs gap inside
    the call and a 5 µs gap between the calls (times in ns)."""
    ops, modules = [], []
    for c, t0 in enumerate((0, 20_000)):
        ops += [_op("fusion.1", t0, 4_000),
                _op("k.1", t0 + 5_000, 10_000)]
        modules.append([f"jit_f({c})", t0, 15_000])
    text = ("%k.1 = f32[2,8,8,64]{3,2,1,0} custom-call(%a, %b, %c), "
            'custom_call_target="tpu_custom_call", operand_layout_'
            "constraints={f32[2,1,1,10,16,128]{5,4,3,2,1,0}, "
            "f32[3,3,128,64]{3,2,1,0}, f32[1,64]{1,0}}, backend_config={}")
    return {"devices": [{"plane": "/device:TPU:0", "ops": ops,
                         "modules": modules}],
            "custom_calls": {"k.1": text}}


def test_reduce_busy_idle_and_kernels():
    r = traces.reduce(_record(), window_s=40e-6)
    assert r.busy_s == pytest.approx(28e-6)
    assert r.op_s == pytest.approx(28e-6)
    assert r.kernel_s == {"merged_conv": pytest.approx(20e-6)}
    assert r.kernel_order == [("merged_conv", 1.0)]
    gaps = dict(r.idle_gaps)
    assert gaps[traces.BETWEEN_CALLS] == pytest.approx(5e-6)
    assert gaps["inside a call, before k.1"] == pytest.approx(2e-6)
    assert gaps[traces.EDGES] == pytest.approx(5e-6)
    assert r.top_ops[0] == ["merged_conv:k.1", pytest.approx(20e-6)]


def test_overlapping_ops_count_once_in_busy():
    rec = _record()
    rec["devices"][0]["ops"].append(_op("copy.1", 1_000, 8_000))
    r = traces.reduce(rec, window_s=40e-6)
    assert r.busy_s == pytest.approx(28e-6 + 1e-6)
    assert r.op_s == pytest.approx(36e-6)


def test_no_device_plane_reads_nothing():
    r = traces.reduce({"devices": []}, window_s=1.0)
    assert r.busy_s == 0 and r.kernel_s == {}
    ctx = harness.MetricContext(trace=r, work={"units": [],
                                               "flops_per_image": 1},
                                peak=harness.peak_of("TPU v5 lite"), batch=1,
                                calls=1, images_per_s=1.0, chips=1)
    reg = harness.Registry()
    for m in ("merged_conv_roofline", "depthwise_conv_roofline",
              "executor.outside_kernel_share", "device.idle_share"):
        assert reg.reader(m)(ctx) is None


@pytest.mark.parametrize("name,kind", [
    ("merged_conv.7", "merged_conv"), ("depthwise_conv", "depthwise_conv")])
def test_named_kernels_are_taken_at_their_name(name, kind):
    assert traces.kernel_kind(name, "") == kind


def test_kernel_kind_from_operands():
    dw = ("%_lambda_.40 = f32[1,56,56,128]{3,2,1,0} custom-call(%a, %b, %c),"
          ' custom_call_target="tpu_custom_call", operand_layout_'
          "constraints={f32[1,1,1,56,64,128]{5,4,3,2,1,0}, "
          "f32[1,1,128,1]{3,2,1,0}, f32[1,128]{1,0}}, backend_config={}")
    assert traces.kernel_kinds(dw) == {"_lambda_.40": "depthwise_conv"}
    assert traces.kernel_kind("x", "%x = f32[2] custom-call()") == "pallas"


# -- the chip trace ----------------------------------------------------------

@pytest.fixture(scope="module")
def chip():
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "resnet34_online_b1.trace.pbtxt")) as f:
        pd = ProfileData.from_text_proto(f.read())
    with open(os.path.join(DATA, "resnet34_online_b1.window.json")) as f:
        win = json.load(f)
    rec = traces.extract(pd)
    kinds = traces.kernel_kinds(win["custom_calls"])
    return rec, win, traces.reduce(rec, win["window_s"], kinds)


def test_chip_trace_attribution(chip):
    """Every Pallas call of the plan is found and told apart: 17
    merged_conv and 2 depthwise (1×1 identity) units per call."""
    rec, win, r = chip
    cfg = harness.Registry().config("resnet34")
    plan_kernels = [u["kernel"] for u in cfg["work"]["units"] if u["kernel"]]
    assert plan_kernels.count("merged_conv") == 17
    assert plan_kernels.count("depthwise_conv") == 2
    assert [k for k, _ in r.kernel_order] == plan_kernels
    (dev,) = rec["devices"]
    assert len(dev["modules"]) == win["calls"]
    assert len(rec["custom_calls"]) == len(plan_kernels)
    assert 0 < r.busy_s < r.window_s
    assert sum(r.kernel_s.values()) < r.op_s


def test_chip_trace_readers(chip):
    """Rooflines and shares read from the recorded trace lie in (0, 100]."""
    _, win, r = chip
    cfg = harness.Registry().config("resnet34")
    ctx = harness.MetricContext(trace=r, work=cfg["work"],
                                peak=harness.peak_of("TPU v5 lite"), batch=1,
                                calls=win["calls"], images_per_s=500.0,
                                chips=1)
    reg = harness.Registry()
    for m in ("merged_conv_roofline", "depthwise_conv_roofline",
              "executor.outside_kernel_share", "device.idle_share", "mfu"):
        v = reg.reader(m)(ctx)
        assert 0 < v <= 100, (m, v)
    fracs = [f for _, f in r.kernel_order]
    bound = cost.kernel_bound_seconds(cfg["work"]["units"], "merged_conv",
                                      1, ctx.peak, fracs)
    assert reg.reader("merged_conv_roofline")(ctx) == pytest.approx(
        100 * win["calls"] * bound / r.kernel_s["merged_conv"])


def test_chip_trace_on_chip_memory(chip):
    """At batch 1 the compiled forward keeps every kernel operand in the
    chip's on-chip memory (layout S(1)): no byte of a kernel call counts
    against the HBM, and the bound falls to operations over peak."""
    _, _, r = chip
    assert all(f == 0.0 for _, f in r.kernel_order)
    text = ("%k = f32[8,4]{1,0:T(8,128)} custom-call(f32[8,4]{1,0:T(8,128)"
            "S(1)} %a, f32[8,4]{1,0} %b), custom_call_target=\"x\"")
    assert traces.hbm_fraction(text) == pytest.approx(2 / 3)


# -- forwards per call --------------------------------------------------------

UNITS = [{"unit": "conv0_1", "kernel": "merged_conv", "flops": 4_000_000,
          "bytes": 40_000, "weight_bytes": 9_000},
         {"unit": "proj0_1", "kernel": None, "flops": 1_000, "bytes": 800,
          "weight_bytes": 100},
         {"unit": "conv1_2", "kernel": "depthwise_conv", "flops": 90_000,
          "bytes": 600_000, "weight_bytes": 400}]


def _forward_ctx(forwards, order, scale=1.0):
    """Three calls of ``forwards`` forwards of :data:`UNITS` at batch 2:
    ``scale`` times one forward's kernel time, and images per second in
    proportion to one over the forwards."""
    red = traces.Reduced(
        window_s=1.0, busy_s=0.5, op_s=0.5,
        kernel_s={"merged_conv": 3e-6 * scale, "depthwise_conv": 2e-6 * scale},
        top_ops=[], idle_gaps=[], kernel_order=order)
    return harness.MetricContext(
        trace=red, work={"units": UNITS, "flops_per_image": 4_091_000},
        peak=harness.peak_of("TPU v5 lite"), batch=2, calls=3,
        images_per_s=400.0 / forwards, chips=1, forwards=forwards)


ONE = [("merged_conv", 1.0), ("depthwise_conv", 0.25)]


def test_readers_count_each_forward_once():
    """Two forwards a call, twice the kernel time and the kernel order
    repeated read as one forward does; so does MFU at half the images per
    second."""
    reg = harness.Registry()
    one, two = _forward_ctx(1, ONE), _forward_ctx(2, ONE * 2, scale=2.0)
    for m in ("merged_conv_roofline", "depthwise_conv_roofline", "mfu"):
        a, b = reg.reader(m)(one), reg.reader(m)(two)
        assert a is not None and b == pytest.approx(a, rel=1e-12), m
    bound = cost.kernel_bound_seconds(UNITS, "depthwise_conv", 2,
                                      one.peak, [1.0, 0.25])
    assert reg.reader("depthwise_conv_roofline")(one) == \
        100 * 3 * bound / 2e-6


@pytest.mark.parametrize("forwards,order", [
    (2, ONE),                                   # one forward's kernels
    (1, ONE * 2),                               # two where one is planned
    (2, ONE + ONE[::-1]),                       # the second out of order
    (2, ONE + ONE[:1]),                         # a kernel missing
])
def test_misaligned_kernel_order_reads_none(forwards, order):
    ctx = _forward_ctx(forwards, order, scale=forwards)
    for kernel in ("merged_conv", "depthwise_conv"):
        assert readers.kernel_roofline(ctx, kernel) is None

