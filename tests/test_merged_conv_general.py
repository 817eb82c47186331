"""Generalized merged-conv kernel certification (this PR's tentpole).

The kernel now serves *every* segment shape the DP can emit: strided
segments (the downsampling convs that dominate MobileNetV2/ResNet34),
W-axis tiles for very wide images, and zero-copy DMA halos from an
HBM-resident input.  Everything here runs the Pallas kernel in interpret
mode on CPU against ``lax.conv_general_dilated``:

* the acceptance matrix — strides {1, 2} × kernel sizes {1, 3, 5, 7};
* a hypothesis property sweep over ``(stride, kh, kw, tile_ho, tile_wo,
  dtype)`` including ragged last tiles on both axes;
* the 2-D ``(tile_ho, tile_wo)`` VMEM planner's accounting;
* the lane-friendly output-channel tile (``bcout`` regression);
* the input-traffic model backing the halo-bytes-saved bench;
* the stride-aware segment enumerator (k coordinate == true merged
  kernel size on strided spans);
* narrow inputs folded into one contraction (``ops.fold_taps``), and the
  units of the benchmark's plans the rule selects.
"""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro import kernels
from repro.kernels.merged_conv import (_VMEM_BUDGET, choose_tiles,
                                       input_traffic_model, merged_conv)

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _oracle(x, w, b, stride, act=None):
    return kernels.apply_activation(kernels.merged_conv_ref(x, w, b, stride=stride),
                                act)


# ---------------------------------------------------------------------------
# acceptance matrix: strides {1, 2} × kernel sizes {1, 3, 5, 7}
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("k", [1, 3, 5, 7])
def test_strided_merged_conv_matrix(stride, k):
    rng = np.random.default_rng(stride * 100 + k)
    x = jnp.asarray(rng.standard_normal((2, 15, 13, 4)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, 4, 6)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.standard_normal(6), jnp.float32)
    y = kernels.merged_conv_op(x, w, b, stride=stride, activation="relu",
                           interpret=True)
    yr = _oracle(x, w, b, stride, "relu")
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)


# (Cin, stride, k) that ops.fold_taps leaves on the tap path: the matrix
# above has Cin 4, which folds, so these pin the multi-tap kernel at
# strides 1–3 with Cin padded to 128 and 256 lanes
TAP_CASES = [(64, 1, 3), (64, 2, 3), (64, 2, 7), (64, 3, 7),
             (130, 1, 5), (130, 2, 3), (130, 2, 5), (130, 3, 7),
             (250, 1, 3), (250, 2, 5), (250, 3, 3), (250, 3, 5)]


@pytest.mark.parametrize("cin,stride,k", TAP_CASES)
def test_strided_merged_conv_matrix_tap_path(cin, stride, k):
    from repro.kernels import ops

    rng = np.random.default_rng(cin * 100 + stride * 10 + k)
    x = jnp.asarray(rng.standard_normal((2, 15, 13, cin)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, cin, 13)) * 0.05, jnp.float32)
    b = jnp.asarray(rng.standard_normal(13), jnp.float32)
    assert not ops.fold_taps(x.shape, w.shape, stride)
    before = sum(ops.FOLDED.values())
    y = kernels.merged_conv_op(x, w, b, stride=stride, activation="relu",
                               tile_ho=4, interpret=True)
    assert sum(ops.FOLDED.values()) == before
    yr = _oracle(x, w, b, stride, "relu")
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("stride", [1, 2, 3])
def test_strided_no_oracle_fallback(stride):
    """With the backend forced to 'pallas', strided convs must go through
    pl.pallas_call (interpret on CPU) — not the jnp fallback."""
    rng = np.random.default_rng(7 + stride)
    x = jnp.asarray(rng.standard_normal((1, 12, 12, 3)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 3, 5)) * 0.1, jnp.float32)
    with kernels.force_backend("pallas"):
        y = kernels.merged_conv_op(x, w, stride=stride, interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_oracle(x, w, None, stride)),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# property sweep: (stride, kh, kw, tile_ho, tile_wo, dtype), ragged tiles
# ---------------------------------------------------------------------------

@given(stride=st.integers(1, 3), kh=st.sampled_from([1, 2, 3, 5, 7]),
       kw=st.sampled_from([1, 2, 3, 5]), tile_ho=st.integers(1, 6),
       tile_wo=st.integers(1, 6), h=st.integers(8, 20), w=st.integers(8, 20),
       bf16=st.booleans())
@settings(max_examples=24, deadline=None)
def test_merged_conv_property(stride, kh, kw, tile_ho, tile_wo, h, w, bf16):
    if h < kh or w < kw:
        return
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    rng = np.random.default_rng(stride * 1009 + kh * 131 + kw * 17
                                + tile_ho * 7 + tile_wo * 3 + h * 29 + w)
    x = jnp.asarray(rng.standard_normal((1, h, w, 3)), dtype)
    wt = jnp.asarray(rng.standard_normal((kh, kw, 3, 5)) * 0.1, dtype)
    b = jnp.asarray(rng.standard_normal(5), dtype)
    y = kernels.merged_conv_op(x, wt, b, stride=stride, tile_ho=tile_ho,
                           tile_wo=tile_wo, activation="relu6",
                           interpret=True)
    yr = _oracle(x, wt, b, stride, "relu6")
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **TOL[dtype])


@given(stride=st.integers(1, 3), kh=st.sampled_from([1, 2, 3, 5, 7]),
       kw=st.sampled_from([1, 2, 3, 5]), tile_ho=st.integers(1, 6),
       tile_wo=st.integers(1, 6), h=st.integers(8, 20), w=st.integers(8, 20),
       cin=st.sampled_from([64, 130, 250]), bf16=st.booleans())
@settings(max_examples=16, deadline=None)
def test_merged_conv_property_wide_cin(stride, kh, kw, tile_ho, tile_wo, h,
                                       w, cin, bf16):
    """The sweep above at Cin 64, 130 and 250, where most shapes stay on
    the tap path: each conv runs folded exactly when ``ops.fold_taps``
    says so, and either path matches the oracle."""
    from repro.kernels import ops

    if h < kh or w < kw:
        return
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    rng = np.random.default_rng(stride * 1009 + kh * 131 + kw * 17 + cin
                                + tile_ho * 7 + tile_wo * 3 + h * 29 + w)
    x = jnp.asarray(rng.standard_normal((1, h, w, cin)), dtype)
    wt = jnp.asarray(rng.standard_normal((kh, kw, cin, 5)) * 0.05, dtype)
    b = jnp.asarray(rng.standard_normal(5), dtype)
    fold = ops.fold_taps(x.shape, wt.shape, stride)
    before = sum(ops.FOLDED.values())
    y = kernels.merged_conv_op(x, wt, b, stride=stride, tile_ho=tile_ho,
                               tile_wo=tile_wo, activation="relu6",
                               interpret=True)
    assert sum(ops.FOLDED.values()) - before == int(fold)
    yr = _oracle(x, wt, b, stride, "relu6")
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **TOL[dtype])


def test_tiling_is_pure_scheduling_all_strides():
    """Any (tile_ho, tile_wo) split produces the same floats per output
    element.  Small-integer data makes every product and partial sum
    exact in fp32, so the check is independent of how the backend's dot
    associates a contraction, and only the tiling itself can differ."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(-4, 5, (2, 17, 30, 4)), jnp.float32)
    w = jnp.asarray(rng.integers(-4, 5, (3, 3, 4, 4)), jnp.float32)
    for s in (1, 2):
        whole = merged_conv(x, w, stride=s, bcout=4, tile_ho=64, tile_wo=64,
                            interpret=True)
        for tho, two in ((1, 64), (64, 1), (2, 3), (5, 4)):
            tiled = merged_conv(x, w, stride=s, bcout=4, tile_ho=tho,
                                tile_wo=two, interpret=True)
            np.testing.assert_array_equal(np.asarray(whole),
                                          np.asarray(tiled))


# ---------------------------------------------------------------------------
# 2-D VMEM planner
# ---------------------------------------------------------------------------

def _working_set(tho, two, cin, kh, kw, s, itemsize, bcout):
    shi = s * tho + kh - 1
    swi = s * two + kw - 1
    return (2 * shi * swi * cin * itemsize              # double-buffered in
            + kh * kw * cin * bcout * itemsize          # weight block
            + tho * two * bcout * (4 + itemsize))       # fp32 acc + out


@pytest.mark.parametrize("h,w,cin,k,s", [
    (224, 224, 64, 7, 1), (224, 224, 64, 7, 2), (112, 112, 128, 5, 2),
    (8, 8192, 32, 3, 1),                    # panorama: single very wide row
    (4096, 8, 16, 3, 1), (16, 16, 8, 3, 1),
])
def test_choose_tiles_bounds_working_set(h, w, cin, k, s):
    tho, two = choose_tiles(h, w, cin, k, k, s, 4, bcout=128)
    ho = (h - k) // s + 1
    wo = (w - k) // s + 1
    assert 1 <= tho <= ho and 1 <= two <= wo
    assert _working_set(tho, two, cin, k, k, s, 4, 128) <= _VMEM_BUDGET or (
        tho == 1 and two == 1)
    # small images degenerate to a single untiled step
    if h * w * cin <= 2048:
        assert (tho, two) == (ho, wo)


def test_choose_tiles_shrinks_width_for_panorama():
    """A single output row of a very wide image must not bound the block."""
    tho, two = choose_tiles(8, 65536, 64, 3, 3, 1, 4, bcout=128)
    assert tho == 1 and two < 65534
    assert _working_set(1, two, 64, 3, 3, 1, 4, 128) <= _VMEM_BUDGET


# ---------------------------------------------------------------------------
# lane-friendly channel tiling (bcout regression)
# ---------------------------------------------------------------------------

def test_channel_tile_is_multiple_of_8():
    # the old divisor walk degraded to bc=1 on primes; now every choice is
    # a multiple of 8 and the channel axis is padded up instead.
    for cout in (1, 7, 13, 97, 100, 127, 128, 130, 257):
        bc = kernels.channel_tile(cout, None)
        assert bc % 8 == 0
        assert bc <= 128
    assert kernels.channel_tile(130, None) == 128
    assert kernels.channel_tile(24, None) == 24
    # explicit lane-hostile requests are rounded up, never searched down
    assert kernels.channel_tile(100, 7) == 8
    assert kernels.channel_tile(100, 48) == 48


@pytest.mark.parametrize("cout", [7, 13, 100, 130])
def test_odd_channel_counts_correct(cout):
    rng = np.random.default_rng(cout)
    x = jnp.asarray(rng.standard_normal((1, 10, 10, 3)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 3, cout)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.standard_normal(cout), jnp.float32)
    y = kernels.merged_conv_op(x, w, b, stride=2, activation="relu",
                           interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_oracle(x, w, b, 2, "relu")),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# folded taps: a narrow input contracts its kh·kw·Cin patch in one dot
# ---------------------------------------------------------------------------

# (kh, stride, Cin, Cout, H, W, tile_ho): the two stems, MobileNetV2's
# strided 32 → 96 merge and its 24 → 32 downsampling conv, at small sizes;
# tile_ho 3 leaves a ragged last row tile (8 and 6 output rows)
FOLD_CASES = [
    (7, 2, 3, 64, 21, 24, 3),
    (3, 2, 3, 32, 19, 17, None),
    (3, 2, 32, 96, 13, 16, 3),
    (3, 2, 24, 32, 15, 18, None),
]


@pytest.mark.parametrize("batch", [2, 128])
@pytest.mark.parametrize("mode", ["none", "int8", "w8a8"])
@pytest.mark.parametrize("kh,stride,cin,cout,h,w,tile_ho", FOLD_CASES,
                         ids=[f"{c[0]}x{c[0]}_s{c[1]}_cin{c[2]}"
                              for c in FOLD_CASES])
def test_folded_taps_match_oracle(kh, stride, cin, cout, h, w, tile_ho,
                                  mode, batch):
    """The folded path agrees with the oracle in float32, with int8
    weights and with int8 weights and activations, and is taken once:
    at batch 2 with the taps gathered, at batch 128 through the
    batch-major convolution (float activations) as well."""
    from repro.kernels import ops, quant

    assert ops.fold_taps((batch, h, w, cin), (kh, kh, cin, cout), stride)
    rng = np.random.default_rng(kh * 1000 + cin)
    x = jnp.asarray(rng.standard_normal((batch, h, w, cin)), jnp.float32)
    wf = jnp.asarray(rng.standard_normal((kh, kh, cin, cout)) * 0.1,
                     jnp.float32)
    b = jnp.asarray(rng.standard_normal(cout), jnp.float32)
    key = (kh, kh, cin, stride)
    before = ops.FOLDED[key]
    if mode == "none":
        y = kernels.merged_conv_op(x, wf, b, stride=stride, tile_ho=tile_ho,
                                   activation="relu", interpret=True)
        yr = _oracle(x, wf, b, stride, "relu")
    else:
        wq, ws = quant.quantize_int8(wf, axis=3)
        y = kernels.merged_conv_op(x, wq, b, stride=stride, tile_ho=tile_ho,
                                   activation="relu", w_scale=ws,
                                   act_quant=mode, interpret=True)
        yr = kernels.apply_activation(kernels.merged_conv_qref(
            x, wq, b, ws, stride=stride, act_quant=mode), "relu")
    assert ops.FOLDED[key] == before + 1
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               **TOL[jnp.float32])


def test_fold_batch_major_from_batch_32_float_only():
    """Folded convs build their patch batch-major from batch 32 up, for
    float activations alone; smaller batches sum the taps."""
    from repro.kernels import ops

    for n in (1, 2, 8, 31):
        assert not ops.fold_batch_major((n, 230, 230, 3), jnp.float32)
    for n in (32, 36, 128, 192, 256):
        assert ops.fold_batch_major((n, 230, 230, 3), jnp.float32)
        assert ops.fold_batch_major((n, 230, 230, 3), jnp.bfloat16)
        assert not ops.fold_batch_major((n, 230, 230, 3), jnp.int8)


@pytest.mark.parametrize("kh,stride,cin,cout,h,w,tile_ho",
                         [FOLD_CASES[0], FOLD_CASES[2]],
                         ids=["7x7_s2_cin3", "3x3_s2_cin32"])
def test_folded_batch_major_ragged_batch(kh, stride, cin, cout, h, w,
                                         tile_ho):
    """Batch-major at a batch of 36, which does not fill whole sublane
    tiles of the kernel's ``(Wo, N)`` block, matches the oracle."""
    from repro.kernels import ops

    x_shape = (36, h, w, cin)
    assert ops.fold_taps(x_shape, (kh, kh, cin, cout), stride)
    assert ops.fold_batch_major(x_shape, jnp.float32)
    rng = np.random.default_rng(kh * 1000 + cin + 36)
    x = jnp.asarray(rng.standard_normal(x_shape), jnp.float32)
    wf = jnp.asarray(rng.standard_normal((kh, kh, cin, cout)) * 0.1,
                     jnp.float32)
    b = jnp.asarray(rng.standard_normal(cout), jnp.float32)
    y = kernels.merged_conv_op(x, wf, b, stride=stride, tile_ho=tile_ho,
                               activation="relu", interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_oracle(x, wf, b, stride, "relu")),
                               **TOL[jnp.float32])


def test_fold_taps_selects_the_benchmark_stems_and_narrow_strided_units():
    """Over the benchmark's stored plans the rule folds ResNet-34's stem
    and MobileNetV2's stem, its strided 32 → 96 merge and its 24 → 32
    downsampling conv; never a unit with Cin ≥ 128, a 1×1 or a depthwise
    unit.  Shapes are the executor's: spatially padded by K − 1."""
    from repro.kernels import ops

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import cnn_reference as R

    want = {"resnet34": {0}, "mobilenetv2": {0, 1, 4}}
    for name, units in want.items():
        with open(os.path.join(root, "bench", "configs",
                               f"{name}.json")) as f:
            cfg = json.load(f)
        with open(os.path.join(root, "bench", "configs",
                               f"{name}.plan.json")) as f:
            plan = json.load(f)
        shapes = R.boundary_shapes(cfg)
        folded = set()
        for i, seg in enumerate(plan["segments"]):
            if R.layer(cfg, seg["j"])["kind"] != "conv":
                continue
            (h, w, cin), (_, _, cout) = shapes[seg["i"]], shapes[seg["j"]]
            k, s = R.geometry(cfg, seg)
            kept = [l for l in seg["kept"]
                    if R.layer(cfg, l)["kind"] == "conv"]
            dw = bool(kept) and all(R.layer(cfg, l)["depthwise"]
                                    for l in kept)
            fold = ops.fold_taps((128, h + k - 1, w + k - 1, cin),
                                 (k, k, 1 if dw else cin, cout), s)
            if fold:
                folded.add(i)
            if cin >= 128 or k == 1 or dw:
                assert not fold, (name, i)
        assert folded == units, name


# ---------------------------------------------------------------------------
# input-traffic model (halo-bytes accounting behind the bench sweep)
# ---------------------------------------------------------------------------

def test_input_traffic_single_tile_is_one_read():
    t = input_traffic_model(16, 16, 8, 3, 3, 1, 4, tile_ho=14, tile_wo=14)
    assert t["dma_bytes"] == t["image_bytes"]
    assert t["saved_bytes"] == 0.0          # the old path was also one read


def test_input_traffic_multi_tile_saves_gather():
    t = input_traffic_model(64, 64, 32, 5, 5, 1, 4, tile_ho=8, tile_wo=60)
    # DMA reads the image once plus seam halos — strictly less than the
    # gather's image read + halo'd-tile write + read back.
    assert t["image_bytes"] <= t["dma_bytes"] < t["gather_bytes"]
    assert t["saved_bytes"] > t["image_bytes"]   # reclaimed ≥ one image read
    # halo re-reads are bounded: (k−1) rows per interior seam
    n_th = -(-60 // 8)
    halo_rows = (n_th - 1) * 4 * 64 * 32 * 4
    assert t["dma_bytes"] <= t["image_bytes"] + halo_rows + 4 * 68 * 32 * 4


# ---------------------------------------------------------------------------
# stride-aware enumeration: k == true merged kernel size
# ---------------------------------------------------------------------------

def test_enumerator_k_matches_segment_geometry_on_strided_spans():
    from repro.core.plan import Segment
    from repro.models import cnn

    net = cnn.ConvNet(specs=(
        cnn.ConvSpec(3, 8, 3, 1, act="relu"),
        cnn.ConvSpec(8, 8, 3, 2, act="relu"),      # strided, forced kept
        cnn.ConvSpec(8, 8, 3, 1, act="relu"),
        cnn.ConvSpec(8, 8, 3, 1, act="relu"),
    ), in_hw=16)
    import jax
    params = cnn.init_params(net, jax.random.PRNGKey(0))
    from repro.models.cnn_host import CNNHost
    host = CNNHost(net, params, batch=1)
    enum = host.enumerator()
    found_strided = False
    for i, j, opts in enum.all_spans():
        has_stride = any(net.spec(l).stride > 1 for l in range(i + 1, j + 1))
        for k, (_val, kept) in opts.items():
            K, S = cnn.segment_geometry(net, Segment(i=i, j=j, k=k, kept=kept))
            assert k == K, (i, j, k, kept, K)
            if has_stride and j - i > 1 and K > 3:
                found_strided = True
    # the previously banned strided-then-k>1 merges are now offered
    assert found_strided


def test_strided_merge_replaced_equals_merged():
    """Replaced ≡ merged must hold for a span that merges a stride-2 conv
    with a following 3×3 conv (previously gated out)."""
    import jax
    from repro.core.plan import CompressionPlan, Segment
    from repro.models import cnn
    from repro.models.cnn_host import CNNHost

    net = cnn.ConvNet(specs=(
        cnn.ConvSpec(3, 8, 3, 1, act="relu"),
        cnn.ConvSpec(8, 8, 3, 2, act="relu"),
        cnn.ConvSpec(8, 8, 3, 1, act="relu"),
    ), in_hw=16)
    params = cnn.init_params(net, jax.random.PRNGKey(1))
    host = CNNHost(net, params, batch=2)
    # merge layers 2..3 (stride 2 then k=3): K = 1 + 2 + 2·2 = 7, S = 2
    seg = Segment(i=1, j=3, k=7, kept=(2, 3))
    K, S = cnn.segment_geometry(net, seg)
    assert (K, S) == (7, 2)
    plan = CompressionPlan(num_layers=3, segments=(
        Segment(i=0, j=1, k=3, kept=(1,), original=True), seg))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 16, 3))
    ra, _ = host.replaced_apply(plan)
    ma, _ = host.merged_apply(plan)
    np.testing.assert_allclose(np.asarray(ra(params, x)),
                               np.asarray(ma(params, x)),
                               rtol=1e-4, atol=1e-4)


def test_wallclock_oracle_median_of_groups():
    from repro.core.latency import WallClockOracle

    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        return jnp.zeros(())

    o = WallClockOracle(warmup=2, iters=10, groups=5)
    lat = o.time_callable(fn)
    assert calls["n"] == 12                 # warmup + iters, protocol shape
    assert lat > 0.0
    # degenerate: fewer iters than groups still times every call once
    calls["n"] = 0
    o2 = WallClockOracle(warmup=1, iters=3, groups=5)
    assert o2.time_callable(fn) > 0.0
    assert calls["n"] == 4
