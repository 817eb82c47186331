"""Depthwise/grouped merged-conv kernel certification (this PR's tentpole).

The depthwise kernel puts MobileNetV2's merged segments on the Pallas
fast path: channel-blocked grid, per-group fp32 accumulators, the shared
phase-major DMA-halo pipeline.  Everything here runs the kernel in
interpret mode on CPU against the ``lax.conv_general_dilated`` grouped
oracle:

* the acceptance matrix — strides {1, 2} × kernel sizes {1, 3, 5} at a
  channel count that is NOT a multiple of 8 (group-padding path);
* a hypothesis property sweep over ``(stride, k, channels, tiles,
  dtype)`` including ragged last tiles and channel-multiplier weights;
* grouped (``feature_group_count < Cin``, ``Cin_g > 1``) cases, with
  explicit multi-group blocks;
* the grouped 2-D VMEM planner and the group-block chooser;
* no-oracle-fallback under ``force_backend('pallas')``;
* tiling as pure scheduling (exact float equality across tile splits).
"""
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro import kernels
from repro.kernels.depthwise_conv import (choose_group_block,
                                          choose_tiles_grouped,
                                          depthwise_conv)
from repro.kernels.merged_conv import _VMEM_BUDGET

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _oracle(x, w, b, stride, groups, act=None):
    y = kernels.depthwise_conv_ref(x, w, b, stride=stride, groups=groups)
    return kernels.apply_activation(y, act)


# ---------------------------------------------------------------------------
# acceptance matrix: strides {1, 2} × k {1, 3, 5}, C=13 (not a multiple of 8)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("k", [1, 3, 5])
def test_depthwise_matrix(stride, k):
    rng = np.random.default_rng(stride * 100 + k)
    c = 13
    x = jnp.asarray(rng.standard_normal((2, 15, 13, c)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, 1, c)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.standard_normal(c), jnp.float32)
    y = kernels.depthwise_conv_op(x, w, b, stride=stride, activation="relu6",
                                  interpret=True)
    yr = _oracle(x, w, b, stride, c, "relu6")
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("stride", [1, 2])
def test_depthwise_no_oracle_fallback(stride):
    """With the backend forced to 'pallas', depthwise convs must go through
    pl.pallas_call (interpret on CPU) — not the jnp fallback."""
    rng = np.random.default_rng(7 + stride)
    x = jnp.asarray(rng.standard_normal((1, 12, 12, 6)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 1, 6)) * 0.1, jnp.float32)
    with kernels.force_backend("pallas"):
        y = kernels.depthwise_conv_op(x, w, stride=stride, interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_oracle(x, w, None, stride, 6)),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# property sweep: (stride, k, channels, cout_mult, tiles, dtype)
# ---------------------------------------------------------------------------

@given(stride=st.integers(1, 2), k=st.sampled_from([1, 3, 5]),
       c=st.integers(3, 19), cout_mult=st.sampled_from([1, 1, 1, 2]),
       tile_ho=st.integers(1, 6), tile_wo=st.integers(1, 6),
       h=st.integers(8, 18), w=st.integers(8, 18), bf16=st.booleans())
@settings(max_examples=24, deadline=None)
def test_depthwise_property(stride, k, c, cout_mult, tile_ho, tile_wo, h, w,
                            bf16):
    if h < k or w < k:
        return
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    rng = np.random.default_rng(stride * 1009 + k * 131 + c * 17
                                + tile_ho * 7 + tile_wo * 3 + h * 29 + w
                                + cout_mult)
    x = jnp.asarray(rng.standard_normal((1, h, w, c)), dtype)
    wt = jnp.asarray(rng.standard_normal((k, k, 1, c * cout_mult)) * 0.1,
                     dtype)
    b = jnp.asarray(rng.standard_normal(c * cout_mult), dtype)
    y = kernels.depthwise_conv_op(x, wt, b, stride=stride, groups=c,
                                  tile_ho=tile_ho, tile_wo=tile_wo,
                                  activation="relu6", interpret=True)
    yr = _oracle(x, wt, b, stride, c, "relu6")
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **TOL[dtype])


# ---------------------------------------------------------------------------
# grouped (feature_group_count < Cin): per-group MXU contractions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("groups,cin_g,cout_g,bgroups", [
    (4, 6, 6, 1), (4, 6, 6, 2), (4, 6, 6, 4),
    (6, 2, 2, 4),                         # group padding: 6 → 8
    (2, 8, 4, 1),                         # cout_g != cin_g
])
def test_grouped_conv(groups, cin_g, cout_g, bgroups):
    rng = np.random.default_rng(groups * 31 + cin_g * 7 + bgroups)
    cin, cout = groups * cin_g, groups * cout_g
    x = jnp.asarray(rng.standard_normal((2, 12, 11, cin)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, cin_g, cout)) * 0.1,
                    jnp.float32)
    b = jnp.asarray(rng.standard_normal(cout), jnp.float32)
    for s in (1, 2):
        y = depthwise_conv(x, w, b, stride=s, groups=groups, bgroups=bgroups,
                           interpret=True)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(_oracle(x, w, b, s, groups)),
                                   rtol=2e-5, atol=2e-5)


def test_grouped_op_dispatch():
    """depthwise_conv_op with explicit groups routes grouped weights."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((1, 10, 10, 12)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 3, 8)) * 0.1, jnp.float32)
    y = kernels.depthwise_conv_op(x, w, stride=1, groups=4, interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_oracle(x, w, None, 1, 4)),
                               rtol=2e-5, atol=2e-5)


def test_tiling_is_pure_scheduling():
    """Any (tile_ho, tile_wo, bgroups) split produces the same floats per
    output element — accumulation order per element never changes."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 13, 14, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 1, 8)) * 0.1, jnp.float32)
    for s in (1, 2):
        whole = depthwise_conv(x, w, stride=s, groups=8, bgroups=8,
                               tile_ho=64, tile_wo=64, interpret=True)
        for tho, two, bg in ((1, 64, 8), (64, 1, 8), (2, 3, 8), (5, 4, 4)):
            tiled = depthwise_conv(x, w, stride=s, groups=8, bgroups=bg,
                                   tile_ho=tho, tile_wo=two, interpret=True)
            np.testing.assert_array_equal(np.asarray(whole),
                                          np.asarray(tiled))


# ---------------------------------------------------------------------------
# grouped VMEM planner + group-block chooser
# ---------------------------------------------------------------------------

def _working_set(tho, two, cin_g, cout_g, kh, kw, s, itemsize, bg):
    shi = s * tho + kh - 1
    swi = s * two + kw - 1
    bcin = bg * cin_g
    return (2 * shi * swi * bcin * itemsize             # double-buffered in
            + kh * kw * bg * cin_g * cout_g * itemsize  # weight block
            + tho * two * bg * cout_g * (4 + itemsize))  # fp32 acc + out


@pytest.mark.parametrize("h,w,cin_g,cout_g,k,s,bg", [
    (224, 224, 1, 1, 7, 1, 128), (224, 224, 1, 1, 7, 2, 128),
    (112, 112, 1, 1, 5, 2, 32),
    (8, 8192, 1, 1, 3, 1, 128),             # panorama: single very wide row
    (16, 16, 1, 1, 3, 1, 8),
    (56, 56, 8, 8, 3, 1, 1),                # grouped footprint
])
def test_choose_tiles_grouped_bounds_working_set(h, w, cin_g, cout_g, k, s,
                                                 bg):
    tho, two = choose_tiles_grouped(h, w, cin_g, cout_g, k, k, s, 4,
                                    bgroups=bg)
    ho = (h - k) // s + 1
    wo = (w - k) // s + 1
    assert 1 <= tho <= ho and 1 <= two <= wo
    assert _working_set(tho, two, cin_g, cout_g, k, k, s, 4, bg) \
        <= _VMEM_BUDGET or (tho == 1 and two == 1)


def test_choose_group_block():
    # depthwise: one whole 128-lane channel block (Mosaic slices the lane
    # axis of a DMA window only at whole tiles; the group axis pads up)
    assert choose_group_block(32, 1, 1) == 128
    assert choose_group_block(13, 1, 1) == 128
    assert choose_group_block(960, 1, 1) == 128
    # an explicit block (interpret-mode sweeps) rounds to a multiple of 8
    assert choose_group_block(32, 1, 1, 13) == 16
    # channel multiplier folds into the lane width
    assert choose_group_block(32, 1, 4, 128) * 4 <= 128
    assert choose_group_block(32, 1, 4) >= 1
    # grouped MXU path: one group per step
    assert choose_group_block(4, 6, 6) == 1


def test_depthwise_traffic_model_reports_halo_saving():
    """Depthwise rows report halo_bytes_saved (group-blocking invariant:
    same aggregate DMA traffic as a dense kernel over the same image)."""
    from repro.kernels.merged_conv import input_traffic_model
    dense = input_traffic_model(230, 230, 64, 7, 7, 1, 2,
                                tile_ho=8, tile_wo=224)
    dw = input_traffic_model(230, 230, 64, 7, 7, 1, 2,
                             tile_ho=8, tile_wo=224, groups=64)
    assert dw["dma_bytes"] == dense["dma_bytes"]
    assert dw["halo_bytes_saved"] == dense["halo_bytes_saved"]
    assert dw["halo_bytes_saved"] > 0
    # default-tiles path consults the grouped planner, still well-formed
    auto = input_traffic_model(114, 114, 32, 3, 3, 2, 2, groups=32)
    assert auto["dma_bytes"] > 0 and auto["relayout_bytes"] > 0
    assert auto["halo_bytes_saved"] == (auto["gather_bytes"]
                                        - auto["dma_bytes"])
