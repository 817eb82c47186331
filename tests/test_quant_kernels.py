"""Quantized merged-kernel certification (this PR's tentpole).

Every quantized execution path — int8 weights (w8a16), int8
weights+activations (w8a8), and the fp8 scaffolding — runs the Pallas
kernels in interpret mode on CPU and is held to TWO references:

* the *quantized* jnp oracle (``*_qref``: dequantized-weight math) with a
  tight tolerance — certifies the kernel computes exactly the dequantized
  arithmetic it claims (post-accumulation per-channel scaling included);
* the *fp32* oracle within the RIGOROUS worst-case error budget of
  :func:`repro.kernels.quant.error_budget` — bounds, not tuned
  tolerances, so a quantization-semantics regression cannot hide inside a
  loose comparison.

Plus the shared primitive's contract: per-tensor mode bit-identical to
the historical ``optim.compress`` helpers (which now re-export it), and
per-channel round-trip error ≤ scale/2 elementwise.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro import kernels
from repro.kernels import quant

QTOL = dict(rtol=2e-4, atol=2e-4)      # kernel vs dequantized-math oracle


def _pad(x, K):
    lo = (K - 1) // 2
    hi = K - 1 - lo
    return jnp.pad(x, ((0, 0), (lo, hi), (lo, hi), (0, 0))) if K > 1 else x


def _conv_budget(mode, x, w, fan_in):
    return quant.error_budget(mode, fan_in=fan_in,
                              x_absmax=float(jnp.max(jnp.abs(x))),
                              w_absmax=float(jnp.max(jnp.abs(w))))


# ---------------------------------------------------------------------------
# shared primitive
# ---------------------------------------------------------------------------

def test_per_tensor_matches_optim_helpers():
    """optim.compress re-exports THE shared primitive (satellite: one
    rounding semantics repo-wide)."""
    from repro.optim import compress as oc
    assert oc.quantize_int8 is quant.quantize_int8
    assert oc.dequantize_int8 is quant.dequantize_int8


@given(seed=st.integers(0, 10_000), axis=st.sampled_from([None, 0, 1, -1]),
       scale=st.floats(1e-3, 1e3))
@settings(max_examples=24, deadline=None)
def test_int8_roundtrip_halfstep(seed, axis, scale):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((5, 7)) * scale, jnp.float32)
    q, s = quant.quantize_int8(x, axis=axis)
    assert q.dtype == jnp.int8
    if axis is not None:
        assert s.shape == (x.shape[axis],)
    y = quant.dequantize_int8(q, s, axis=axis)
    step = np.asarray(s) if axis is None else \
        np.expand_dims(np.asarray(s),
                       [i for i in range(x.ndim) if i != axis % x.ndim])
    assert np.all(np.abs(np.asarray(x - y)) <= step / 2 + 1e-12)


def test_fp8_roundtrip_relative():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    q, s = quant.quantize_fp8(x, axis=1)
    assert q.dtype == jnp.float8_e4m3fn
    y = quant.dequantize(q, s, axis=1)
    # e4m3 half-ulp: 2^-4 relative, after the per-channel rescale
    err = np.abs(np.asarray(x - y))
    bound = np.abs(np.asarray(x)) * 2.0 ** -4 + np.asarray(s)[None, :]
    assert np.all(err <= bound)


def test_error_budget_monotone_and_zero_for_fp():
    assert quant.error_budget("none", fan_in=9, x_absmax=1., w_absmax=1.) == 0
    b_int8 = quant.error_budget("int8", fan_in=9, x_absmax=1., w_absmax=1.)
    b_w8a8 = quant.error_budget("w8a8", fan_in=9, x_absmax=1., w_absmax=1.)
    assert 0 < b_int8 < b_w8a8


# ---------------------------------------------------------------------------
# dense merged conv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["int8", "w8a8", "fp8"])
@pytest.mark.parametrize("stride", [1, 2])
def test_merged_conv_quant_matrix(mode, stride):
    rng = np.random.default_rng(hash((mode, stride)) % 2**31)
    k, cin, cout = 3, 5, 13
    x = jnp.asarray(rng.standard_normal((2, 12, 12, cin)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, cin, cout)) * .3, jnp.float32)
    b = jnp.asarray(rng.standard_normal(cout), jnp.float32)
    wq, ws = quant.quantize_weight(w, mode, axis=3)
    xp = _pad(x, k)
    aq = mode if mode == "w8a8" else "none"
    y = kernels.merged_conv_op(xp, wq, b, stride=stride, w_scale=ws,
                               act_quant=aq, interpret=True)
    yq = kernels.merged_conv_qref(xp, wq, b, ws, stride=stride, act_quant=aq)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yq), **QTOL)
    yf = kernels.merged_conv_ref(xp, w, b, stride=stride)
    budget = _conv_budget(mode, x, w, fan_in=k * k * cin)
    maxdiff = float(jnp.max(jnp.abs(y - yf)))
    assert maxdiff <= budget, (maxdiff, budget)


@pytest.mark.parametrize("mode", ["int8", "w8a8", "fp8"])
@pytest.mark.parametrize("cin,stride", [(64, 2), (130, 1), (250, 3)])
def test_merged_conv_quant_tap_path(mode, cin, stride):
    """The matrix above at input widths that ``ops.fold_taps`` leaves on
    the multi-tap kernel (its Cin 5 folds), strides 1–3."""
    from repro.kernels import ops

    rng = np.random.default_rng(cin * 10 + stride)
    k, cout = 3, 13
    x = jnp.asarray(rng.standard_normal((2, 13, 13, cin)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, cin, cout)) * .1, jnp.float32)
    b = jnp.asarray(rng.standard_normal(cout), jnp.float32)
    wq, ws = quant.quantize_weight(w, mode, axis=3)
    xp = _pad(x, k)
    assert not ops.fold_taps(xp.shape, w.shape, stride)
    aq = mode if mode == "w8a8" else "none"
    y = kernels.merged_conv_op(xp, wq, b, stride=stride, w_scale=ws,
                               act_quant=aq, interpret=True)
    yq = kernels.merged_conv_qref(xp, wq, b, ws, stride=stride, act_quant=aq)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yq), **QTOL)
    yf = kernels.merged_conv_ref(xp, w, b, stride=stride)
    budget = _conv_budget(mode, x, w, fan_in=k * k * cin)
    maxdiff = float(jnp.max(jnp.abs(y - yf)))
    assert maxdiff <= budget, (maxdiff, budget)


@given(stride=st.integers(1, 2), k=st.sampled_from([1, 3, 5]),
       cin=st.integers(2, 9), cout=st.integers(3, 17),
       h=st.integers(8, 14), mode=st.sampled_from(["int8", "w8a8", "fp8"]))
@settings(max_examples=20, deadline=None)
def test_merged_conv_quant_sweep(stride, k, cin, cout, h, mode):
    rng = np.random.default_rng(hash((stride, k, cin, cout, h, mode))
                                % 2**31)
    x = jnp.asarray(rng.standard_normal((1, h, h, cin)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, cin, cout)) * .2, jnp.float32)
    wq, ws = quant.quantize_weight(w, mode, axis=3)
    xp = _pad(x, k)
    aq = mode if mode == "w8a8" else "none"
    y = kernels.merged_conv_op(xp, wq, None, stride=stride, w_scale=ws,
                               act_quant=aq, interpret=True)
    yq = kernels.merged_conv_qref(xp, wq, None, ws, stride=stride,
                                  act_quant=aq)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yq), **QTOL)
    yf = kernels.merged_conv_ref(xp, w, None, stride=stride)
    assert float(jnp.max(jnp.abs(y - yf))) <= \
        _conv_budget(mode, x, w, fan_in=k * k * cin)


def test_merged_conv_quant_no_oracle_fallback():
    """Quantized convs must route through pl.pallas_call when the backend
    is forced — the fast path exists, not just the qref."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((1, 10, 10, 4)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 4, 8)) * .2, jnp.float32)
    wq, ws = quant.quantize_weight(w, "int8", axis=3)
    xp = _pad(x, 3)
    with kernels.force_backend("pallas"):
        y = kernels.merged_conv_op(xp, wq, None, w_scale=ws, interpret=True)
    yq = kernels.merged_conv_qref(xp, wq, None, ws)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yq), **QTOL)


# ---------------------------------------------------------------------------
# depthwise / grouped merged conv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["int8", "w8a8"])
@pytest.mark.parametrize("stride", [1, 2])
def test_depthwise_quant_matrix(mode, stride):
    rng = np.random.default_rng(hash((mode, stride, "dw")) % 2**31)
    k, c = 3, 13                        # C not a multiple of 8: padding path
    x = jnp.asarray(rng.standard_normal((2, 11, 11, c)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, 1, c)) * .3, jnp.float32)
    b = jnp.asarray(rng.standard_normal(c), jnp.float32)
    wq, ws = quant.quantize_weight(w, mode, axis=3)
    xp = _pad(x, k)
    aq = mode if mode == "w8a8" else "none"
    y = kernels.depthwise_conv_op(xp, wq, b, stride=stride, w_scale=ws,
                                  act_quant=aq, interpret=True)
    yq = kernels.depthwise_conv_qref(xp, wq, b, ws, stride=stride,
                                     act_quant=aq)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yq), **QTOL)
    yf = kernels.depthwise_conv_ref(xp, w, b, stride=stride)
    assert float(jnp.max(jnp.abs(y - yf))) <= \
        _conv_budget(mode, x, w, fan_in=k * k)       # depthwise fan-in


@given(stride=st.integers(1, 2), k=st.sampled_from([1, 3, 5]),
       groups=st.integers(2, 6), cin_g=st.integers(1, 3),
       mode=st.sampled_from(["int8", "w8a8"]))
@settings(max_examples=16, deadline=None)
def test_grouped_quant_sweep(stride, k, groups, cin_g, mode):
    rng = np.random.default_rng(hash((stride, k, groups, cin_g, mode))
                                % 2**31)
    cin, cout = groups * cin_g, groups * 2
    x = jnp.asarray(rng.standard_normal((1, 10, 10, cin)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, cin_g, cout)) * .2,
                    jnp.float32)
    wq, ws = quant.quantize_weight(w, mode, axis=3)
    xp = _pad(x, k)
    aq = mode if mode == "w8a8" else "none"
    y = kernels.depthwise_conv_op(xp, wq, None, stride=stride, groups=groups,
                                  w_scale=ws, act_quant=aq, interpret=True)
    yq = kernels.depthwise_conv_qref(xp, wq, None, ws, stride=stride,
                                     groups=groups, act_quant=aq)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yq), **QTOL)
    yf = kernels.depthwise_conv_ref(xp, w, None, stride=stride,
                                    groups=groups)
    assert float(jnp.max(jnp.abs(y - yf))) <= \
        _conv_budget(mode, x, w, fan_in=k * k * cin_g)


# ---------------------------------------------------------------------------
# merged rank-r FFN
# ---------------------------------------------------------------------------

def _ffn_budget(mode, x, u, v):
    """Two-stage worst case: stage-1 budget propagates through |V|."""
    d, r = u.shape
    xm = float(jnp.max(jnp.abs(x)))
    um = float(jnp.max(jnp.abs(u)))
    vm = float(jnp.max(jnp.abs(v)))
    b1 = quant.error_budget(mode, fan_in=d, x_absmax=xm, w_absmax=um)
    hm = float(jnp.max(jnp.abs(x @ u))) + b1
    # dequantized V entries exceed |V|max by at most half a scale step
    vm_q = vm * (1.0 + 1.0 / quant.INT8_QMAX)
    b2 = quant.error_budget(mode, fan_in=r, x_absmax=hm, w_absmax=vm)
    return b2 + b1 * r * vm_q


@pytest.mark.parametrize("mode", ["int8", "w8a8", "fp8"])
def test_merged_ffn_quant(mode):
    rng = np.random.default_rng(hash((mode, "ffn")) % 2**31)
    d, r, tok = 24, 10, 9
    x = jnp.asarray(rng.standard_normal((2, tok, d)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((d, r)) * .3, jnp.float32)
    v = jnp.asarray(rng.standard_normal((r, d)) * .3, jnp.float32)
    uq, us = quant.quantize_weight(u, mode, axis=1)
    vq, vs = quant.quantize_weight(v, mode, axis=1)
    aq = mode if mode == "w8a8" else "none"
    y = kernels.merged_ffn_op(x, uq, vq, u_scale=us, v_scale=vs,
                              act_quant=aq, interpret=True)
    yq = kernels.merged_ffn_qref(x, uq, vq, us, vs, act_quant=aq)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yq), **QTOL)
    yf = kernels.merged_ffn_ref(x, u, v)
    maxdiff = float(jnp.max(jnp.abs(y - yf)))
    budget = _ffn_budget(mode, x.reshape(-1, d), u, v)
    assert maxdiff <= budget, (maxdiff, budget)


@given(d=st.integers(8, 40), r=st.integers(2, 16), tok=st.integers(1, 12),
       mode=st.sampled_from(["int8", "w8a8"]))
@settings(max_examples=16, deadline=None)
def test_merged_ffn_quant_sweep(d, r, tok, mode):
    rng = np.random.default_rng(hash((d, r, tok, mode)) % 2**31)
    x = jnp.asarray(rng.standard_normal((1, tok, d)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((d, r)) * .2, jnp.float32)
    v = jnp.asarray(rng.standard_normal((r, d)) * .2, jnp.float32)
    uq, us = quant.quantize_weight(u, mode, axis=1)
    vq, vs = quant.quantize_weight(v, mode, axis=1)
    aq = mode if mode == "w8a8" else "none"
    y = kernels.merged_ffn_op(x, uq, vq, u_scale=us, v_scale=vs,
                              act_quant=aq, interpret=True)
    yq = kernels.merged_ffn_qref(x, uq, vq, us, vs, act_quant=aq)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yq), **QTOL)
    yf = kernels.merged_ffn_ref(x, u, v)
    assert float(jnp.max(jnp.abs(y - yf))) <= \
        _ffn_budget(mode, x.reshape(-1, d), u, v)
