"""Merge-math correctness: composed kernels ≡ composed layers (Eq. 1),
and network-level replaced ≡ merged equality — the cornerstone invariant.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st
from jax import lax

from repro.core import merge as M
from repro.core import compress
from repro.models import cnn, cnn_host, zoo


def conv(x, w, s=1, dw=False):
    return lax.conv_general_dilated(
        x, w, (s, s), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=w.shape[-1] if dw else 1)


@pytest.mark.parametrize("k1,k2,s1", [(3, 3, 1), (1, 3, 1), (3, 1, 1),
                                      (5, 3, 1), (3, 3, 2), (1, 1, 2),
                                      (2, 3, 1), (3, 2, 1)])
def test_conv_pair_composition(k1, k2, s1):
    key = jax.random.PRNGKey(k1 * 100 + k2 * 10 + s1)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (2, 14, 14, 3))
    w1 = jax.random.normal(ks[1], (k1, k1, 3, 5))
    w2 = jax.random.normal(ks[2], (k2, k2, 5, 4))
    y = conv(conv(x, w1, s=s1), w2)
    wm, _ = M.merge_conv_pair(w1, w2, stride1=s1)
    assert wm.shape[0] == (k2 - 1) * s1 + k1
    np.testing.assert_allclose(y, conv(x, wm, s=s1), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dw1,dw2", [(True, True), (True, False),
                                     (False, True)])
def test_depthwise_composition(dw1, dw2):
    key = jax.random.PRNGKey(17)
    ks = jax.random.split(key, 3)
    c = 6
    x = jax.random.normal(ks[0], (2, 12, 12, c))
    w1 = jax.random.normal(ks[1], (3, 3, 1, c) if dw1 else (3, 3, c, c))
    w2 = jax.random.normal(ks[2], (3, 3, 1, c) if dw2 else (3, 3, c, c))
    y = conv(conv(x, w1, dw=dw1), w2, dw=dw2)
    wm, dwm = M.merge_conv_pair(w1, w2, dw1=dw1, dw2=dw2)
    assert dwm == (dw1 and dw2)
    np.testing.assert_allclose(y, conv(x, wm, dw=dwm), rtol=2e-4, atol=2e-4)


@given(n=st.integers(2, 4), seed=st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_conv_chain_composition(n, seed):
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    chans = [3] + [int(rng.integers(2, 6)) for _ in range(n)]
    ks = [int(rng.choice([1, 3])) for _ in range(n)]
    strides = [int(rng.choice([1, 1, 2])) for _ in range(n)]
    keys = jax.random.split(key, n + 1)
    x = jax.random.normal(keys[0], (1, 20, 20, 3))
    ws = [jax.random.normal(keys[i + 1], (ks[i], ks[i], chans[i], chans[i + 1]))
          * 0.5 for i in range(n)]
    y = x
    for w, s in zip(ws, strides):
        y = conv(y, w, s=s)
    wm, sm, _ = M.merge_conv_chain(ws, strides, [False] * n)
    np.testing.assert_allclose(y, conv(x, wm, s=sm), rtol=3e-4, atol=3e-4)


def test_bias_and_bn_folding():
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (2, 10, 10, 4))
    w = jax.random.normal(ks[1], (3, 3, 4, 4))
    b = jax.random.normal(ks[2], (4,))
    gamma = jax.random.normal(ks[3], (4,)) + 1.0
    beta = jax.random.normal(ks[4], (4,))
    mean = jax.random.normal(ks[5], (4,)) * 0.1
    var = jnp.abs(jax.random.normal(ks[0], (4,))) + 0.5
    y = conv(x, w) + b
    y = (y - mean) / jnp.sqrt(var + 1e-5) * gamma + beta
    wf, bf = M.fold_batchnorm(w, b, gamma, beta, mean, var)
    np.testing.assert_allclose(y, conv(x, wf) + bf, rtol=2e-4, atol=2e-4)


def test_dirac_skip_fusion():
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (2, 10, 10, 5))
    w = jax.random.normal(jax.random.PRNGKey(6), (3, 3, 5, 5))
    y = x[:, 1:-1, 1:-1, :] + conv(x, w)
    np.testing.assert_allclose(y, conv(x, M.fuse_skip_add(w)),
                               rtol=2e-4, atol=2e-4)


@given(n=st.integers(1, 4), seed=st.integers(0, 50))
@settings(max_examples=20, deadline=None)
def test_rank_merge_chain(n, seed):
    key = jax.random.PRNGKey(seed)
    d = 12
    keys = jax.random.split(key, 2 * n + 1)
    rng = np.random.default_rng(seed)
    factors = []
    for i in range(n):
        r = int(rng.integers(1, 6))
        factors.append((jax.random.normal(keys[2 * i], (d, r)) * 0.3,
                        jax.random.normal(keys[2 * i + 1], (r, d)) * 0.3))
    x = jax.random.normal(keys[-1], (5, d))
    y = x
    for u, v in factors:
        y = y + (y @ u) @ v
    um, vm = M.merge_linear_residual_chain(factors)
    assert um.shape[1] == sum(u.shape[1] for u, _ in factors)  # Eq.1 analogue
    np.testing.assert_allclose(y, x + (x @ um) @ vm, rtol=1e-4, atol=1e-4)
    # SVD truncation at full numerical rank is exact
    ut, vt = M.truncate_rank(um, vm, d)
    np.testing.assert_allclose(y, x + (x @ ut) @ vt, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Network-level equality: replaced(plan) == merged(plan) for DP plans
# ---------------------------------------------------------------------------

NETS = {
    "tiny_resnet": lambda: zoo.tiny_resnet(),
    "tiny_resnet_bn": lambda: zoo.tiny_resnet(norm="bn"),
    "tiny_resnet_gn": lambda: zoo.tiny_resnet(width=16, norm="gn"),
    "tiny_mobilenet": lambda: zoo.tiny_mobilenet(),
    "tiny_unet": lambda: zoo.ddpm_unet(in_hw=16, ch=8, ch_mult=(1, 2),
                                       num_res_blocks=1,
                                       attn_resolutions=(8,), gn_groups=4),
    "tiny_unet_plain": lambda: zoo.ddpm_unet(in_hw=16, ch=8,
                                             ch_mult=(1, 2),
                                             num_res_blocks=1,
                                             attn_resolutions=(),
                                             gn_groups=4),
}


@pytest.mark.parametrize("name", sorted(NETS))
@pytest.mark.parametrize("method", ["layermerge", "depth", "layeronly"])
def test_replaced_equals_merged(name, method):
    net = NETS[name]()
    params = cnn.init_params(net, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (2, net.in_hw, net.in_hw, net.in_ch))
    if net.temb_ch:                  # a time-conditioned net takes (x, t)
        x = (x, jnp.array([10, 900]))
    host = cnn_host.CNNHost(net, params, batch=2)
    tested = 0
    for ratio in (0.55, 0.75, 0.95):
        res = compress(host, budget_ratio=ratio, P=200, method=method)
        if res is None:
            continue
        ra, _ = host.replaced_apply(res.plan)
        ma, _ = host.merged_apply(res.plan)
        yr, ym = ra(params, x), ma(params, x)
        scale = float(jnp.abs(yr).max()) + 1e-9
        assert float(jnp.abs(yr - ym).max()) / scale < 1e-4, (name, method, ratio)
        tested += 1
    assert tested > 0, f"no feasible budget for {name}/{method}"


def test_original_equals_identity_plan():
    net = zoo.tiny_resnet()
    params = cnn.init_params(net, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
    y0 = cnn.apply_replaced(net, params, x)           # plan=None
    from repro.core.plan import identity_plan
    y1 = cnn.apply_replaced(net, params, x, identity_plan(net.L,
                                                          net.layer_descs()))
    np.testing.assert_allclose(y0, y1, rtol=1e-6, atol=1e-6)


def test_fully_pruned_segment_is_identity():
    """A segment with every conv pruned must merge to the identity."""
    from repro.core.plan import CompressionPlan, Segment
    net = zoo.tiny_resnet()
    params = cnn.init_params(net, jax.random.PRNGKey(2))
    # layers 2..5 are the two stage-1 residual blocks (all shape-preserving)
    segs = [Segment(i=0, j=1, k=3, kept=(1,), original=True),
            Segment(i=1, j=5, k=1, kept=())]
    for l in range(6, net.L + 1):
        segs.append(Segment(i=l - 1, j=l, k=net.spec(l).k, kept=(l,),
                            original=True))
    plan = CompressionPlan(num_layers=net.L, segments=tuple(segs))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 16, 3))
    host = cnn_host.CNNHost(net, params, batch=2)
    ra, _ = host.replaced_apply(plan)
    ma, _ = host.merged_apply(plan)
    np.testing.assert_allclose(ra(params, x), ma(params, x),
                               rtol=1e-4, atol=1e-4)
