"""The DDPM UNet on the program's CNN path, at toy width on the CPU.

* the per-image time bias folds exactly through a merged conv pair;
* nearest upsampling as a broadcast equals ``jax.image.resize``'s;
* pre-activation spans: multi-layer spans stay inside one ResBlock, and
  the convs outside ResBlocks are barriers;
* ``apply_replaced(plan)`` equals the executed merged graph under random
  plans that merge and prune inside ResBlocks, with a different timestep
  per image;
* the DDIM sampler runs every step in one program (one trace per shape),
  counts its steps, equals a Python loop of forwards, and survives an
  artifact round trip.
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime
from repro.core import merge as M
from repro.core.plan import CompressionPlan, Segment
from repro.models import cnn, cnn_host, zoo

#: DDPM's training schedule (Ho et al. 2020), which the sampler is given
DDPM = {"timesteps": 1000, "beta_start": 1e-4, "beta_end": 0.02}


def _net():
    return zoo.ddpm_unet(in_hw=16, ch=16, ch_mult=(1, 2), num_res_blocks=1,
                         attn_resolutions=(8,), gn_groups=8)


@pytest.fixture(scope="module")
def setup():
    net = _net()
    params = cnn.init_params(net, jax.random.PRNGKey(0))
    host = cnn_host.CNNHost(net, params, batch=3)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 16, 3))
    t = jnp.array([0, 417, 999])
    return net, params, host, x, t


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


def test_time_bias_folds_exactly():
    """conv2(conv1(x) + b1 + c[n]) + b2 == merged(x) + b2 + S2ᵀ(b1 + c[n])
    on a once-padded input with VALID convs, c different per image."""
    k = jax.random.split(jax.random.PRNGKey(2), 6)
    x = jax.random.normal(k[0], (4, 10, 10, 8))
    w1 = jax.random.normal(k[1], (3, 3, 8, 16)) * 0.2
    w2 = jax.random.normal(k[2], (3, 3, 16, 12)) * 0.2
    b1, b2 = jax.random.normal(k[3], (16,)), jax.random.normal(k[4], (12,))
    c = jax.random.normal(k[5], (4, 16)) * 3.0
    xp = jnp.pad(x, ((0, 0), (2, 2), (2, 2), (0, 0)))
    hi = jax.lax.Precision.HIGHEST

    def conv(a, w):
        return jax.lax.conv_general_dilated(
            a, w, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=hi)
    want = conv(conv(xp, w1) + b1 + c[:, None, None, :], w2) + b2
    wm, _ = M.merge_conv_pair(w1, w2)
    bm = M.merge_bias_through(w2, b1, b2)
    cm = M.merge_bias_through(w2, c, None)
    got = conv(xp, wm) + bm + cm[:, None, None, :]
    assert got.shape == want.shape == (4, 10, 10, 12)
    assert _rel(got, want) < 1e-6
    # the Dense that makes c folds the same way
    s = jax.random.normal(k[0], (4, 5))
    dw, db = jax.random.normal(k[1], (5, 16)), jax.random.normal(k[2], (16,))
    fw = M.merge_bias_through(w2, dw, None)
    fb = M.merge_bias_through(w2, db, None)
    assert _rel(s @ fw + fb, M.merge_bias_through(w2, s @ dw + db, None)) \
        < 1e-5


@pytest.mark.parametrize("factor", [2, 3])
def test_upsample_is_nearest(factor):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 7, 3))
    want = jax.image.resize(x, (2, 5 * factor, 7 * factor, 3), "nearest")
    np.testing.assert_array_equal(np.asarray(cnn.upsample_nearest(x, factor)),
                                  np.asarray(want))


def test_spans_stay_inside_resblocks():
    net = _net()
    blocks = [(sk.start, sk.end) for sk in net.skips if sk.kind == "add"]
    for i, j in blocks:
        assert net.allowed_span(i, j)
        assert not net.allowed_span(i, j + 1)
        if i > 0:
            assert not net.allowed_span(i - 1, j)
    inside = {l for i, j in blocks for l in range(i + 1, j + 1)}
    for l in range(1, net.L + 1):
        s = net.spec(l)
        if l not in inside:         # conv_in, resampling, attention, conv_out
            assert not net.prunable(l)
        elif s.cin == s.cout:
            assert net.prunable(l)


def _random_plan(net, rng):
    blocks = {sk.start: sk.end for sk in net.skips if sk.kind == "add"}
    segs, l = [], 0
    while l < net.L:
        if l in blocks and rng.random() < 0.8:
            j = blocks[l]
            kept = set(rng.choice([(l + 1, l + 2), (l + 1,), (l + 2,), ()]))
            kept |= {m for m in (l + 1, l + 2) if not net.prunable(m)}
            kept = tuple(sorted(kept))
            K, _ = cnn.segment_geometry(net, Segment(l, j, 0, kept))
            segs.append(Segment(l, j, K, kept))
            l = j
        else:
            s = net.spec(l + 1)
            segs.append(Segment(l, l + 1, s.k if s.kind == "conv" else 1,
                                (l + 1,), original=True))
            l += 1
    return CompressionPlan(num_layers=net.L, segments=tuple(segs))


@pytest.mark.parametrize("seed", range(4))
def test_merged_equals_replaced_random_plans(setup, seed):
    net, params, host, x, t = setup
    plan = _random_plan(net, random.Random(seed))
    assert any(s.j - s.i > 1 for s in plan.segments)
    yr = cnn.apply_replaced(net, params, (x, t), plan)
    ym = runtime.execute(host.lower_plan(plan), (x, t))
    assert _rel(ym, yr) < 1e-5


def test_identity_plan_is_the_original_bit_for_bit(setup):
    net, params, host, x, t = setup
    from repro.core.plan import identity_plan
    ident = identity_plan(net.L, net.layer_descs())
    y0 = cnn.apply_replaced(net, params, (x, t))
    y1 = runtime.execute(host.lower_plan(ident), (x, t))
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))


def test_ddim_schedule():
    ts, a, prev = runtime.ddim_schedule(20, **DDPM)
    assert list(ts) == list(range(950, -1, -50))
    assert prev[-1] == 1.0 and np.all(prev[:-1] == a[1:])
    assert np.all(np.diff(a) > 0)
    assert list(runtime.ddim_schedule(3, **DDPM)[0]) == [666, 333, 0]
    # ᾱ_t = Π (1 − β_s) over the given linear β, at the visited timesteps
    betas = np.linspace(1e-4, 0.02, 1000)
    np.testing.assert_allclose(a, np.cumprod(1 - betas)[ts], rtol=1e-12)
    ts2, a2, _ = runtime.ddim_schedule(
        20, **{**DDPM, "timesteps": 500, "beta_end": 0.04})
    assert list(ts2) == list(range(475, -1, -25)) and a2[0] < a[0]


def test_sampler_is_one_program_a_call(setup, tmp_path):
    """Two calls, one trace of the sampling program; steps counted; the
    same numbers as a Python loop of forwards; and an artifact round
    trip samples the same."""
    net, params, host, x, t = setup
    plan = _random_plan(net, random.Random(7))
    graph = host.lower_plan(plan)
    ex = runtime.GraphExecutor(graph)
    smp = runtime.Sampler(ex, 3, **DDPM)
    y = smp.apply(x)
    smp.apply(x).block_until_ready()
    assert dict(ex.traces) == {("sample", x.shape): 1}
    assert smp.steps_run == 6
    ts, a, prev = runtime.ddim_schedule(3, **DDPM)
    z = x
    for k in range(3):
        eps = cnn.apply_replaced(net, params,
                                 (z, jnp.full((3,), ts[k])), plan)
        x0 = (z - np.sqrt(1 - a[k]) * eps) / np.sqrt(a[k])
        z = np.sqrt(prev[k]) * x0 + np.sqrt(1 - prev[k]) * eps
    assert _rel(y, z) < 1e-5
    path = str(tmp_path / "ddpm.npz")
    runtime.save(path, graph, plan)
    art = runtime.load(path)
    assert art.graph.meta["temb_dim"] == net.temb_dim
    y2 = runtime.Sampler(art.executor(), 3, **DDPM).apply(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))


def test_sampler_refuses_an_unconditioned_graph():
    net = zoo.tiny_resnet()
    host = cnn_host.CNNHost(net, cnn.init_params(net, jax.random.PRNGKey(0)))
    from repro.core.plan import identity_plan
    ex = runtime.GraphExecutor(host.lower_plan(
        identity_plan(net.L, net.layer_descs())))
    with pytest.raises(ValueError, match="time-conditioned"):
        runtime.Sampler(ex, 3, **DDPM)


def test_sampler_names_its_work(setup):
    """The compiled sampler keeps the time MLP under ``temb``, the update
    under ``sampler`` and the units' pre-activation norms under
    ``unitNN/epilogue``."""
    net, params, host, x, t = setup
    plan = _random_plan(net, random.Random(3))
    smp = runtime.Sampler(runtime.GraphExecutor(host.lower_plan(plan)), 2,
                          **DDPM)
    text = smp.lower(x).compile().as_text()
    for name in ("/temb/", "/sampler/", "/unit01/epilogue/"):
        assert name in text, name
