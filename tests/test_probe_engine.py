"""Probe-engine certification: batched table construction must be
bit-identical to the sequential reference under the analytic oracle
(entries, Pareto drops, DP plans), within tolerance under the wall-clock
oracle, and the vmapped Dirac-masked importance batch must reproduce the
scalar Eq. 4 fine-tune exactly.  Plus: cache round-trips, mixed
conv/attn/upsample barrier hosts, and the pmap-sharded fine-tune path."""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.testing.subproc import run_code
from repro.core import (AnalyticTPUOracle, ImportanceSpec, WallClockOracle,
                        accuracy_perf, build_tables, compress,
                        layer_latencies, original_latency, solve_dp,
                        table_cache, xent_loss)
from repro.core.importance import _adam_finetune, adam_finetune_batched
from repro.models import cnn, cnn_host, zoo


def _host(net, key=0, batch=4):
    params = cnn.init_params(net, jax.random.PRNGKey(key))
    return cnn_host.CNNHost(net, params, batch=batch), params


@pytest.fixture(scope="module")
def resnet_host():
    return _host(zoo.tiny_resnet(num_classes=4, in_hw=8, width=4,
                                 blocks=(2,)))


@pytest.fixture(scope="module")
def unet_host():
    """Mixed-barrier chain: the DDPM UNet's pre-activation ResBlocks,
    attention, upsample and concat at toy width."""
    return _host(zoo.ddpm_unet(in_hw=8, ch=8, ch_mult=(1, 2),
                               num_res_blocks=1, attn_resolutions=(4,),
                               gn_groups=4))


def _spec(net, steps=2):
    x = jax.random.normal(jax.random.PRNGKey(1), (8, net.in_hw, net.in_hw, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 4)
    return ImportanceSpec(loss_fn=xent_loss, perf_fn=accuracy_perf,
                          train_batches=[(x, y)], eval_batches=[(x, y)],
                          steps=steps, lr=1e-3)


# ---------------------------------------------------------------------------
# Analytic parity — bit-identical entries, drops, and DP plans
# ---------------------------------------------------------------------------

def test_analytic_bit_identical_and_plan_identical(resnet_host):
    host, params = resnet_host
    bat = build_tables(host, engine="batched")
    seq = build_tables(host, engine="sequential")
    assert bat.entries == seq.entries
    assert bat.num_pruned == seq.num_pruned
    assert bat.stats.num_latency_buckets < bat.stats.num_latency_probes
    L = len(host.descs())
    T0 = 0.7 * original_latency(host)
    rb = solve_dp(L, bat.fn(), T0, 100, original_k=host.original_k)
    rs = solve_dp(L, seq.fn(), T0, 100, original_k=host.original_k)
    assert rb.plan == rs.plan and rb.objective == rs.objective


def test_compress_engines_agree_analytic(resnet_host):
    host, params = resnet_host
    rb = compress(host, budget_ratio=0.7, P=100, engine="batched")
    rs = compress(host, budget_ratio=0.7, P=100, engine="sequential")
    assert rb.plan == rs.plan
    assert rb.original_latency == rs.original_latency


def test_layer_latencies_bucketed(resnet_host):
    host, params = resnet_host
    oracle = AnalyticTPUOracle()
    lb = layer_latencies(host, oracle, engine="batched")
    ls = layer_latencies(host, oracle, engine="sequential")
    assert lb == ls
    assert len(lb) == len(host.descs())


# ---------------------------------------------------------------------------
# Mixed conv/attn/pool/upsample barriers
# ---------------------------------------------------------------------------

def test_mixed_barrier_host_bit_identical(unet_host):
    host, params = unet_host
    bat = build_tables(host, engine="batched")
    seq = build_tables(host, engine="sequential")
    assert bat.entries == seq.entries
    # barrier kinds land in distinct buckets but still dedup across depth
    assert bat.stats.num_latency_buckets < bat.stats.num_latency_probes


def test_mixed_barrier_wallclock_runs(unet_host):
    host, params = unet_host
    oracle = WallClockOracle(warmup=1, iters=2, groups=1)
    tb = build_tables(host, latency_oracle=oracle, params=params,
                      engine="batched")
    assert tb.stats.num_compiles == tb.stats.num_latency_buckets
    assert tb.stats.num_timings == tb.stats.num_latency_buckets
    assert all(lat > 0.0 for row in tb.entries.values()
               for _, lat, _ in row.values())


# ---------------------------------------------------------------------------
# Wall-clock tolerance
# ---------------------------------------------------------------------------

def test_wallclock_within_tolerance(resnet_host):
    host, params = resnet_host
    oracle = WallClockOracle(warmup=2, iters=10, groups=2)
    bat = build_tables(host, latency_oracle=oracle, params=params,
                       engine="batched", prune=False)
    seq = build_tables(host, latency_oracle=oracle, params=params,
                       engine="sequential", prune=False)
    assert bat.stats.num_compiles == bat.stats.num_latency_buckets
    assert seq.stats.num_compiles == seq.stats.num_latency_probes
    for sp, row in seq.entries.items():
        for k, (_, lat_s, _) in row.items():
            lat_b = bat.entries[sp][k][1]
            # CI timing jitter on ~100µs probes is large; this bounds
            # gross attribution errors (wrong bucket, wrong units), not
            # timer noise.
            assert lat_b > 0.0
            assert lat_b / lat_s < 20.0 and lat_s / lat_b < 20.0


# ---------------------------------------------------------------------------
# Batched importance — exact vs the scalar fine-tune
# ---------------------------------------------------------------------------

def test_importance_batched_matches_sequential(resnet_host):
    host, params = resnet_host
    spec = _spec(host.net)
    base = accuracy_perf(lambda p, x: cnn.apply_replaced(host.net, p, x),
                         params, spec.eval_batches)
    bat = build_tables(host, importance=spec, base_perf=base,
                       engine="batched", prune=False)
    seq = build_tables(host, importance=spec, base_perf=base,
                       engine="sequential", prune=False)
    assert bat.stats.num_importance_batches > 0
    # singleton k-buckets route through the scalar path by design
    for sp, row in seq.entries.items():
        for k, (imp_s, _, _) in row.items():
            np.testing.assert_allclose(bat.entries[sp][k][0], imp_s,
                                       rtol=1e-6, atol=1e-7)


def test_importance_normed_host_falls_back(resnet_host):
    """BN inside a span changes the fine-tune parametrization — the host
    must decline the batch and the engine must fall back, still matching
    the sequential reference."""
    host, params = _host(zoo.tiny_resnet(num_classes=4, in_hw=8, width=4,
                                         blocks=(1,), norm="bn"))
    spec = _spec(host.net)
    base = accuracy_perf(lambda p, x: cnn.apply_replaced(host.net, p, x),
                         params, spec.eval_batches)
    bat = build_tables(host, importance=spec, base_perf=base,
                       engine="batched", prune=False)
    seq = build_tables(host, importance=spec, base_perf=base,
                       engine="sequential", prune=False)
    assert bat.stats.num_importance_sequential > 0
    for sp, row in seq.entries.items():
        for k, (imp_s, _, _) in row.items():
            np.testing.assert_allclose(bat.entries[sp][k][0], imp_s,
                                       rtol=1e-6, atol=1e-7)


def test_adam_finetune_batched_equals_scalar(resnet_host):
    """The vmapped masked Adam on a singleton batch reproduces the scalar
    fine-tune leaf-for-leaf (the mask is all-ones here)."""
    host, params = resnet_host
    spec = _spec(host.net, steps=3)
    apply_fn = lambda p, x: cnn.apply_replaced(host.net, p, x)
    scalar = _adam_finetune(apply_fn, params, spec)
    stacked = jax.tree.map(lambda x: x[None], params)
    batched = adam_finetune_batched(apply_fn, stacked, spec)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b[0], rtol=1e-6, atol=1e-7), scalar, batched)


def test_pmap_sharded_finetune_subprocess():
    """With >1 local device the batched fine-tune pmap-shards the probe
    axis; results must match the single-device vmap path."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.importance import (ImportanceSpec, _adam_finetune,
                                           adam_finetune_batched, xent_loss,
                                           accuracy_perf)
        from repro.models import cnn, zoo
        assert jax.local_device_count() == 2
        net = zoo.tiny_resnet(num_classes=4, in_hw=8, width=4, blocks=(1,))
        params = cnn.init_params(net, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 8, 8, 3))
        y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 4)
        spec = ImportanceSpec(loss_fn=xent_loss, perf_fn=accuracy_perf,
                              train_batches=[(x, y)], eval_batches=[(x, y)],
                              steps=2, lr=1e-3)
        apply_fn = lambda p, xx: cnn.apply_replaced(net, p, xx)
        # 3 lanes on 2 devices: exercises padding + unpadding
        stacked = jax.tree.map(lambda a: jnp.stack([a, a * 1.5, a * 0.5]),
                               params)
        out = adam_finetune_batched(apply_fn, stacked, spec)
        for lane, scale in enumerate((1.0, 1.5, 0.5)):
            ref = _adam_finetune(
                apply_fn, jax.tree.map(lambda a: a * scale, params), spec)
            jax.tree.map(lambda r, o: np.testing.assert_allclose(
                r, o[lane], rtol=2e-5, atol=2e-6), ref,
                jax.tree.map(lambda t: t, out))
        print("PMAP_FT_OK")
    """)
    r = run_code(code, devices=2, timeout=300)
    assert "PMAP_FT_OK" in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# On-disk table cache
# ---------------------------------------------------------------------------

def test_cache_roundtrip_hit(resnet_host, tmp_path):
    host, params = resnet_host
    cold = build_tables(host, engine="batched", cache_dir=str(tmp_path))
    warm = build_tables(host, engine="batched", cache_dir=str(tmp_path))
    assert not cold.stats.cache_hit and warm.stats.cache_hit
    assert warm.entries == cold.entries
    assert warm.num_pruned == cold.num_pruned


def test_cache_serves_across_engines(resnet_host, tmp_path):
    """Batched and sequential are certified to agree, so either build may
    serve the other's key."""
    host, params = resnet_host
    cold = build_tables(host, engine="sequential", cache_dir=str(tmp_path))
    warm = build_tables(host, engine="batched", cache_dir=str(tmp_path))
    assert warm.stats.cache_hit
    assert warm.entries == cold.entries


def test_cache_miss_on_param_and_oracle_change(tmp_path):
    net = zoo.tiny_resnet(num_classes=4, in_hw=8, width=4, blocks=(2,))
    host0, _ = _host(net, key=0)
    build_tables(host0, engine="batched", cache_dir=str(tmp_path))
    host1, _ = _host(net, key=1)          # different parameter content
    t1 = build_tables(host1, engine="batched", cache_dir=str(tmp_path))
    assert not t1.stats.cache_hit
    t2 = build_tables(host0, engine="batched", cache_dir=str(tmp_path),
                      latency_oracle=AnalyticTPUOracle(op_overhead=2e-6))
    assert not t2.stats.cache_hit          # oracle config is in the key
    t3 = build_tables(host0, engine="batched", cache_dir=str(tmp_path),
                      method="depth")
    assert not t3.stats.cache_hit          # method is in the key


def test_cache_disabled_for_unnamed_importance(resnet_host, tmp_path):
    """Measured ImportanceSpecs close over arbitrary callables — without
    an explicit cache_token the build must not be cached."""
    host, params = resnet_host
    spec = _spec(host.net)
    key = table_cache.cache_key(host, AnalyticTPUOracle(), "layermerge",
                                spec)
    assert key is None
    named = ImportanceSpec(**{**spec.__dict__, "cache_token": "toy-v1"})
    key2 = table_cache.cache_key(host, AnalyticTPUOracle(), "layermerge",
                                 named)
    assert key2 is not None


def test_cache_torn_file_is_miss(resnet_host, tmp_path):
    host, params = resnet_host
    oracle = AnalyticTPUOracle()
    build_tables(host, engine="batched", cache_dir=str(tmp_path))
    key = table_cache.cache_key(host, oracle, "layermerge", "magnitude")
    path = tmp_path / f"tables_{key}.json"
    path.write_text(path.read_text()[: 40])     # torn write
    again = build_tables(host, engine="batched", cache_dir=str(tmp_path))
    assert not again.stats.cache_hit            # corrupt entry → rebuild
    healed = build_tables(host, engine="batched", cache_dir=str(tmp_path))
    assert healed.stats.cache_hit               # rebuild re-published
