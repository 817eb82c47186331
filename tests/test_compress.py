"""End-to-end Algorithm 2 with the *measured* pipeline (Eq. 4 importance +
wall-clock latency oracle) on a micro network — the paper's full loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ImportanceSpec, WallClockOracle, accuracy_perf,
                        compress, distill_loss, neg_loss_perf, xent_loss)
from repro.models import cnn, cnn_host, zoo


def _toy_data(key, n, hw, classes=4):
    """Deterministic synthetic classification: quadrant-mean task."""
    x = jax.random.normal(key, (n, hw, hw, 3))
    q = hw // 2
    means = jnp.stack([x[:, :q, :q].mean((1, 2, 3)), x[:, :q, q:].mean((1, 2, 3)),
                       x[:, q:, :q].mean((1, 2, 3)), x[:, q:, q:].mean((1, 2, 3))],
                      axis=1)
    y = jnp.argmax(means, axis=1)
    return x, y


@pytest.fixture(scope="module")
def setup():
    net = zoo.tiny_resnet(num_classes=4, in_hw=8, width=4, blocks=(2,))
    params = cnn.init_params(net, jax.random.PRNGKey(0))
    xtr, ytr = _toy_data(jax.random.PRNGKey(1), 64, 8)
    xev, yev = _toy_data(jax.random.PRNGKey(2), 64, 8)
    return net, params, [(xtr, ytr)], [(xev, yev)]


def test_measured_importance_compress(setup):
    net, params, train_b, eval_b = setup
    host = cnn_host.CNNHost(net, params, batch=4)
    spec = ImportanceSpec(loss_fn=xent_loss, perf_fn=accuracy_perf,
                          train_batches=train_b, eval_batches=eval_b,
                          steps=3, lr=1e-3)
    base = accuracy_perf(lambda p, x: cnn.apply_replaced(net, p, x),
                         params, eval_b)
    res = compress(host, budget_ratio=0.7, P=100, method="layermerge",
                   importance=spec, base_perf=base)
    assert res is not None
    assert res.plan.latency <= res.original_latency  # genuinely compressed
    # importance entries are positive (exp-normalized) and ≤ ~exp(1)
    for (i, j), row in res.tables.entries.items():
        for k, (imp, lat, kept) in row.items():
            assert imp > 0.0 and lat > 0.0


def test_wallclock_oracle_compress(setup):
    net, params, *_ = setup
    host = cnn_host.CNNHost(net, params, batch=4)
    oracle = WallClockOracle(warmup=1, iters=3)
    res = compress(host, budget_ratio=0.7, P=60, method="layermerge",
                   latency_oracle=oracle, params=params)
    assert res is not None and res.speedup > 1.0
    # merged network still runs and matches replaced
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, 8, 3))
    ra, _ = host.replaced_apply(res.plan)
    ma, _ = host.merged_apply(res.plan)
    np.testing.assert_allclose(ra(params, x), ma(params, x),
                               rtol=1e-4, atol=1e-4)


def test_distill_importance_mode(setup):
    """Data-free self-distillation proxy (DESIGN §2.4) runs end to end."""
    net, params, train_b, eval_b = setup
    host = cnn_host.CNNHost(net, params, batch=4)
    teacher = jax.jit(lambda x: cnn.apply_replaced(net, params, x))
    loss = distill_loss(teacher)
    spec = ImportanceSpec(loss_fn=loss, perf_fn=neg_loss_perf(loss),
                          train_batches=[train_b[0][0]],
                          eval_batches=[eval_b[0][0]], steps=2, lr=1e-3)
    res = compress(host, budget_ratio=0.75, P=80, importance=spec,
                   base_perf=0.0)
    assert res is not None


def test_finetune_recovers_accuracy(setup):
    """Fine-tuning the replaced net improves the toy-task loss (sanity of the
    Algorithm 2 fine-tune step)."""
    net, params, train_b, eval_b = setup
    host = cnn_host.CNNHost(net, params, batch=4)
    res = compress(host, budget_ratio=0.6, P=100)
    ra, _ = host.replaced_apply(res.plan)
    from repro.core.importance import ImportanceSpec as IS, _adam_finetune
    spec = IS(loss_fn=xent_loss, perf_fn=accuracy_perf,
              train_batches=train_b * 8, eval_batches=eval_b, steps=25,
              lr=3e-3)
    before = float(xent_loss(ra, params, train_b[0]))
    tuned = _adam_finetune(ra, params, spec)
    after = float(xent_loss(ra, tuned, train_b[0]))
    assert after < before


def test_plan_serialization_roundtrip(setup):
    net, params, *_ = setup
    host = cnn_host.CNNHost(net, params, batch=4)
    res = compress(host, budget_ratio=0.7, P=100)
    from repro.core.plan import CompressionPlan
    plan2 = CompressionPlan.from_json(res.plan.to_json())
    assert plan2.segments == res.plan.segments
    assert plan2.A == res.plan.A and plan2.C == res.plan.C


# ---------------------------------------------------------------------------
# python -m repro.compress: the wall-clock guards
# ---------------------------------------------------------------------------

def _cli(tmp_path, *extra):
    from repro import compress as cli
    return cli.main(["--arch", "tiny_resnet", "--budget-ratio", "0.7",
                     "--P", "50", "--max-span", "2",
                     "--out", str(tmp_path / "a.npz"), *extra])


def test_cli_wallclock_fails_when_a_probe_was_quarantined(tmp_path,
                                                          monkeypatch):
    from repro.testing import faults
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    # the first bucket's timing fails on every attempt: it falls back to
    # the analytic estimate, the artifact is still written, the run fails
    with faults.inject(faults.Fault("probe.time", "raise", nth=1, times=3)):
        with pytest.raises(SystemExit) as e:
            _cli(tmp_path, "--oracle", "wallclock", "--probe-retries", "2")
    assert "quarantined" in str(e.value.code)
    assert (tmp_path / "a.npz").exists()


def test_cli_refuses_wallclock_workers_off_cpu(tmp_path, monkeypatch,
                                               capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    # a parent that holds a chip: the refusal comes before any probe runs
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(SystemExit) as e:
        _cli(tmp_path, "--oracle", "wallclock", "--workers", "2",
             "--cache-dir", str(tmp_path / "tables"))
    assert e.value.code == 3
    assert "workers would time the CPU" in capsys.readouterr().out
    assert not (tmp_path / "a.npz").exists()


# ---------------------------------------------------------------------------
# The compile cache every entry point enables
# ---------------------------------------------------------------------------

def test_compile_cache_lands_in_the_env_dir(tmp_path):
    import os
    import subprocess
    import sys

    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64)))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(tmp_path)
    assert os.listdir(tmp_path), "nothing was cached in the env dir"


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import pathlib

    from repro.launch import cache

    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = pathlib.Path(__file__).resolve().parents[1]
    assert pathlib.Path(path) == root / ".jax_cache"
