"""Quickstart: LayerMerge end-to-end on a small CNN (the paper's pipeline).

Builds a tiny ResNet, pre-trains it briefly on a synthetic task, runs
Algorithm 2 (tables → DP → replace → fine-tune → merge) at a 60 % latency
budget with *measured* wall-clock latency tables, and reports the paper's
headline numbers: accuracy before/after and the real speed-up of the
merged network on this host.

Finally it exports the merged network as a portable artifact, reloads
it, and verifies the reloaded executor output is identical — the
compress-once / deploy-everywhere contract of repro.runtime.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import os
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.core import (ImportanceSpec, WallClockOracle, accuracy_perf,
                        compress, xent_loss)
from repro.core.importance import _adam_finetune
from repro.launch.cache import enable_compile_cache
from repro.models import cnn, cnn_host, zoo


def toy_task(key, n, hw, classes=4):
    x = jax.random.normal(key, (n, hw, hw, 3))
    q = hw // 2
    means = jnp.stack([x[:, :q, :q].mean((1, 2, 3)),
                       x[:, :q, q:].mean((1, 2, 3)),
                       x[:, q:, :q].mean((1, 2, 3)),
                       x[:, q:, q:].mean((1, 2, 3))], axis=1)
    return x, jnp.argmax(means, axis=1)


def main():
    enable_compile_cache()
    net = zoo.tiny_resnet(num_classes=4, in_hw=16, width=8, blocks=(2, 2))
    params = cnn.init_params(net, jax.random.PRNGKey(0))
    xtr, ytr = toy_task(jax.random.PRNGKey(1), 256, 16)
    xev, yev = toy_task(jax.random.PRNGKey(2), 256, 16)
    apply0 = lambda p, x: cnn.apply_replaced(net, p, x)

    # 1. pre-train
    spec = ImportanceSpec(loss_fn=xent_loss, perf_fn=accuracy_perf,
                          train_batches=[(xtr, ytr)], eval_batches=[(xev, yev)],
                          steps=150, lr=3e-3)
    params = _adam_finetune(apply0, params, spec)
    base_acc = accuracy_perf(apply0, params, [(xev, yev)])
    print(f"pre-trained accuracy: {base_acc:.3f}")

    # 2. LayerMerge at 60% latency budget, measured latency tables
    host = cnn_host.CNNHost(net, params, batch=32)
    ispec = ImportanceSpec(loss_fn=xent_loss, perf_fn=accuracy_perf,
                           train_batches=[(xtr, ytr)],
                           eval_batches=[(xev, yev)], steps=5, lr=1e-3)
    res = compress(host, budget_ratio=0.6, P=200, method="layermerge",
                   latency_oracle=WallClockOracle(warmup=2, iters=5),
                   importance=ispec, base_perf=base_acc, params=params)
    plan = res.plan
    print(f"plan: A*={plan.A} |C*|={len(plan.C)}/{net.L} "
          f"ks={plan.ks}")

    # 3. fine-tune the replaced network (Algorithm 2, line before merge)
    ra, _ = host.replaced_apply(plan)
    ft = ImportanceSpec(loss_fn=xent_loss, perf_fn=accuracy_perf,
                        train_batches=[(xtr, ytr)],
                        eval_batches=[(xev, yev)], steps=150, lr=1e-3)
    params_ft = _adam_finetune(ra, params, ft)
    acc_ft = accuracy_perf(ra, params_ft, [(xev, yev)])

    # 4. merge at inference time and measure the real speed-up
    ma, _ = host.merged_apply(plan, params_ft)
    acc_merged = accuracy_perf(ma, params_ft, [(xev, yev)])

    def timeit(fn):
        fn()
        t0 = time.perf_counter()
        for _ in range(20):
            jax.block_until_ready(fn())
        return (time.perf_counter() - t0) / 20
    f_orig = jax.jit(lambda x: apply0(params, x))
    f_merged = jax.jit(lambda x: ma(params_ft, x))
    t_orig = timeit(lambda: f_orig(xev))
    t_merged = timeit(lambda: f_merged(xev))
    print(f"accuracy: original {base_acc:.3f} -> merged {acc_merged:.3f} "
          f"(replaced {acc_ft:.3f})")
    print(f"latency:  original {t_orig*1e3:.2f} ms -> merged "
          f"{t_merged*1e3:.2f} ms  ({t_orig/t_merged:.2f}x speed-up, "
          f"DP-predicted {res.speedup:.2f}x)")
    assert abs(acc_merged - acc_ft) < 1e-6, "merge must be exact"

    # 5. export the merged network as a portable artifact and reload it
    from repro import runtime
    res.params = params_ft          # publish the fine-tuned weights
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "tiny_resnet.npz")
        fp = res.save(path)
        art = runtime.load(path)
        y_live = ma(params_ft, xev)
        y_art = art.apply(xev)
        assert art.plan == plan, "artifact plan round-trip"
        assert float(jnp.abs(y_live - y_art).max()) < 1e-5, \
            "artifact reload must reproduce the merged network"
        print(f"artifact: {os.path.getsize(path)/1024:.1f} KiB, "
              f"fingerprint {fp[:16]}, reload exact")


if __name__ == "__main__":
    main()
