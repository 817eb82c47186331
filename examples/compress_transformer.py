"""LayerMerge on a transformer — the paper's technique on the assigned
architectures (DESIGN §2.1 rank-merge).

Pre-trains a small smollm-family LM on synthetic text, runs LayerMerge /
Depth / LayerOnly at several latency budgets (analytic v5e oracle), fine-
tunes each plan, and prints a Pareto mini-table (the transformer analogue
of the paper's Tables 1–3).

Run:  PYTHONPATH=src python examples/compress_transformer.py
"""
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import ImportanceSpec, compress, neg_loss_perf
from repro.core.importance import _adam_finetune
from repro.data.pipeline import SyntheticTokens
from repro.launch.cache import enable_compile_cache
from repro.models import transformer as T
from repro.models.transformer_host import CostEnv, TransformerHost


def main():
    enable_compile_cache()
    cfg = dataclasses.replace(
        get_config("smollm-135m"), name="smollm-mini", num_layers=6,
        d_model=96, num_heads=4, num_kv_heads=2, head_dim=24, d_ff=256,
        vocab_size=256, dtype="float32", remat=False)
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    data = SyntheticTokens(cfg.vocab_size, 16, 64, seed=0)
    batches = [data.batch_at(i) for i in range(8)]
    batches = [{k: jnp.asarray(v) for k, v in b.items()} for b in batches]

    def loss_fn(apply_fn, p, batch):
        logits = apply_fn(p, batch).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, batch["targets"][..., None],
                                   axis=-1)[..., 0]
        return jnp.mean(nll)

    plain_apply = lambda p, b: T.forward(cfg, p, b)
    spec = ImportanceSpec(loss_fn=loss_fn, perf_fn=neg_loss_perf(loss_fn),
                          train_batches=batches[:6], eval_batches=batches[6:],
                          steps=120, lr=2e-3)
    params = _adam_finetune(plain_apply, params, spec)
    base = neg_loss_perf(loss_fn)(plain_apply, params, batches[6:])
    print(f"pre-trained eval loss: {-base:.3f}")

    host = TransformerHost(cfg, params, env=CostEnv(batch=16, seq=64))
    ispec = dataclasses.replace(spec, steps=8, lr=1e-3)
    export = None
    print(f"{'method':12s} {'budget':>6s} {'speedup':>8s} {'eval loss':>10s}")
    for method in ("layermerge", "depth", "layeronly"):
        for ratio in (0.8, 0.6, 0.45):
            res = compress(host, budget_ratio=ratio, P=300, method=method,
                           importance=ispec, base_perf=base, params=params)
            if res is None:
                print(f"{method:12s} {ratio:6.2f} {'infeasible':>8s}")
                continue
            ra, _ = host.replaced_apply(res.plan)
            ft = dataclasses.replace(spec, steps=120)
            tuned = _adam_finetune(ra, params, ft)
            ev = -neg_loss_perf(loss_fn)(ra, tuned, batches[6:])
            print(f"{method:12s} {ratio:6.2f} {res.speedup:8.2f} {ev:10.3f}")
            if method == "layermerge" and ratio == 0.6:
                export = (res, tuned)

    # export the fine-tuned LayerMerge@0.6 plan as a portable artifact and
    # verify the reloaded executor reproduces the merged forward exactly
    import os
    import tempfile

    from repro import runtime

    if export is None:
        return
    res, tuned = export
    res.params = tuned
    ma, _ = host.merged_apply(res.plan, tuned)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "smollm_mini.npz")
        fp = res.save(path)
        art = runtime.load(path)
        y_live = ma(tuned, batches[-1])
        y_art = art.apply(batches[-1])
        assert float(jnp.abs(y_live - y_art).max()) < 1e-5
        print(f"artifact: fingerprint {fp[:16]}, reload exact "
              f"({os.path.getsize(path)/1024:.1f} KiB)")


if __name__ == "__main__":
    main()
