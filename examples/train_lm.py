"""End-to-end training driver: a SmolLM-family model on synthetic data.

Runs the full production stack — config → init → sharded data pipeline →
jit'd train_step (loss/grad/clip/AdamW) → fault-tolerant loop with async
checkpoints — for a few hundred steps and reports the loss curve.

Presets:
  tiny (default) — ~3 M params, runs on CPU in ~2 min (CI / this container)
  100m           — the full smollm-135m config (use on real accelerators)

Run:  PYTHONPATH=src python examples/train_lm.py [--preset tiny]
          [--steps 300] [--resume]
"""
import argparse
import dataclasses
import shutil

import jax

from repro.configs import get_config
from repro.data.pipeline import GlobalBatcher, SyntheticTokens
from repro.launch.cache import enable_compile_cache
from repro.models import transformer as T
from repro.optim.adamw import AdamWConfig
from repro.train.loop import LoopConfig, train_loop


def preset_config(name):
    base = get_config("smollm-135m")
    if name == "100m":
        return base, 8, 1024
    cfg = dataclasses.replace(
        base, name="smollm-tiny", num_layers=4, d_model=128, num_heads=4,
        num_kv_heads=2, head_dim=32, d_ff=384, vocab_size=512,
        dtype="float32", remat=False)
    return cfg, 16, 64


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=["tiny", "100m"])
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg, batch, seq = preset_config(args.preset)
    if not args.resume:
        shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train_lm] {cfg.name}: {n/1e6:.1f}M params, "
          f"batch={batch} seq={seq}")

    data = SyntheticTokens(cfg.vocab_size, batch, seq, seed=0)
    batcher = GlobalBatcher(data)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps,
                          weight_decay=0.01)
    loop_cfg = LoopConfig(total_steps=args.steps, ckpt_every=100,
                          ckpt_dir=args.ckpt_dir, log_every=25)
    result = train_loop(cfg, opt_cfg, loop_cfg, params, batcher)
    first = sum(result.losses[:10]) / max(len(result.losses[:10]), 1)
    last = sum(result.losses[-10:]) / max(len(result.losses[-10:]), 1)
    print(f"[train_lm] loss {first:.3f} -> {last:.3f} over "
          f"{result.final_step} steps ({result.restarts} restarts)")
    assert last < first, "training must reduce the loss"


if __name__ == "__main__":
    main()
