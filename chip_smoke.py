#!/usr/bin/env python3
"""Chip smoke test: LayerMerge's main path on a TPU, for ResNet-34 at its
published width (224×224 inputs, 1000 classes, weights seeded at random).

One chip, the default::

    python3 chip_smoke.py [--max-span 4] [--seed 0] [--out DIR]

1. Plan: latency tables timed on the chip by the wall-clock oracle, with
   quarantine off so that any probe the chip refuses fails the run;
   magnitude importance; the DP at a 0.6 latency budget.
2. Save the merged artifact, reload it, and run the jitted
   ``GraphExecutor`` on two seeded batches of 8 images.
3. Check that the compiled executor holds one Pallas kernel
   (``tpu_custom_call``) per conv unit: no unit fell back to the jnp
   oracle or to interpret mode.
4. Compare its output with the replaced network (plain XLA convs) on the
   same chip, both at full fp32 matmul precision.

Four chips::

    python3 chip_smoke.py --chips 4

runs only the sharded path: the artifact through ``GraphExecutor`` under a
data-parallel host mesh at batch 32, against the one-chip executor on the
same batch.  Its plan comes from the analytic oracle, because the
chip-timed table build is a one-chip phase.

Everything runs in this one process: a chip belongs to one process.  The
compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache/`` in the checkout.  The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``; any failure exits non-zero
without printing it, and so does a run that finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "resnet34"
BUDGET_RATIO = 0.6
IN_HW = 224
BATCH = 8                 # per chip
N_BATCHES = 2
# The repo's merged == replaced bar (tests/test_merge.py): max |y - y_ref|
# over max |y_ref|.  Both sides run at fp32 matmul precision.
TOL = 1e-4
KERNEL = 'custom_call_target="tpu_custom_call"'


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def images(seed: int, batch: int, n: int):
    import jax
    return [jax.random.normal(jax.random.PRNGKey(seed + 1 + i),
                              (batch, IN_HW, IN_HW, 3)) for i in range(n)]


def compare(name: str, y, y_ref) -> None:
    """Log the error against the reference; fail past :data:`TOL`."""
    import numpy as np
    y, y_ref = np.asarray(y, np.float64), np.asarray(y_ref, np.float64)
    if y.shape != y_ref.shape or not np.isfinite(y).all():
        raise SystemExit(f"{name}: output shape {y.shape} (reference "
                         f"{y_ref.shape}) or non-finite values")
    err = float(np.abs(y - y_ref).max())
    scale = float(np.abs(y_ref).max())
    top1 = float((y.argmax(-1) == y_ref.argmax(-1)).mean())
    log(f"{name}: max abs err {err:.3e}, max rel err {err / scale:.3e} "
        f"(tolerance {TOL:g}), top-1 agreement {top1:.4f}")
    if not err <= TOL * scale:
        raise SystemExit(f"{name}: relative error {err / scale:.3e} "
                         f"exceeds {TOL:g}")


def timed(fn, x) -> tuple:
    import jax
    t0 = time.perf_counter()
    y = jax.block_until_ready(fn(x))
    return y, time.perf_counter() - t0


def plan_and_save(args, oracle, probe_config, out_path: str):
    """Run the compression pipeline and publish the artifact."""
    import jax
    from repro.compress import build_host
    from repro.core import compress

    t0 = time.perf_counter()
    host, source = build_host(ARCH, seed=args.seed, batch=BATCH,
                              max_span=args.max_span)
    res = compress(host, budget_ratio=BUDGET_RATIO, latency_oracle=oracle,
                   importance="magnitude", probe_config=probe_config)
    if res is None:
        raise SystemExit(f"no plan fits budget ratio {BUDGET_RATIO}")
    plan_s = time.perf_counter() - t0
    st = res.tables.stats
    log(f"plan: {plan_s:.1f} s, {st.num_latency_probes} table probes in "
        f"{st.num_latency_buckets} buckets, {st.num_compiles} compiles, "
        f"{st.num_timings} timings, {res.num_quarantined} quarantined")
    log(f"plan: {res.plan.num_layers} layers -> {len(res.plan.C)} kept, "
        f"{len(res.plan.segments)} segments, predicted speed-up "
        f"{res.speedup:.3f} (T_orig {res.original_latency * 1e3:.3f} ms)")
    # The weight fold is exact fp32 (core.merge); the precision context
    # also covers the BN folding and bias sums around it.
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        res.save(out_path, extra_meta={"source": source})
    log(f"artifact: {out_path} ({os.path.getsize(out_path) / 2**20:.1f} "
        f"MiB) in {time.perf_counter() - t0:.1f} s")
    return host, res


def one_chip(args) -> None:
    import jax
    from repro import runtime
    from repro.core import ProbeConfig, WallClockOracle

    host, res = plan_and_save(args, WallClockOracle(),
                              ProbeConfig(quarantine=False),
                              os.path.join(args.out, f"{ARCH}.npz"))
    if res.num_quarantined:
        raise SystemExit(f"{res.num_quarantined} probes were quarantined")

    art = runtime.load(os.path.join(args.out, f"{ARCH}.npz"))
    ex = art.executor()
    n_conv = sum(u.kind == "conv" for u in art.graph.units)
    xs = images(args.seed, BATCH, N_BATCHES)
    ref = jax.jit(host.replaced_apply(res.plan)[0])
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        hlo = ex.lower(xs[0]).compile().as_text()
        n_kern = hlo.count(KERNEL)
        log(f"executor: {len(art.graph.units)} units, {n_conv} conv units, "
            f"{n_kern} tpu_custom_call kernels (compiled in "
            f"{time.perf_counter() - t0:.1f} s)")
        if n_kern != n_conv:
            raise SystemExit(f"{n_kern} Pallas kernels for {n_conv} conv "
                             f"units: some unit did not run its kernel")
        ex.apply(xs[0]).block_until_ready()            # warm the jit
        ref(host.params, xs[0]).block_until_ready()
        for i, x in enumerate(xs):
            y, dt = timed(ex.apply, x)
            y_ref, dt_ref = timed(lambda x: ref(host.params, x), x)
            log(f"batch {i}: executor {dt * 1e3:.2f} ms, replaced network "
                f"{dt_ref * 1e3:.2f} ms (host clock, batch {BATCH})")
            compare(f"batch {i} executor vs replaced", y, y_ref)


def four_chips(args) -> None:
    import jax
    from repro import runtime
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.rules import make_unit_rules

    if len(jax.devices()) != 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found "
                         f"{len(jax.devices())}")
    path = os.path.join(args.out, f"{ARCH}_4chip.npz")
    plan_and_save(args, None, None, path)
    rules = make_unit_rules(make_host_mesh())
    sharded = runtime.load(path, rules=rules).executor(rules)
    single = runtime.load(path).executor()
    (x,) = images(args.seed, 4 * BATCH, 1)
    with jax.default_matmul_precision("highest"):
        hlo = sharded.lower(x).compile().as_text()
        log(f"sharded executor: mesh {dict(rules.mesh.shape)}, "
            f"{hlo.count(KERNEL)} tpu_custom_call kernels, "
            f"{hlo.count('all-gather(')} all-gathers")
        sharded.apply(x).block_until_ready()
        single.apply(x).block_until_ready()
        y, dt = timed(sharded.apply, x)
        y1, dt1 = timed(single.apply, x)
    log(f"batch {4 * BATCH}: 4 chips {dt * 1e3:.2f} ms, one chip "
        f"{dt1 * 1e3:.2f} ms (host clock)")
    log(f"sharded output placement: {y.sharding}")
    compare("sharded vs one-chip executor", y, y1)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--max-span", type=int, default=4,
                    help="longest merged segment the planner considers "
                         "(bounds the table build to a few minutes)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, ".smoke_out"))
    args = ap.parse_args(argv)

    import jax
    from repro.launch.cache import enable_compile_cache

    cache = enable_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[chip_smoke] needs a TPU; JAX found {devs[0].platform}",
              file=sys.stderr)
        raise SystemExit(2)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {device['kind']} x{device['count']}, jax "
        f"{jax.__version__}, compile cache {cache}")
    os.makedirs(args.out, exist_ok=True)
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args)
    log(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
