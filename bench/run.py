#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the TPU this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Prints, as its last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its limit.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  JAX's persistent compilation cache is kept
in ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import Registry, configure_jax, log, run
    reg = Registry(ROOT)
    cell = reg.cell(args.workload)

    import jax

    import repro  # noqa: F401  the system under test: without it, no run
    configure_jax(ROOT)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        log(f"needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device {device}, jax {jax.__version__}")
    result = run(reg, args.workload, args.seed, args.seconds,
                 bool(args.trace), T_START, device)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
