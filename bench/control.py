#!/usr/bin/env python3
"""Readings that the correctness limit of a cell is set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 2]

For each seed, in this one process: the cell's system under test is built
and driven for a short window at the cell's own batch, exactly as
``bench/run.py`` drives it; a sample of its answers is compared with the
plain reference (the *program's* reading), and the reference computed in
the nearest precision below the configuration's (three bfloat16 passes
for float32 at ``highest``) is compared with the same reference on the
same inputs (the *control's* reading).  The limit must lie above every
program reading and below every control reading.  The benchmark's own
runs never run this.  Prints one JSON line per seed, then a summary.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))


def readings(reg, workload: str, seed: int, seconds: float) -> dict:
    """The program's and the control's reading for one seed."""
    import numpy as np

    from bench.harness import Cell, _sample, reference_outputs, rel_err
    cell = Cell(reg, workload, seed)
    t = cell.traffic
    lat, outs, _ = cell.window(seconds)
    picks = _sample(seed, len(lat), t["pool"], t["check_calls"])
    y = np.concatenate([np.asarray(outs[i]) for i in picks])
    del outs
    cell.free()
    with cell.precision:
        ref = reference_outputs(cell.fam, cell.cfg, seed, t, picks)
        ctl = reference_outputs(cell.fam, cell.cfg, seed, t, picks, passes=3)
    return {"seed": seed, "calls": len(lat), "images": len(y),
            "program": float(rel_err(y, ref).max()),
            "control": float(rel_err(ctl, ref).max()),
            "limit": cell.cfg["check"]["max_rel_err"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    import jax

    from bench.harness import Registry, configure_jax
    configure_jax(ROOT)
    if jax.devices()[0].platform != "tpu":
        print("control readings are taken on the TPU", file=sys.stderr)
        return 2
    reg = Registry(ROOT)
    rows = []
    for s in args.seeds.split(","):
        rows.append(readings(reg, args.workload, int(s), args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "program_max": max(r["program"] for r in rows),
        "control_min": min(r["control"] for r in rows),
        "limit": rows[0]["limit"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
