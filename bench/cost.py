"""Work counts of a planned CNN, at unpadded shapes, in float32.

Counted once from the plan and the configuration's layer list, never from
the lowered program, so that MFU and the kernel rooflines read the same
work whatever implements it.  Each segment of the plan is one merged
convolution (kernel size ``K`` from its kept convs; a segment with no
kept conv is the 1×1 depthwise identity the plan's lowering defines), and
runs in the ``merged_conv`` kernel, or in ``depthwise_conv`` when every
kept conv is depthwise.  Pooling, 1×1 projection shortcuts and the
classifier head run outside the kernels.

* ``flops``: 2 × multiply-adds per image (elementwise work — bias,
  activation, residual add, pooling — is not counted);
* ``bytes``: the unit's input and output activations per image, plus
  ``weight_bytes`` read once per call.

``python3 bench/cost.py <config>`` prints the counts that the
configuration file pins under ``work``.
"""
from __future__ import annotations

import json
import os
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import cnn_reference as R  # noqa: E402

F32 = 4


def units(cfg, plan) -> list[dict]:
    """One entry per unit the plan defines, in execution order."""
    shapes = R.boundary_shapes(cfg)
    proj_end = {sk["end"]: sk for sk in cfg["skips"] if sk.get("proj")}
    out = []
    for seg in plan["segments"]:
        i, j = seg["i"], seg["j"]
        (h, w, cin), (ho, wo, cout) = shapes[i], shapes[j]
        act = F32 * (h * w * cin + ho * wo * cout)
        last = R.layer(cfg, j)
        if last["kind"] != "conv":
            out.append({"unit": f"{last['kind']}{i}_{j}", "kernel": None,
                        "flops": 0, "bytes": act, "weight_bytes": 0})
            continue
        K, _ = R.geometry(cfg, seg)
        kept = [l for l in seg["kept"] if R.layer(cfg, l)["kind"] == "conv"]
        dw = all(R.layer(cfg, l)["depthwise"] for l in kept)
        cin_g = 1 if dw else cin
        out.append({"unit": f"conv{i}_{j}",
                    "kernel": "depthwise_conv" if dw else "merged_conv",
                    "flops": 2 * K * K * cin_g * cout * ho * wo,
                    "bytes": act,
                    "weight_bytes": F32 * (K * K * cin_g * cout + cout)})
        if j in proj_end:
            c0 = shapes[proj_end[j]["start"]]
            out.append({"unit": f"proj{proj_end[j]['start']}_{j}",
                        "kernel": None,
                        "flops": 2 * c0[2] * cout * ho * wo,
                        "bytes": F32 * (c0[0] * c0[1] * c0[2]
                                        + ho * wo * cout),
                        "weight_bytes": F32 * (c0[2] * cout + cout)})
    c, n = shapes[-1][2], cfg["num_classes"]
    out.append({"unit": "head", "kernel": None, "flops": 2 * c * n,
                "bytes": F32 * (shapes[-1][0] * shapes[-1][1] * c + n),
                "weight_bytes": F32 * (c * n + n)})
    return out


def work(cfg, plan) -> dict:
    """The ``work`` record a configuration file pins."""
    us = units(cfg, plan)
    return {"flops_per_image": sum(u["flops"] for u in us),
            "bytes_per_image": sum(u["bytes"] for u in us),
            "weight_bytes": sum(u["weight_bytes"] for u in us),
            "units": us}


def kernel_bound_seconds(units_, kernel: str, batch: int, peak,
                         hbm_fractions=None) -> float:
    """Least time the chip could spend in one call's ``kernel`` units: for
    each unit the larger of its operations over peak FLOP/s and its bytes
    over peak bytes/s.  ``hbm_fractions``, one per kernel unit in order,
    scales a unit's bytes to the share the program keeps in HBM."""
    t = 0.0
    ku = [u for u in units_ if u["kernel"]]
    fracs = hbm_fractions if hbm_fractions is not None else [1.0] * len(ku)
    for u, frac in zip(ku, fracs):
        if u["kernel"] == kernel:
            t += max(u["flops"] * batch / peak["flops_per_s"],
                     frac * (u["bytes"] * batch + u["weight_bytes"])
                     / peak["bytes_per_s"])
    return t


def main(argv=None) -> None:
    (name,) = argv if argv is not None else sys.argv[1:]
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(here, "configs", cfg["plan"])) as f:
        plan = json.load(f)
    print(json.dumps(work(cfg, plan), indent=1))


if __name__ == "__main__":
    main()
