"""The DDPM family: a diffusion UNet sampled by DDIM through the program's
normal path.

Seeded weights (:func:`bench.ddpm_reference.init_params`) → the program's
CNN host for ``zoo.ddpm_unet`` at the configuration's sizes → the stored
plan, lowered by ``CNNHost.lower_plan`` → an artifact saved and loaded
back with ``repro.runtime`` → ``GraphExecutor`` → ``runtime.Sampler``,
whose ``apply(x_T)`` runs the traffic's ``steps`` DDIM steps in one
program: the entry the measured window drives.  A call is ``steps``
forwards of the network (:func:`forwards`).

Besides the family protocol of :mod:`bench`, this module holds the
readers of the family's own per-layer metrics, which find attention units
and the work between forwards by the program's scopes
(:func:`attention_share`, :func:`between_forwards_share`).
"""
from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp

from bench import ddpm_reference as R
from bench import scopes

F32 = 4


def init_params(cfg, key):
    return R.init_params(cfg, key)


def make_inputs(cfg, key, pool: int, batch: int):
    """``pool`` batches of noise ``x_T ~ N(0, I)``, NHWC, on the device."""
    return jax.random.normal(
        key, (pool, batch, cfg["in_hw"], cfg["in_hw"], cfg["in_ch"]),
        jnp.float32)


def forwards(cfg, traffic):
    return traffic["steps"]


def reference(cfg, params, x, plan, passes=None, *, traffic):
    return R.sample(cfg, params, x, plan, traffic["steps"], passes)


# ---------------------------------------------------------------------------
# The program's network
# ---------------------------------------------------------------------------

_LAYER_KEYS = ("kind", "cin", "cout", "k", "stride", "norm", "act", "temb")


def _zoo_net(cfg):
    """``zoo.ddpm_unet`` at the configuration's sizes, checked against the
    configuration's own layer list so that the two cannot drift apart."""
    from repro.models import zoo
    net = zoo.ddpm_unet(
        in_hw=cfg["in_hw"], ch=cfg["ch"], ch_mult=tuple(cfg["ch_mult"]),
        num_res_blocks=cfg["num_res_blocks"],
        attn_resolutions=tuple(cfg["attn_resolutions"]),
        gn_groups=cfg["gn_groups"], gn_eps=cfg["gn_eps"],
        out_ch=cfg["out_ch"])
    have = [dataclasses.asdict(s) for s in net.specs]
    want = [R.layer(cfg, l) for l in range(1, len(cfg["layers"]) + 1)]
    if len(have) != len(want):
        raise ValueError(f"{cfg['name']}: the program's network has "
                         f"{len(have)} layers, the configuration "
                         f"{len(want)}")
    for l, (w, h) in enumerate(zip(want, have), 1):
        diff = {k: (w.get(k, False), h[k]) for k in _LAYER_KEYS
                if w.get(k, False) != h[k]}
        if h["kind"] != "upsample" and (h["gn_groups"], h["gn_eps"]) != (
                cfg["gn_groups"], cfg["gn_eps"]):
            diff["gn"] = (cfg["gn_groups"], h["gn_groups"])
        if diff:
            raise ValueError(f"{cfg['name']} layer {l}: config != program "
                             f"{diff}")
    skips = [(s.kind, s.start, s.end, s.proj) for s in net.skips]
    cskips = [(s["kind"], s["start"], s["end"], bool(s.get("proj")))
              for s in cfg["skips"]]
    if skips != cskips or (net.in_hw, net.in_ch, net.temb_dim, net.temb_ch,
                           net.preact) != (cfg["in_hw"], cfg["in_ch"],
                                           cfg["temb_dim"], cfg["temb_ch"],
                                           True):
        raise ValueError(f"{cfg['name']}: config and program networks "
                         f"differ")
    return net


def _schedule(cfg) -> dict:
    """The sampler's β schedule, from the configuration's ``diffusion``
    block, which the reference reads too."""
    d = cfg["diffusion"]
    return {k: d[k] for k in ("timesteps", "beta_start", "beta_end")}


def check_config(cfg):
    """The configuration's layers are the program's ``zoo.ddpm_unet``, its
    sampler is the program's (DDIM, η = 0) and its stored plan is one the
    program accepts over as many layers."""
    from repro.core.plan import CompressionPlan

    d = cfg["diffusion"]
    if (d["sampler"], d["eta"]) != ("ddim", 0.0):
        raise ValueError(f"{cfg['name']}: the program samples by DDIM with "
                         f"eta 0, the configuration asks for {d}")
    net = _zoo_net(cfg)
    plan = CompressionPlan.from_json(cfg["plan_text"])
    if not plan.num_layers == net.L == len(cfg["layers"]):
        raise ValueError(f"{cfg['name']}: the plan covers {plan.num_layers} "
                         f"layers, the program's network {net.L}")
    for seg in plan.segments:
        if not net.allowed_span(seg.i, seg.j):
            raise ValueError(f"{cfg['name']}: segment ({seg.i}, {seg.j}] "
                             f"is not a span the program merges")


def build(cfg, params, plan_text: str, workdir: str, clock=None, *,
          traffic):
    """Lower the plan, publish and reload the artifact; its sampler.

    The plan is lowered by ``CNNHost.lower_plan`` traced once under
    ``jax.jit``, so that the weight fold is one program rather than one
    per eager operation."""
    from repro import runtime
    from repro.core.plan import CompressionPlan
    from repro.models.cnn_host import CNNHost
    from repro.runtime import ir

    host = CNNHost(_zoo_net(cfg), params)
    plan = CompressionPlan.from_json(plan_text)
    traced = {}

    def fold(p):
        traced["graph"] = host.lower_plan(plan, p)
        return ir.graph_params(traced["graph"])
    merged = jax.block_until_ready(jax.jit(fold)(params))
    graph = ir.bind_params(traced.pop("graph"), merged)
    _mark(clock, "host and plan lowering")
    path = os.path.join(workdir, f"{cfg['name']}.npz")
    runtime.save(path, graph, plan, meta={"source": cfg["source"]})
    _mark(clock, "artifact save")
    try:
        art = runtime.load(path)
    finally:
        os.remove(path)
    _mark(clock, "artifact load")
    return runtime.Sampler(art.executor(), traffic["steps"], **_schedule(cfg))


def _mark(clock, name):
    if clock is not None:
        clock.mark(name)


# ---------------------------------------------------------------------------
# Work counts
# ---------------------------------------------------------------------------

def units(cfg, plan) -> list[dict]:
    """One entry per unit of one forward, in execution order, counted from
    the plan and the layer list as ``bench.cost`` counts a CNN's: 2 ×
    multiply-adds per image (elementwise work — norms, activations, adds,
    the DDIM update — not counted); ``bytes``, the unit's input and output
    activations per image; ``weight_bytes`` read once per call.  A unit
    records ``scope``, the program's name for it (``temb``, or ``unitNN``
    of its segment), and ``kind``.  Conv segments run in ``merged_conv``
    (a segment with no kept conv is the 1×1 ``depthwise_conv`` identity);
    the time MLP with every unit's bias Dense, attention, upsampling and
    1×1 shortcuts run outside the kernels."""
    shapes = R.boundary_shapes(cfg)
    E, D = cfg["temb_ch"], cfg["temb_dim"]
    proj_end = {sk["end"]: sk for sk in cfg["skips"] if sk.get("proj")}
    segs = plan["segments"]
    temb_out = [R.layer(cfg, s["j"])["cout"] for s in segs
                if any(R.layer(cfg, l).get("temb") for l in s["kept"])]
    out = [{"unit": "temb", "scope": "temb", "kind": "temb", "kernel": None,
            "flops": 2 * (D * E + E * E + E * sum(temb_out)),
            "bytes": F32 * (D + E + sum(temb_out)),
            "weight_bytes": F32 * (D * E + E * E + 2 * E
                                   + (E + 1) * sum(temb_out))}]
    for n, seg in enumerate(segs):
        i, j = seg["i"], seg["j"]
        (h, w, cin), (ho, wo, _) = shapes[i], shapes[j]
        last = R.layer(cfg, j)
        scope = f"unit{n:02d}"
        if last["kind"] == "attn":
            c = cin
            out.append({"unit": f"attn{i}_{j}", "scope": scope,
                        "kind": "attention", "kernel": None,
                        "flops": 2 * (4 * h * w * c * c + 2 * (h * w) ** 2 * c),
                        "bytes": F32 * 2 * h * w * c,
                        "weight_bytes": F32 * (4 * c * c + 6 * c)})
            continue
        if last["kind"] == "upsample":
            out.append({"unit": f"upsample{i}_{j}", "scope": scope,
                        "kind": "upsample", "kernel": None, "flops": 0,
                        "bytes": F32 * (h * w * cin + ho * wo * cin),
                        "weight_bytes": 0})
            continue
        cout = last["cout"]
        K, _ = R.geometry(cfg, seg)
        ident = not seg["kept"]
        cin_g = 1 if ident else cin
        out.append({"unit": f"conv{i}_{j}", "scope": scope, "kind": "conv",
                    "kernel": "depthwise_conv" if ident else "merged_conv",
                    "flops": 2 * K * K * cin_g * cout * ho * wo,
                    "bytes": F32 * (h * w * cin + ho * wo * cout),
                    "weight_bytes": F32 * (K * K * cin_g * cout + cout)})
        if j in proj_end:
            c0 = shapes[proj_end[j]["start"]][2]
            out.append({"unit": f"proj{proj_end[j]['start']}_{j}",
                        "scope": scope, "kind": "proj", "kernel": None,
                        "flops": 2 * c0 * cout * ho * wo,
                        "bytes": F32 * (h * w * c0 + ho * wo * cout),
                        "weight_bytes": F32 * (c0 * cout + cout)})
    return out


def work(cfg, plan) -> dict:
    """The ``work`` record the configuration file pins, for one forward."""
    us = units(cfg, plan)
    return {"flops_per_image": sum(u["flops"] for u in us),
            "bytes_per_image": sum(u["bytes"] for u in us),
            "weight_bytes": sum(u["weight_bytes"] for u in us),
            "units": us}


# ---------------------------------------------------------------------------
# Per-layer readers
# ---------------------------------------------------------------------------

# A ``while`` instruction's trace event spans the ops of its body, which
# the trace lists as well: the sampler's step loop is one, its event the
# length of the whole call.  Shares of device-op time leave such
# containers out of both sides.
_LOOP = re.compile(r"while(\.\d+)?")


def _loops(ops) -> float:
    return sum(s for name, s in ops if _LOOP.fullmatch(name))


def _scoped(ctx):
    """(roles, device-op seconds less loop containers), or None where
    there is nothing of this family's to read: no device ops, or work
    counts without program scopes."""
    roles = ctx.roles
    if roles is None or roles.op_s <= 0:
        return None
    if not any("scope" in u for u in ctx.work.get("units", ())):
        return None
    op_s = roles.op_s - _loops(ctx.trace.top_ops)
    return (roles, op_s) if op_s > 0 else None


def attention_share(ctx):
    """Per cent of device-op time in the attention units, every role
    counted, found by their ``unitNN`` scopes in the pinned work."""
    got = _scoped(ctx)
    attn = {u["scope"] for u in ctx.work.get("units", ())
            if u.get("kind") == "attention"}
    if got is None or not attn:
        return None
    roles, op_s = got
    s = sum(t for by_unit in roles.by_unit.values()
            for unit, t in by_unit.items() if unit in attn)
    return 100.0 * s / op_s


def between_forwards_share(ctx):
    """Per cent of device-op time outside every unit and outside ``head``:
    the time MLP (``temb``), the DDIM update (``sampler``) and anything
    left unscoped, the step loop's own event excepted."""
    got = _scoped(ctx)
    if got is None:
        return None
    roles, op_s = got
    outside = roles.by_unit.get(scopes.OTHER, {}).get("", 0.0) \
        - _loops(roles.other_ops)
    return 100.0 * outside / op_s
