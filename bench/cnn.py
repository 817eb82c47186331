"""The CNN family: a configuration driven through the program's normal path.

Seeded weights (:func:`bench.cnn_reference.init_params`) → the program's
CNN host for the zoo network the configuration names → the stored plan,
lowered by ``CNNHost.lower_plan`` → an artifact saved and loaded back with
``repro.runtime`` → ``GraphExecutor``, whose jitted forward is the entry
the measured window drives.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp

from bench import cnn_reference, cost


def init_params(cfg, key):
    return cnn_reference.init_params(cfg, key)


def make_inputs(cfg, key, pool: int, batch: int):
    """``pool`` distinct NHWC batches, made on the device in one call."""
    return jax.random.normal(
        key, (pool, batch, cfg["in_hw"], cfg["in_hw"], cfg["in_ch"]),
        jnp.float32)


def reference(cfg, params, x, plan, passes=None, *, traffic=None):
    return cnn_reference.forward(cfg, params, x, plan, passes)


def work(cfg, plan):
    return cost.work(cfg, plan)


def check_config(cfg):
    """The configuration's layers are the program's zoo network, and its
    stored plan is one the program accepts, over as many layers."""
    from repro.core.plan import CompressionPlan

    net = _zoo_net(cfg)
    plan = CompressionPlan.from_json(cfg["plan_text"])
    if not plan.num_layers == net.L == len(cfg["layers"]):
        raise ValueError(f"{cfg['name']}: the plan covers {plan.num_layers} "
                         f"layers, the program's network {net.L}, the "
                         f"configuration {len(cfg['layers'])}")


def _zoo_net(cfg):
    """The program's network for this configuration, checked against the
    configuration's own layer list so that the two cannot drift apart."""
    from repro.models import zoo
    net = getattr(zoo, cfg["zoo"])()
    want = [cnn_reference.layer(cfg, l) for l in range(1, len(cfg["layers"])
                                                         + 1)]
    have = [dataclasses.asdict(s) for s in net.specs]
    for l, (w, h) in enumerate(zip(want, have), 1):
        diff = {k: (w[k], h[k]) for k in w if k in h and w[k] != h[k]}
        if diff:
            raise ValueError(f"{cfg['name']} layer {l}: config != program "
                             f"{diff}")
    skips = [(s.kind, s.start, s.end, s.proj) for s in net.skips]
    cskips = [(s["kind"], s["start"], s["end"], bool(s.get("proj")))
              for s in cfg["skips"]]
    if (len(have) != len(want) or skips != cskips
            or (net.in_hw, net.in_ch, net.num_classes, net.act_after_merge)
            != (cfg["in_hw"], cfg["in_ch"], cfg["num_classes"],
                bool(cfg.get("act_after_merge")))):
        raise ValueError(f"{cfg['name']}: config and program networks "
                         f"differ")
    return net


def build(cfg, params, plan_text: str, workdir: str, clock=None, *,
          traffic=None):
    """Lower the plan, publish and reload the artifact; the executor.

    The plan is lowered by ``CNNHost.lower_plan`` traced once under
    ``jax.jit`` (as ``CNNHost.merged_apply`` does), so that the weight
    fold is one program rather than one per eager operation.
    """
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
    return art.executor()


def _mark(clock, name):
    if clock is not None:
        clock.mark(name)
