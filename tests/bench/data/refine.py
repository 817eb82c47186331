"""A family that is not a CNN, for the benchmark's own tests.

The network is one residual MLP step, ``x + tanh(x W1 + b1) W2 + b2``,
and a call applies it ``traffic["steps"]`` times, as a sampler applies its
denoiser: so a call makes ``steps`` forwards of the network.  The system
under test is the step loop under one ``jax.jit``; the reference applies
the same step in a Python loop with float32 matmuls at ``HIGHEST`` (in
three bfloat16 passes for the control).  Nothing here is a Pallas kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.cnn_reference import matmul

F32 = 4


def init_params(cfg, key):
    d, h = cfg["dim"], cfg["hidden"]
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (d, h)) / d ** 0.5,
            "b1": jnp.full((h,), 0.1),
            "w2": jax.random.normal(k2, (h, d)) / h ** 0.5,
            "b2": jnp.full((d,), -0.1)}


def make_inputs(cfg, key, pool: int, batch: int):
    return jax.random.normal(key, (pool, batch, cfg["dim"]), jnp.float32)


def forwards(cfg, traffic):
    return traffic["steps"]


def _step(p, x, mm):
    return x + mm(jnp.tanh(mm(x, p["w1"]) + p["b1"]), p["w2"]) + p["b2"]


def reference(cfg, params, x, plan, passes=None, *, traffic):
    for _ in range(traffic["steps"]):
        x = _step(params, x, lambda a, b: matmul(a, b, passes))
    return x


class Sampler:
    """The entry the window drives: ``steps`` network steps in one
    program."""

    def __init__(self, params, steps: int):
        self.params = params

        def run(p, x):
            return jax.lax.fori_loop(0, steps,
                                     lambda _, y: _step(p, y, jnp.dot), x)
        self._run = jax.jit(run)

    def apply(self, x):
        return self._run(self.params, x)

    def lower(self, x):
        return self._run.lower(self.params, x)


def build(cfg, params, plan_text, workdir, clock=None, *, traffic):
    return Sampler(params, traffic["steps"])


def work(cfg, plan):
    d, h = cfg["dim"], cfg["hidden"]
    unit = {"unit": "step", "kernel": None, "flops": 2 * 2 * d * h,
            "bytes": F32 * 2 * d, "weight_bytes": F32 * (2 * d * h + d + h)}
    return {"flops_per_image": unit["flops"], "bytes_per_image": unit["bytes"],
            "weight_bytes": unit["weight_bytes"], "units": [unit]}


def check_config(cfg):
    if cfg["plan_json"]["method"] != "identity":
        raise ValueError(f"{cfg['name']}: the network runs unplanned")
