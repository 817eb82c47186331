"""DDIM sampling over a time-conditioned CNN graph, in one program a call.

A diffusion model is served by its sampler: one request is a batch of
noise ``x_T`` taken to images ``x_0`` through ``steps`` forwards of the
denoiser.  :class:`Sampler` runs those steps inside one jitted
``lax.fori_loop`` over a :class:`~repro.runtime.executor.GraphExecutor`'s
graph and weights, so a call is one program on the device and the host
issues it once.

The schedule is the model's training one, which the caller passes: ``T``
= ``timesteps`` and a linear β from ``beta_start`` to ``beta_end`` (DDPM,
Ho et al. 2020: 1000, 1e-4, 0.02), ``ᾱ_t = Π_{s≤t}(1 − β_s)``.
DDIM (Song et al. 2021) with η = 0 visits ``steps`` timesteps ``t_k =
(steps − 1 − k)·(T // steps)`` (950, 900, …, 0 for 20 of 1000) and at
each computes, from the model's noise estimate ε = ε_θ(x_t, t),

    x0     = (x_t − √(1 − ᾱ_t) ε) / √ᾱ_t
    x_prev = √ᾱ_prev x0 + √(1 − ᾱ_prev) ε

with ``ᾱ_prev`` the next visited timestep's, and 1 after the last step.
``x0`` is not clipped, as in the DDIM authors' ``generalized_steps``.
The schedule is computed in float64 on the host and enters the program
as float32 constants.

Names for profiles: ``apply`` runs under the host span
``executor.apply``; inside the program each step runs the graph's time
MLP under ``temb`` and its units under ``unitNN`` (see
:mod:`repro.runtime.executor`), then the update above under ``sampler``.
The executor's ``traces`` counter gains one ``("sample", shape)`` entry
per compile; :attr:`Sampler.steps_run` counts the denoising steps issued.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.rules import use_rules

from . import executor as _executor


def ddim_schedule(steps: int, *, timesteps: int, beta_start: float,
                  beta_end: float):
    """(visited timesteps, ᾱ_t, ᾱ_prev) of a DDIM run over a linear β
    schedule of ``timesteps``, as numpy arrays (int32, float64, float64),
    one entry per step in order."""
    betas = np.linspace(beta_start, beta_end, timesteps, dtype=np.float64)
    abar = np.cumprod(1.0 - betas)
    ts = np.arange(steps)[::-1] * (timesteps // steps)
    prev = np.concatenate([abar[ts[1:]], [1.0]])
    return ts.astype(np.int32), abar[ts], prev


class Sampler:
    """``apply(x_T)``: ``steps`` DDIM (η = 0) steps of an executor's
    time-conditioned graph, one jitted program a call; the keywords are
    the model's β schedule (:func:`ddim_schedule`)."""

    def __init__(self, ex: _executor.GraphExecutor, steps: int, *,
                 timesteps: int, beta_start: float, beta_end: float):
        if "temb_dim" not in ex.graph.meta:
            raise ValueError("the sampler needs a time-conditioned graph")
        self.executor = ex
        self.steps = steps
        #: denoising steps issued by :meth:`apply`
        self.steps_run = 0
        ts, abar, prev = ddim_schedule(steps, timesteps=timesteps,
                                       beta_start=beta_start,
                                       beta_end=beta_end)
        consts = tuple(jnp.asarray(a, jnp.float32) for a in (
            np.sqrt(abar), np.sqrt(1.0 - abar), np.sqrt(prev),
            np.sqrt(1.0 - prev)))
        ts = jnp.asarray(ts)
        graph, traces = ex.graph, ex.traces

        def sample(p, x):
            traces["sample", tuple(x.shape)] += 1
            a, s, a_prev, s_prev = consts

            def step(k, x):
                t = jnp.full((x.shape[0],), ts[k], jnp.int32)
                eps = _executor.execute(graph, (x, t), params=p)
                with jax.named_scope("sampler"):
                    x0 = (x - s[k] * eps) / a[k]
                    return a_prev[k] * x0 + s_prev[k] * eps
            return jax.lax.fori_loop(0, steps, step, x)
        self._sample = jax.jit(sample)

    def apply(self, x):
        """Images from the noise batch ``x`` (NHWC), jitted."""
        with jax.profiler.TraceAnnotation("executor.apply"), \
                use_rules(self.executor.rules):
            y = self._sample(self.executor.params, x)
        self.steps_run += self.steps
        return y

    def lower(self, x):
        """The sampling program, lowered for ``x`` (ahead-of-time
        inspection: ``.compile().as_text()`` names what it runs)."""
        with use_rules(self.executor.rules):
            return self._sample.lower(self.executor.params, x)
