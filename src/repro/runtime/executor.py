"""Shared UnitGraph interpreter — ONE execution path for every merged net.

Replaces the two per-host apply loops (``cnn.apply_merged`` over
``MergedUnit`` lists and ``transformer_host._apply_units`` /
``T.forward_compressed`` over tuple units): both hosts lower plans to
:class:`repro.runtime.ir.UnitGraph` and this module runs them.  Every
unit routes through the public kernel entry points in
:mod:`repro.kernels` — Pallas ``merged_conv`` / ``merged_ffn`` on TPU,
the jnp oracles elsewhere — so the serving path exercises exactly the
kernels the latency tables timed.

Entry points:

* :func:`execute` — full forward (CNN image / transformer prefill).
* :func:`run_units` — a bare unit chain, no embed/head (segment probes).
* :func:`init_cache` / :func:`decode_step` / :func:`make_serve_step` —
  KV-cache-aware one-token decode for serving compressed transformers;
  :func:`slot_state` stacks the per-unit cache into the per-slot state
  the continuous serve engine vmaps over.
* :func:`jit_apply` — jitted ``fn(params, inputs)`` with the graph's
  arrays exposed as a pytree (fine-tuning / sharding consumers).
* :class:`GraphExecutor` — the mesh-aware serving entry point: resolves
  the graph's logical-axis annotations (:mod:`repro.runtime.ir`) through
  a :class:`ShardingRules` into ``NamedSharding``s, places params and
  caches, and jits prefill/decode once under the mesh.  ``rules=None``
  (or a one-device mesh) is the SAME code path — every
  ``logical_constraint`` is a no-op without ambient rules — so the
  single-host executor is just the trivial mesh, not a second
  interpreter.

The unit loop is a python loop: compressed networks are shallow by
construction (that is the point of the paper), so trace cost is small
and every unit keeps its own fused kernel launch.

Names for profiles.  The CNN forward runs unit ``i`` of ``graph.units``
under ``jax.named_scope(f"unit{i:02d}")`` and each of its ops under one
role scope: ``pad`` (spatial padding), ``lane_pad`` (input channels to
128 lanes, groups to a whole block), ``weight_prep`` (weight, bias and
scale padding or reshaping done per call), ``relayout`` (phase-major),
``kernel`` (the ``pallas_call``, named ``merged_conv`` or
``depthwise_conv``; off the TPU, the jnp oracle), ``crop`` (output
slices) or ``epilogue`` (residual add, projection, concat, group norm,
activation; a pool, upsample or attention unit whole); the classifier
runs under ``head``.  The names
reach the compiled HLO as ``op_name`` metadata and change nothing else.
:meth:`GraphExecutor.apply` runs under the profiler span
``executor.apply``, and ``GraphExecutor.traces`` counts its traces
(compiles) by input shape.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro import kernels
from repro.models import cnn as _cnn
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import transformer as T
from repro.models import xlstm as XL
from repro.sharding.rules import (current_rules, logical_constraint,
                                  param_shardings_with_shapes, use_rules)

from . import ir


def execute(graph: ir.UnitGraph, inputs, params=None):
    """Run a UnitGraph: NHWC image batch (cnn) or token batch (transformer).

    ``params`` optionally rebinds the graph's arrays (see
    :func:`repro.runtime.ir.graph_params`) — the pure-function form used
    under jit and by fine-tuning consumers.
    """
    if params is not None:
        graph = ir.bind_params(graph, params)
    if graph.family == "cnn":
        return _execute_cnn(graph, inputs)
    if graph.family == "transformer":
        return _execute_transformer(graph, inputs)
    raise ValueError(f"unknown graph family {graph.family!r}")


def jit_apply(graph: ir.UnitGraph):
    """(jitted ``fn(params, inputs)``, params pytree) for a graph."""
    params = ir.graph_params(graph)
    fn = jax.jit(lambda p, x: execute(graph, x, params=p))
    return fn, params


# ---------------------------------------------------------------------------
# CNN family
# ---------------------------------------------------------------------------

#: NHWC activation layout of the CNN unit loop: batch data-parallel,
#: channels on the model axis (the merged-conv analogue of 'act_ffn')
_CNN_ACT = ("batch", None, None, "act_channels")


def _conv_kernel(u, x):
    """One merged conv unit through its kernel entry point.

    XLA cannot partition a Mosaic kernel, so under a mesh the call runs
    once per data shard inside a ``shard_map``, its weights replicated.
    """
    ws = u.params.get("w_scale")
    aq = u.quant if (ws is not None and u.quant == "w8a8") else "none"
    op = kernels.depthwise_conv_op if u.depthwise else kernels.merged_conv_op

    def call(x, w, b, ws):
        return op(x, w, b, stride=u.stride, w_scale=ws, act_quant=aq)

    args = (x, u.params["w"], u.params["b"], ws)
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return call(*args)
    bspec = P(*rules.spec(("batch",), x.shape[:1]))
    return shard_map(call, mesh=rules.mesh,
                     in_specs=(bspec, P(), P(), P()), out_specs=bspec,
                     check_vma=False)(*args)


def _execute_cnn(graph: ir.UnitGraph, x):
    saved: dict[int, jax.Array] = {}
    x = logical_constraint(x, _CNN_ACT)
    if graph.meta.get("save_input"):
        saved[0] = x
    for i, u in enumerate(graph.units):
        with jax.named_scope(f"unit{i:02d}"):
            x = _cnn_unit(u, x, saved)
        if u.save_at is not None:
            saved[u.save_at] = x
    if graph.meta.get("head") == "classifier":
        with jax.named_scope("head"):
            head = graph.params["head"]
            x = x.mean(axis=(1, 2))
            x = x @ head["w"] + head["b"]
    return x


def _cnn_unit(u, x, saved):
    """One CNN unit, its ops under the role scopes of the module docstring:
    ``pad`` here, the kernel wrappers' own roles, then ``epilogue``."""
    if u.kind == "conv":
        K = u.params["w"].shape[0]
        lo = (K - 1) // 2
        hi = K - 1 - lo
        if K > 1:
            with jax.named_scope("pad"):
                x = jnp.pad(x, ((0, 0), (lo, hi), (lo, hi), (0, 0)))
        x = _conv_kernel(u, x)
    with jax.named_scope("epilogue"):
        if u.kind == "conv":
            if u.add_from is not None:
                base = saved[u.add_from]
                if "proj" in u.params:
                    pr = u.params["proj"]
                    base = _cnn._conv(base, pr["w"], u.proj_stride, False,
                                      padding="SAME") + pr["b"]
                x = x + base
            if u.concat_from is not None:
                x = jnp.concatenate([x, saved[u.concat_from]], axis=-1)
            if "gn" in u.params:
                x = _cnn._gn(x, u.params["gn"], u.gn_groups)
            x = _cnn._act(x, u.act)
        elif u.kind == "pool":
            x = jax.lax.reduce_window(
                x, 0.0, jax.lax.add, (1, u.k, u.k, 1),
                (1, u.stride, u.stride, 1), "SAME") / (u.k * u.k)
            if u.concat_from is not None:
                x = jnp.concatenate([x, saved[u.concat_from]], axis=-1)
        elif u.kind == "upsample":
            n, h, w_, c = x.shape
            x = jax.image.resize(
                x, (n, h * u.factor, w_ * u.factor, c), "nearest")
            if u.concat_from is not None:
                x = jnp.concatenate([x, saved[u.concat_from]], axis=-1)
        elif u.kind == "attn":
            x = _cnn._tiny_self_attention(x, u.params)
        else:
            raise ValueError(f"unit kind {u.kind!r} in cnn graph")
        return logical_constraint(x, _CNN_ACT)


# ---------------------------------------------------------------------------
# Transformer family
# ---------------------------------------------------------------------------

def _apply_unit(cfg, u, x, positions, mrope):
    """One prefill/probe unit: lowrank residual or kept sublayer."""
    if u.kind == "lowrank":
        us, vs = u.params.get("u_scale"), u.params.get("v_scale")
        aq = u.quant if (us is not None and u.quant == "w8a8") else "none"
        return logical_constraint(
            kernels.merged_ffn_op(x, u.params["u"], u.params["v"],
                                  u_scale=us, v_scale=vs, act_quant=aq),
            ("batch", "seq", "act_embed"))
    if u.kind != "sublayer":
        raise ValueError(f"unit kind {u.kind!r} in transformer graph")
    sub = u.params
    h = L.rms_norm(x, sub["norm"], cfg.norm_eps)
    kind = u.sub_kind
    if kind == "moe":
        t = MOE.moe_ffn(sub["p"], h, cfg, capacity_factor=cfg.capacity_factor)
    elif kind == "ffn":
        t = L.ffn(sub["p"], h, cfg.ffn_kind)
    else:
        t = T._temporal_apply(cfg, kind, sub["p"], h, positions, mrope)
    return logical_constraint(x + t, ("batch", "seq", "act_embed"))


def run_units(cfg, units, x, positions=None):
    """Bare unit chain, no embed/unembed — the segment-probe forward."""
    if positions is None:
        positions = jnp.arange(x.shape[1])[None, :]
    for u in units:
        x = _apply_unit(cfg, u, x, positions, None)
    return x


def _execute_transformer(graph: ir.UnitGraph, batch):
    cfg = graph.meta["config"]
    gp = graph.params
    x = T._embed_in(cfg, gp, batch)
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.arange(x.shape[1])[None, :]
    mrope = batch.get("mrope_positions")
    for u in graph.units:
        x = _apply_unit(cfg, u, x, positions, mrope)
    x = L.rms_norm(x, gp["final_norm"], cfg.norm_eps)
    return T._unembed(cfg, gp, x)


# ---------------------------------------------------------------------------
# KV-cache decode (serving)
# ---------------------------------------------------------------------------

def _state_axes(u) -> dict:
    """Logical axes of one unit's decode state ('kv_seq' decode layout)."""
    if u.kind == "sublayer" and u.sub_kind in ir.TEMPORAL_KINDS:
        if u.sub_kind in ("attn", "attn_local"):
            return dict(L.CACHE_AXES)
        if u.sub_kind == "rglru":
            return dict(RG.RGLRU_STATE_AXES)
        if u.sub_kind == "mlstm":
            return dict(XL.MLSTM_STATE_AXES)
        return dict(XL.SLSTM_STATE_AXES)
    return {}


def cache_axes(graph: ir.UnitGraph) -> list:
    """Per-unit logical-axes pytree aligned with :func:`init_cache`."""
    return [_state_axes(u) for u in graph.units]


def _is_names(x):
    return isinstance(x, tuple) or x is None


def _constrain_state(c, ax):
    """logical_constraint over one unit's decode-state pytree."""
    if not ax:
        return c
    return jax.tree.map(
        lambda names, a: logical_constraint(a, names) if names else a,
        ax, c, is_leaf=_is_names)


def init_cache(graph: ir.UnitGraph, batch_size: int, seq_len: int):
    """Per-unit decode state: KV cache for attention sublayers, recurrent
    state for rglru/mlstm/slstm, ``{}`` for stateless units."""
    cfg = graph.meta["config"]
    dtype = jnp.dtype(cfg.dtype)
    caches = []
    for u in graph.units:
        if u.kind == "sublayer" and u.sub_kind in ir.TEMPORAL_KINDS:
            if u.sub_kind in ("attn", "attn_local"):
                window = cfg.local_window if u.sub_kind == "attn_local" else 0
                caches.append(L.init_cache(cfg, batch_size, seq_len, dtype,
                                           window=window))
            elif u.sub_kind == "rglru":
                caches.append(RG.init_rglru_state(cfg, batch_size, dtype))
            elif u.sub_kind == "mlstm":
                caches.append(XL.init_mlstm_state(cfg, batch_size))
            else:
                caches.append(XL.init_slstm_state(cfg, batch_size))
        else:
            caches.append({})
    return caches


def decode_step(graph: ir.UnitGraph, cache, batch):
    """One-token decode through the compressed unit chain.

    ``batch``: {'tokens': (B, 1)} (or 'embeds').  Returns (logits,
    new_cache).  Low-rank units are position-independent residual maps,
    so they apply to the single-token activation directly — the merged
    segments cost O(1) state, one of the serving wins of depth
    compression.
    """
    cfg = graph.meta["config"]
    gp = graph.params
    x = T._embed_in(cfg, gp, batch)
    mrope = batch.get("mrope_positions")
    new_cache = []
    for u, c in zip(graph.units, cache):
        if u.kind == "sublayer" and u.sub_kind in ir.TEMPORAL_KINDS:
            sub = u.params
            h = L.rms_norm(x, sub["norm"], cfg.norm_eps)
            kind = u.sub_kind
            if kind in ("attn", "attn_local"):
                window = cfg.local_window if kind == "attn_local" else 0
                t, c = L.attention_decode(sub["p"], h, cfg, c, window=window,
                                          mrope_positions=mrope)
            elif kind == "rglru":
                t, c = RG.rglru_decode(sub["p"], h, cfg, c)
            elif kind == "mlstm":
                t, c = XL.mlstm_decode(sub["p"], h, cfg, c)
            else:
                t, c = XL.slstm_decode(sub["p"], h, cfg, c)
            x = logical_constraint(x + t, ("batch", "seq", "act_embed"))
            c = _constrain_state(c, _state_axes(u))
        else:
            x = _apply_unit(cfg, u, x, None, mrope)
        new_cache.append(c)
    x = L.rms_norm(x, gp["final_norm"], cfg.norm_eps)
    return T._unembed(cfg, gp, x), new_cache


def make_serve_step(graph: ir.UnitGraph):
    """(``step(params, cache, batch) → (logits, cache)``, params pytree).

    The jittable one-token serve step for a compressed transformer —
    the artifact-backed analogue of
    :func:`repro.train.step.make_serve_step`.
    """
    params = ir.graph_params(graph)

    def step(p, cache, batch):
        return decode_step(ir.bind_params(graph, p), cache, batch)
    return step, params


def slot_state(graph: ir.UnitGraph, slots: int, seq_len: int):
    """Per-slot decode state for the continuous serve engine.

    One fresh single-request cache (:func:`init_cache` with batch 1)
    stacked so every leaf gains a leading ``(slots,)`` axis — including
    each attention cache's scalar ``pos``, which is what lets every slot
    advance its own sequence position independently under the engine's
    vmapped chunk step (see :func:`repro.runtime.serving.stack_cache`).
    """
    from .serving import stack_cache
    return stack_cache(init_cache(graph, 1, seq_len), slots)


# ---------------------------------------------------------------------------
# Mesh-aware execution (sharded serving)
# ---------------------------------------------------------------------------

def graph_shardings(rules, graph: ir.UnitGraph):
    """NamedSharding pytree for :func:`ir.graph_params` under ``rules``.

    Resolved from the graph's declarative axes annotations with per-leaf
    divisibility fallback (a dim the mesh does not divide is replicated,
    the GQA kv<TP contract) — sharding stays data in the artifact.
    """
    return param_shardings_with_shapes(rules, ir.graph_axes(graph),
                                       ir.graph_params(graph))


def cache_shardings(rules, graph: ir.UnitGraph, cache):
    """NamedSharding pytree for a decode cache ('kv_seq' layout)."""
    return param_shardings_with_shapes(rules, cache_axes(graph), cache)


def _shapes(tree) -> tuple:
    """The shape of an array input, or the shapes of a pytree's leaves."""
    shapes = tuple(tuple(a.shape) for a in jax.tree.leaves(tree))
    return shapes[0] if len(shapes) == 1 else shapes


class GraphExecutor:
    """Jitted, mesh-aware prefill/decode over one :class:`UnitGraph`.

    ``rules=None`` (or a rules object without a mesh) is the trivial
    single-device executor: the same traced programs, with every
    ``logical_constraint`` a no-op and params left where they are.  With
    rules, params are ``device_put`` onto the shardings their logical
    axes resolve to, prefill/decode are traced once under the ambient
    rules (so activation and KV-cache constraints bake into the jitted
    programs), and fresh caches come back mesh-placed.
    """

    def __init__(self, graph: ir.UnitGraph, rules=None):
        self.graph = graph
        self.rules = rules if (rules is not None
                               and rules.mesh is not None) else None
        params = ir.graph_params(graph)
        if self.rules is not None:
            params = jax.device_put(params, graph_shardings(self.rules,
                                                            graph))
        self.params = params
        #: traces of ``apply``, by ``("apply", input shape)``: the Python
        #: body runs only when JAX traces, so a count above 1, or a key
        #: that appears during serving, is a recompile.  The body closes
        #: over the counter, not ``self``, so that dropping the executor
        #: frees its weights at once.
        traces = self.traces = collections.Counter()

        def prefill(p, batch):
            traces["apply", _shapes(batch)] += 1
            return execute(graph, batch, params=p)
        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(
            lambda p, cache, batch: decode_step(ir.bind_params(graph, p),
                                                cache, batch))

    def apply(self, batch, params=None):
        """Full forward (CNN image batch / transformer prefill), jitted."""
        with jax.profiler.TraceAnnotation("executor.apply"), \
                use_rules(self.rules):
            return self._prefill(self.params if params is None else params,
                                 batch)

    def lower(self, batch, params=None):
        """The jitted full forward, lowered for ``batch`` (ahead-of-time
        inspection: ``.compile().as_text()`` names the kernels it runs)."""
        with use_rules(self.rules):
            return self._prefill.lower(
                self.params if params is None else params, batch)

    def init_cache(self, batch_size: int, seq_len: int):
        cache = init_cache(self.graph, batch_size, seq_len)
        if self.rules is not None:
            cache = jax.device_put(
                cache, cache_shardings(self.rules, self.graph, cache))
        return cache

    def decode(self, cache, batch, params=None):
        """One-token decode step, jitted: ``(logits, new_cache)``."""
        with use_rules(self.rules):
            return self._decode(self.params if params is None else params,
                                cache, batch)

    def serve_step(self):
        """``(step(params, cache, batch), params)`` for the serve loops.

        The step is unjitted — :mod:`repro.runtime.serving` scans and
        jits it; callers must run it under ``use_rules(self.rules)``
        (the serving entry points take ``rules=`` and do this).
        """
        step, _ = make_serve_step(self.graph)
        return step, self.params

    def continuous_engine(self, *, slots: int, max_seq: int, **kw):
        """A :class:`repro.runtime.serving.ContinuousEngine` over this
        graph: mid-stream admission/retirement with per-slot failure
        isolation, using the executor's params and cache constructor.
        Keyword extras (``chunk``, ``eos_id``, ``max_queue``,
        ``slot_nan_limit``, ``clock``, ...) pass through.  Certified on
        a single device; under a mesh prefer the fixed scheduler.
        """
        from .serving import ContinuousEngine
        step, params = self.serve_step()
        return ContinuousEngine(
            step, params, lambda b, s: init_cache(self.graph, b, s),
            slots=slots, max_seq=max_seq, rules=self.rules, **kw)
