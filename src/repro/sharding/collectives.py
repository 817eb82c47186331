"""shard_map building blocks for the distribution layer.

* :func:`flash_decode_attention` — decode attention with the KV cache
  sharded along *sequence* over the 'model' axis (flash-decoding): each
  shard computes a partial (m, l, o) softmax triple over its cache slice;
  the exact global softmax is reconstructed with one pmax + two psums of
  O(B·H·D) — instead of all-gathering the (B·S·KVH·D) cache.  This is the
  §Perf fix for decode cells (the XLA baseline all-gathers the cache).

* :func:`gpipe_forward` — GPipe-style pipelined forward over an axis
  ('pod'): stage p holds layers [p·L/P, (p+1)·L/P); microbatches stream
  through a collective_permute shift register.  Forward-only (serving /
  dry-run); the training path uses DP over 'pod' by default.

* :func:`compressed_allreduce` — int8 error-feedback gradient all-reduce
  (optim/compress.py) bound to a mesh axis.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.optim.compress import compressed_psum


# ---------------------------------------------------------------------------
# Flash-decoding: distributed LSE combine over a sequence-sharded cache
# ---------------------------------------------------------------------------

def _local_partial(q, k, v, valid, scale):
    """Partial attention over the local KV slice (GQA-aware).

    q: (B, KVH, G, D); k, v: (B, S_l, KVH, D); valid: (B, S_l) bool.
    Returns (o: (B, KVH, G, D) unnormalized, l: (B, KVH, G), m: same).
    """
    s = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return o, l, m


def flash_decode_attention(q, k, v, valid, *, mesh: Mesh,
                           axis: str = "model"):
    """Exact decode attention with seq-sharded KV (GQA supported).

    q: (B, H, D) replicated over ``axis``; k, v: (B, S, KVH, D) sharded on
    S; valid: (B, S) bool sharded on S.  H must be a multiple of KVH.
    Returns (B, H, D).
    """
    b, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, kvh, g, d)
    # keep the batch dim sharded over the data axes — only the kv-seq dim
    # participates in the LSE combine (replicating batch would all-gather
    # the entire cache across 'data': the refuted first attempt, see §Perf)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape) or None
    if batch_axes:
        n_data = 1
        for a in batch_axes:
            n_data *= mesh.shape[a]
        if b % n_data != 0:           # e.g. long_500k batch=1: replicate
            batch_axes = None
    bspec = batch_axes if batch_axes and len(batch_axes) > 1 else \
        (batch_axes[0] if batch_axes else None)

    def local(qg, k, v, valid):
        o, l, m = _local_partial(qg, k, v, valid, scale)
        g_m = lax.pmax(m, axis)
        corr = jnp.exp(m - g_m)
        o = lax.psum(o * corr[..., None], axis)
        l = lax.psum(l * corr, axis)
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    out = shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec), P(bspec, axis), P(bspec, axis), P(bspec, axis)),
        out_specs=P(bspec),
    )(qg, k, v, valid)
    return out.reshape(b, h, d)


def flash_decode_reference(q, k, v, valid):
    """Oracle: plain masked softmax attention over the full cache (GQA)."""
    b, h, d = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, kvh, h // kvh, d)
    scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w, v.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# GPipe forward over an axis
# ---------------------------------------------------------------------------

def gpipe_forward(stage_fn, stage_params, x, *, mesh: Mesh,
                  axis: str = "pod", num_micro: int = 4):
    """Pipelined forward.

    stage_params: pytree stacked on a leading stage axis (size = mesh[axis]),
    sharded over ``axis``.  x: (B, ...) replicated.  stage_fn(params, x_mb)
    applies one stage.  Returns stage_{P-1}'s outputs for all microbatches.
    """
    n_stage = mesh.shape[axis]
    assert x.shape[0] % num_micro == 0

    def local(params_local, x_local):
        params_local = jax.tree.map(lambda t: t[0], params_local)
        idx = lax.axis_index(axis)
        mbs = x_local.reshape((num_micro, x_local.shape[0] // num_micro)
                              + x_local.shape[1:])
        buf = jnp.zeros_like(mbs[0])
        outs = jnp.zeros_like(mbs)
        perm = [(i, i + 1) for i in range(n_stage - 1)]
        for t in range(num_micro + n_stage - 1):
            inject = mbs[min(t, num_micro - 1)]
            buf_in = jnp.where(idx == 0,
                               jnp.where(t < num_micro, inject,
                                         jnp.zeros_like(inject)),
                               buf)
            y = stage_fn(params_local, buf_in)
            out_t = t - (n_stage - 1)
            if 0 <= out_t < num_micro:
                outs = outs.at[out_t].set(y)
            buf = lax.ppermute(y, axis, perm)
        # only the last stage's outs are meaningful — replicate them
        outs = lax.psum(jnp.where(idx == n_stage - 1, outs,
                                  jnp.zeros_like(outs)), axis)
        return outs.reshape(x_local.shape)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)


def compressed_allreduce(grads, *, mesh: Mesh, axis: str = "data"):
    """int8 all-reduce of data-parallel gradients (call on replicated-over-
    axis grads; returns the summed result on every shard)."""
    fn = shard_map(lambda g: compressed_psum(g, axis), mesh=mesh,
                    in_specs=P(axis), out_specs=P(axis))
    return fn(grads)
