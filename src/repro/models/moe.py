"""Mixture-of-Experts FFN — top-k routing with capacity-based dispatch.

Dispatch/combine use **scatter-add / gather** (O(N·k·D) memory) rather than
the classical GShard one-hot einsums (O(N·E·C) — intractable at production
shapes: qwen3-moe train_4k would need a ~10^13-element dispatch tensor).
Capacity semantics match GShard: per-expert buffers of
``C = ceil(N·k/E · capacity_factor)`` slots, first-come-first-served in
token order; overflowing (token, slot) pairs are dropped (their gate weight
is zeroed, the residual path carries the token).

Sharding: the ``experts`` logical axis maps to the mesh 'model' axis
(expert parallelism); tokens stay on 'data'.  XLA inserts the all-to-all
pair around the expert GEMMs.  A sort-based grouped-GEMM dispatch is the
§Perf upgrade path.

LayerMerge note (DESIGN §2.3): routed expert FFNs are *prunable but not
linearizable* — routing is input-dependent and discontinuous, so MoE
sublayers participate in the DP only as prune-or-keep units.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def moe_axes():
    return {
        "router": ("embed", "experts"),
        "w_gate": ("experts", "expert_embed", "expert_ffn"),
        "w_up": ("experts", "expert_embed", "expert_ffn"),
        "w_down": ("experts", "expert_ffn", "expert_embed"),
    }


def init_moe(cfg, key, dtype):
    d, e, dff = cfg.d_model, cfg.num_experts, cfg.moe_dff
    ks = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(dff)
    p = {
        "router": jax.random.normal(ks[0], (d, e), dtype) * s_in,
        "w_gate": jax.random.normal(ks[1], (e, d, dff), dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (e, d, dff), dtype) * s_in,
        "w_down": jax.random.normal(ks[3], (e, dff, d), dtype) * s_out,
    }
    return p, moe_axes()


def route(p, xt, cfg):
    """Top-k gating.  xt: (N, D) → (gates (N,k), experts (N,k) int32)."""
    logits = (xt @ p["router"]).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    top_g, top_e = jax.lax.top_k(gates, cfg.experts_per_token)
    top_g = top_g / jnp.sum(top_g, axis=-1, keepdims=True)
    return top_g.astype(xt.dtype), top_e


def capacity_positions(top_e, num_experts, capacity):
    """FCFS slot index of each (token, slot) within its expert's buffer.

    Sort-based ranking: stable-argsort groups token-slots by expert, the
    within-group rank is ``arange − group_start``.  O(Nk log Nk) work and an
    O(E) cumsum — the naive one-hot cumsum is O(Nk·E) memory and lowers to
    quadratic reduce-window work (~10^14 FLOPs/chip at qwen3-moe train_4k).
    """
    n, k = top_e.shape
    flat = top_e.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    counts = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1, mode="drop")
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    ranks_sorted = jnp.arange(n * k, dtype=jnp.int32) - starts[sorted_e]
    pos = jnp.zeros((n * k,), jnp.int32).at[order].set(ranks_sorted,
                                                       mode="drop")
    pos = pos.reshape(n, k)
    keep = pos < capacity
    return pos, keep


def capacity_positions_cumsum(top_e, num_experts, capacity):
    """Reference one-hot-cumsum ranking (GShard formulation) — kept as the
    oracle for the sort-based version; only safe at toy sizes."""
    n, k = top_e.shape
    onehot = jax.nn.one_hot(top_e.reshape(n * k), num_experts,
                            dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)
    pos = jnp.sum(pos * onehot, axis=-1).reshape(n, k)
    keep = pos < capacity
    return pos, keep


def _moe_group(p, xt, cfg, capacity):
    """Single-group dispatch→experts→combine (vmapped over groups)."""
    e = cfg.num_experts
    top_g, top_e = route(p, xt, cfg)
    pos, keep = capacity_positions(top_e, e, capacity)
    gate_kept = top_g * keep.astype(top_g.dtype)
    safe_pos = jnp.where(keep, pos, capacity - 1)
    contrib = jnp.where(keep[..., None], 1.0, 0.0).astype(xt.dtype)
    expert_in = jnp.zeros((e, capacity, xt.shape[-1]), xt.dtype)
    expert_in = expert_in.at[top_e, safe_pos].add(
        xt[:, None, :] * contrib, mode="drop")
    return expert_in, (top_e, safe_pos, gate_kept)


def moe_ffn(p, x, cfg, *, capacity_factor: float = 1.25,
            num_groups: int | None = None):
    """x: (B, S, D) → (B, S, D).  Top-k, capacity-dropped, GShard-style
    GROUPED dispatch: tokens are grouped by data shard so the scatter and
    gather are chip-local; buffers are sharded (group→data, expert→model)
    and only the token-sized combine crosses the 'model' axis.

    §Perf lesson (EXPERIMENTS.md): an ungrouped global-capacity buffer makes
    XLA psum whole (E, C, D) buffers across data shards (~27 GB/chip/step at
    qwen3-moe train_4k); a capacity-dim sharding constraint is 22× worse
    (scatter targets are data-dependent, XLA falls back to full exchange).
    Grouping is what removes the buffer collectives entirely.
    """
    from repro.sharding.rules import current_rules, logical_constraint
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    n = b * s
    if num_groups is None:
        r = current_rules()
        num_groups = 1
        if r is not None and r.mesh is not None:
            num_groups = int(__import__("numpy").prod(
                [r.mesh.shape[a] for a in ("pod", "data")
                 if a in r.mesh.shape]))
    g = max(1, math.gcd(num_groups, n))
    xt = x.reshape(g, n // g, d)
    capacity = max(int(math.ceil(n / g * k / e * capacity_factor)), 1)
    expert_in, (top_e, safe_pos, gate_kept) = jax.vmap(
        lambda xg: _moe_group(p, xg, cfg, capacity))(xt)
    expert_in = logical_constraint(expert_in,
                                   ("moe_group", "experts", None, None))

    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in, p["w_gate"]))
    h = h * jnp.einsum("gecd,edf->gecf", expert_in, p["w_up"])
    expert_out = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    expert_out = logical_constraint(expert_out,
                                    ("moe_group", "experts", None, None))

    # group-local gather + gate-weighted combine
    out = jax.vmap(lambda eo, te, sp, gk:
                   jnp.sum(eo[te, sp] * gk[..., None], axis=1))(
        expert_out, top_e, safe_pos, gate_kept)
    out = logical_constraint(out.reshape(b, s, d),
                             ("batch", "seq", "act_embed"))
    return out


def moe_ffn_sharded(p, x, cfg, *, capacity_factor: float = 1.25, rules=None):
    """shard_map MoE (§Perf iteration 3): expert-local dispatch + one
    token-sized psum.

    Each (data, model) chip: routes its LOCAL tokens against the full router
    (512 KB gather), scatters only the slots destined to its LOCAL experts
    into an (E_loc, C, D) buffer (no communication), runs the expert GEMMs,
    gathers its partial token outputs, and psums (tokens × d_model) over the
    'model' axis — ~268 MB/layer at qwen3 train_4k instead of the
    ~15.8 GB/layer of buffer all-reduce XLA's SPMD chose for the gather/
    scatter formulation (EXPERIMENTS §Perf).

    Expert weights are TP-sharded over 'model' and replicated over data
    ('expert_embed' rule); optimizer moments stay fully sharded (ZeRO-1).
    """
    import numpy as np

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    n_model = mesh.shape["model"]
    e_loc = e // n_model
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_data = int(np.prod([mesh.shape[a] for a in data_axes])) or 1
    n = b * s
    capacity = max(int(math.ceil(n / n_data * k / e * capacity_factor)), 1)
    bspec = data_axes if len(data_axes) > 1 else data_axes[0]

    def local(x_loc, router, wg, wu, wd):
        nt = x_loc.shape[0] * x_loc.shape[1]
        xt = x_loc.reshape(nt, d)
        logits = (xt @ router).astype(jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)
        top_g, top_e = jax.lax.top_k(gates, k)
        top_g = (top_g / jnp.sum(top_g, axis=-1, keepdims=True)
                 ).astype(xt.dtype)
        pos, keep = capacity_positions(top_e, e, capacity)
        ei = jax.lax.axis_index("model")
        local_slot = top_e - ei * e_loc
        is_local = (local_slot >= 0) & (local_slot < e_loc)
        contrib = keep & is_local
        safe_slot = jnp.where(contrib, local_slot, 0)
        safe_pos = jnp.where(contrib, pos, capacity - 1)
        cmask = contrib[..., None].astype(xt.dtype)
        buf = jnp.zeros((e_loc, capacity, d), xt.dtype)
        buf = buf.at[safe_slot, safe_pos].add(xt[:, None, :] * cmask,
                                              mode="drop")
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd)
        part = out_buf[safe_slot, safe_pos] * (top_g[..., None] * cmask)
        out = jax.lax.psum(jnp.sum(part, axis=1), "model")
        return out.reshape(x_loc.shape)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec), P(), P("model"), P("model"), P("model")),
        out_specs=P(bspec),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def moe_dispatch(p, x, cfg, *, capacity_factor: float = 1.25):
    """Entry point used by the model: picks the shard_map path when an
    expert-divisible mesh is active, else the dense grouped path."""
    from repro.sharding.rules import current_rules
    r = current_rules()
    if r is not None and r.mesh is not None and "model" in r.mesh.shape \
            and cfg.num_experts % r.mesh.shape["model"] == 0 \
            and r.rules.get("moe_shard_map", True):
        return moe_ffn_sharded(p, x, cfg, capacity_factor=capacity_factor,
                               rules=r)
    return moe_ffn(p, x, cfg, capacity_factor=capacity_factor)


def aux_load_balance_loss(p, x, cfg):
    """Switch-style load-balancing auxiliary (fraction·prob dot product)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    logits = (xt @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_e = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top_e, cfg.num_experts), axis=0)
    prob = jnp.mean(probs, axis=0)
    return cfg.num_experts * jnp.sum(frac * prob)
