"""Plan-aware CNNs in pure JAX — the paper-faithful substrate.

A network is a flat chain of 1-indexed :class:`ConvSpec` units plus skip
annotations.  The same definition can be *applied* three ways:

* original            — ``apply_replaced(net, params, x, identity_plan)``;
* replaced (pruned, unmerged) — ``apply_replaced(net, params, x, plan)``:
  activations outside ``A`` are dropped, convs outside ``C`` become the
  identity, padding is re-ordered to the front of every merged group
  (paper Appendix A), GroupNorms are moved to group ends (in a
  pre-activation net, below, the GroupNorm before a dropped activation
  goes with it);
* merged              — ``CNNHost.lower_plan(plan, params)`` folds every
  segment into a single convolution (Eq. 1 composition via
  :func:`merge_segment`: BN folding, skip-add Dirac fusion) and lowers
  the result to a :class:`repro.runtime.ir.UnitGraph` that the shared
  executor (:mod:`repro.runtime.executor`) runs.

``apply_replaced(plan)`` and the executed merged graph are *exactly
equal* (same function, same floats up to accumulation order) — asserted
by ``tests/test_merge.py`` and ``tests/test_runtime.py``; this equality
is the cornerstone of the paper's method.

Skip blocks may carry a projection shortcut (ResNet downsample blocks);
those blocks cannot be Dirac-fused, so spans may only sit *inside* them.

A *pre-activation* net (``ConvNet.preact``, the DDPM UNet of
:func:`repro.models.zoo.ddpm_unet`) reads ``norm``/``act`` of a conv as
acting on the conv's input: ``conv(act(GN(x)))``.  The nonlinearity kept
at a segment boundary ``i`` is then layer ``i+1``'s GroupNorm and
activation, applied at the start of the segment to its branch, while
boundaries hold raw values: a residual add reads the raw block input.
Residual adds are never Dirac-fused there, and multi-layer spans lie
inside one residual block.  A time-conditioned net (``temb_ch > 0``)
takes the pair ``(x, t)``: a sinusoidal embedding of the integer
timesteps ``t`` through a two-layer MLP, whose SiLU feeds one Dense per
``temb`` conv, added after that conv as a per-image bias ``(N, C)``.  A
merged segment carries that bias folded through its later kept convs
(:func:`segment_time_bias`); a pruned conv takes its bias with it.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import merge as M
from repro.core.plan import CompressionPlan, LayerDesc, Segment, identity_plan


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ConvSpec:
    cin: int
    cout: int
    k: int = 3
    stride: int = 1
    depthwise: bool = False
    act: str = "relu"            # 'relu' | 'relu6' | 'silu' | 'none'
    norm: str | None = None      # None | 'bn' (frozen, foldable) | 'gn'
    gn_groups: int = 8
    bias: bool = True
    kind: str = "conv"           # 'conv' | 'pool' (avg) | 'upsample' | 'attn'
    temb: bool = False           # a per-image time bias follows the conv
    gn_eps: float = 1e-5

    @property
    def shape_preserving(self) -> bool:
        return (self.kind == "conv" and self.stride == 1
                and self.cin == self.cout)


@dataclasses.dataclass(frozen=True)
class SkipSpec:
    kind: str                    # 'add' | 'concat'
    start: int                   # boundary position (block = layers start+1..end)
    end: int
    proj: bool = False           # 1x1 projection shortcut (stride = block stride)


@dataclasses.dataclass(frozen=True)
class ConvNet:
    specs: tuple[ConvSpec, ...]
    skips: tuple[SkipSpec, ...] = ()
    in_hw: int = 32
    in_ch: int = 3
    head: str = "classifier"     # 'classifier' | 'none'
    num_classes: int = 10
    act_after_merge: bool = False   # paper's MobileNetV2 trick (Appendix A)
    preact: bool = False         # norm/act of a conv act on its input
    temb_dim: int = 0            # sinusoidal timestep embedding width
    temb_ch: int = 0             # time-MLP width; 0: not time-conditioned

    @property
    def L(self) -> int:
        return len(self.specs)

    def spec(self, l: int) -> ConvSpec:
        return self.specs[l - 1]

    # -- compressibility metadata -------------------------------------------
    def _in_block(self, i: int, j: int) -> bool:
        """Layers i+1..j lie inside one residual-add block."""
        return any(sk.kind == "add" and sk.start <= i and j <= sk.end
                   for sk in self.skips)

    def prunable(self, l: int) -> bool:
        """A shape-preserving conv; in a pre-activation net, one inside a
        residual block (the others are barriers)."""
        return self.spec(l).shape_preserving and (
            not self.preact or self._in_block(l - 1, l))

    def irreducible(self) -> tuple[int, ...]:
        """R — layers that cannot be pruned (shapes differ, non-convs, a
        pre-activation net's barriers)."""
        return tuple(l for l in range(1, self.L + 1) if not self.prunable(l))

    def layer_descs(self, params=None) -> list[LayerDesc]:
        descs = []
        for l in range(1, self.L + 1):
            s = self.spec(l)
            w = (params or {}).get("layers", [{}] * self.L)[l - 1].get("w") \
                if params else None
            val = float(jnp.sum(jnp.abs(w))) if w is not None else 0.0
            descs.append(LayerDesc(
                index=l, kind="dwconv" if s.depthwise else s.kind,
                growth=(s.k - 1) if s.kind == "conv" else 0,
                value=val,
                prunable=self.prunable(l),
                linearizable=(s.kind == "conv"),
                meta={"stride": s.stride, "k": s.k},
            ))
        return descs

    def allowed_span(self, i: int, j: int) -> bool:
        """Span predicate: skip-block consistency + barrier units
        (pool/upsample/attn) must not sit strictly inside.

        Strided interiors are *allowed*: the paper's Appendix A ban (don't
        merge a strided conv with a following k>1 conv) guarded against a
        kernel blow-up the old stride-1 Pallas fast path could not execute.
        The merged-conv kernel now runs strided segments on the MXU and the
        enumerator's stride-aware growth keeps the k coordinate exact, so
        the blow-up is a latency trade the DP prices from the table instead
        of a hard ban.

        In a pre-activation net a multi-layer span lies inside one
        residual block: its add reads the raw block input, which no conv
        of the branch sees, so no block is Dirac-fused.
        """
        if self.preact and j - i > 1 and not self._in_block(i, j):
            return False
        if j - i > 1:
            for l in range(i + 1, j + 1):
                if self.spec(l).kind != "conv":
                    return False
        for sk in self.skips:
            inter = max(0, min(j, sk.end) - max(i, sk.start))
            if inter == 0:
                continue
            inside = (sk.start <= i and j <= sk.end)
            whole_block = (i <= sk.start and sk.end <= j)
            if sk.kind == "concat" or sk.proj:
                # never merge across (or Dirac-fuse) these blocks
                if not inside:
                    return False
            else:  # plain skip-add
                if not (whole_block or inside):
                    return False
                if whole_block:
                    # Dirac fusion needs stride-1, odd kernels in the block
                    for l in range(sk.start + 1, sk.end + 1):
                        sl = self.spec(l)
                        if sl.stride > 1 or sl.k % 2 == 0 or sl.kind != "conv":
                            return False
        return True

    # -- shape inference ------------------------------------------------------
    def boundary_shapes(self) -> list[tuple[int, int, int]]:
        """(h, w, c) at every boundary position 0..L (post-concat)."""
        shapes = [(self.in_hw, self.in_hw, self.in_ch)]
        h = w = self.in_hw
        c = self.in_ch
        concat_at = {sk.end: sk.start for sk in self.skips
                     if sk.kind == "concat"}
        for l in range(1, self.L + 1):
            s = self.spec(l)
            if s.kind == "conv":
                h, w = -(-h // s.stride), -(-w // s.stride)
                c = s.cout
            elif s.kind == "pool":
                h, w = -(-h // s.stride), -(-w // s.stride)
            elif s.kind == "upsample":
                h, w = h * s.stride, w * s.stride
            if l in concat_at:
                c += shapes[concat_at[l]][2]
            shapes.append((h, w, c))
        return shapes


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(net: ConvNet, key: jax.Array, dtype=jnp.float32):
    if net.preact:
        return _init_preact(net, key, dtype)
    params = []
    keys = jax.random.split(key, net.L + len(net.skips) + 2)
    shapes = net.boundary_shapes()
    for l in range(1, net.L + 1):
        s = net.spec(l)
        p = {}
        if s.kind == "conv":
            cin_eff = shapes[l - 1][2]
            if s.depthwise:
                wshape = (s.k, s.k, 1, s.cout)
                fan_in = s.k * s.k
            else:
                wshape = (s.k, s.k, cin_eff, s.cout)
                fan_in = s.k * s.k * cin_eff
            w = jax.random.normal(keys[l], wshape, dtype) * math.sqrt(2.0 / fan_in)
            p["w"] = w
            if s.bias:
                p["b"] = jnp.zeros((s.cout,), dtype)
            if s.norm == "bn":
                p["bn"] = {"gamma": jnp.ones((s.cout,), dtype),
                           "beta": jnp.zeros((s.cout,), dtype),
                           "mean": jnp.zeros((s.cout,), dtype),
                           "var": jnp.ones((s.cout,), dtype)}
            elif s.norm == "gn":
                p["gn"] = {"gamma": jnp.ones((s.cout,), dtype),
                           "beta": jnp.zeros((s.cout,), dtype)}
        params.append(p)
    skip_params = []
    for idx, sk in enumerate(net.skips):
        if sk.proj:
            cin = shapes[sk.start][2]
            cout = shapes[sk.end][2]
            stride = 1
            for l in range(sk.start + 1, sk.end + 1):
                stride *= net.spec(l).stride
            w = jax.random.normal(keys[net.L + 1 + idx], (1, 1, cin, cout),
                                  dtype) * math.sqrt(2.0 / cin)
            skip_params.append({"w": w, "b": jnp.zeros((cout,), dtype)})
        else:
            skip_params.append({})
    head = {}
    if net.head == "classifier":
        c_final = shapes[-1][2]
        head["w"] = jax.random.normal(keys[0], (c_final, net.num_classes),
                                      dtype) * math.sqrt(1.0 / c_final)
        head["b"] = jnp.zeros((net.num_classes,), dtype)
    return {"layers": params, "skips": skip_params, "head": head}


def _init_preact(net: ConvNet, key: jax.Array, dtype):
    """Seeded weights of a pre-activation net: variance scaling (fan-avg,
    uniform) for every conv, 1×1 and dense layer, with no zero-initialised
    output layer; small random biases; GroupNorm affines near (1, 0)."""
    shapes = net.boundary_shapes()
    keys = iter(jax.random.split(key, 8 * (net.L + len(net.skips)) + 8))

    def vs(shape, fan_in, fan_out):
        lim = math.sqrt(6.0 / (fan_in + fan_out))
        return jax.random.uniform(next(keys), shape, dtype, -lim, lim)

    def bias(c):
        return 0.05 * jax.random.normal(next(keys), (c,), dtype)

    def gn(c):
        return {"gamma": 1.0 + 0.1 * jax.random.normal(next(keys), (c,),
                                                       dtype),
                "beta": 0.1 * jax.random.normal(next(keys), (c,), dtype)}

    def dense(cin, cout):
        return vs((cin, cout), cin, cout), bias(cout)

    layers = []
    for l in range(1, net.L + 1):
        s = net.spec(l)
        c = shapes[l - 1][2]
        p = {}
        if s.kind == "conv":
            p["w"] = vs((s.k, s.k, c, s.cout), s.k * s.k * c,
                        s.k * s.k * s.cout)
            p["b"] = bias(s.cout)
            if s.norm == "gn":
                p["gn"] = gn(c)
            if s.temb:
                w, b = dense(net.temb_ch, s.cout)
                p["temb"] = {"w": w, "b": b}
        elif s.kind == "attn":
            p["gn"] = gn(c)
            for n in "qkvo":
                p[f"w{n}"], p[f"b{n}"] = dense(c, c)
        layers.append(p)
    skips = []
    for sk in net.skips:
        # the block's own width: boundary ``end`` may also take a concat
        cin, cout = shapes[sk.start][2], net.spec(sk.end).cout
        skips.append({"w": vs((1, 1, cin, cout), cin, cout),
                      "b": bias(cout)} if sk.proj else {})
    out = {"layers": layers, "skips": skips, "head": {}}
    if net.temb_ch:
        w0, b0 = dense(net.temb_dim, net.temb_ch)
        w1, b1 = dense(net.temb_ch, net.temb_ch)
        out["temb"] = {"w0": w0, "b0": b0, "w1": w1, "b1": b1}
    return out


# ---------------------------------------------------------------------------
# Primitive application
# ---------------------------------------------------------------------------

def _act(x, name):
    if name == "relu":
        return jax.nn.relu(x)
    if name == "relu6":
        return jnp.clip(x, 0.0, 6.0)
    if name == "silu":
        return jax.nn.silu(x)
    return x


def _conv(x, w, stride, depthwise, padding="VALID"):
    groups = w.shape[-1] if depthwise else 1
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


def _frozen_bn(x, bn, eps=1e-5):
    scale = bn["gamma"] / jnp.sqrt(bn["var"] + eps)
    return x * scale + (bn["beta"] - bn["mean"] * scale)


def _gn(x, gn, groups, eps=1e-5):
    n, h, w, c = x.shape
    g = math.gcd(groups, c)
    xg = x.reshape(n, h, w, g, c // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) / jnp.sqrt(var + eps)
    return xg.reshape(n, h, w, c) * gn["gamma"] + gn["beta"]


def _folded_wb(spec: ConvSpec, p):
    """Conv weight/bias with frozen BN folded in (exact at inference)."""
    w = p["w"]
    b = p.get("b")
    if "bn" in p:
        bn = p["bn"]
        w, b = M.fold_batchnorm(w, b, bn["gamma"], bn["beta"], bn["mean"],
                                bn["var"])
    return w, (jnp.zeros((spec.cout,), w.dtype) if b is None else b)


def _center_crop_to(src, like):
    """Center-crop ``src`` spatially to the shape of ``like`` (Dirac tap)."""
    dh = src.shape[1] - like.shape[1]
    dw = src.shape[2] - like.shape[2]
    if dh == 0 and dw == 0:
        return src
    assert dh >= 0 and dw >= 0 and dh % 2 == 0 and dw % 2 == 0, (
        src.shape, like.shape)
    return src[:, dh // 2: src.shape[1] - dh // 2,
               dw // 2: src.shape[2] - dw // 2, :]


def segment_geometry(net: ConvNet, seg: Segment) -> tuple[int, int]:
    """(merged kernel size, merged stride) of a segment under its kept set."""
    K, S = 1, 1
    kept = set(seg.kept)
    for l in seg.layers:
        s = net.spec(l)
        if s.kind != "conv":
            continue
        k_eff = s.k if l in kept else 1
        K = K + (k_eff - 1) * S
        S *= s.stride
    return K, S


def _skip_stride(net: ConvNet, sk: SkipSpec) -> int:
    s = 1
    for l in range(sk.start + 1, sk.end + 1):
        if net.spec(l).kind in ("conv", "pool"):
            s *= net.spec(l).stride
        elif net.spec(l).kind == "upsample":
            s //= net.spec(l).stride
    return s


def _apply_proj(saved, skp, stride):
    return _conv(saved, skp["w"], stride, False, padding="SAME") + skp["b"]


def _segment_gn(net: ConvNet, layers, seg: Segment):
    """GN moved to segment end (paper Appendix A): the last kept conv's GN
    whose channel count matches the segment output; None otherwise."""
    kept = set(seg.kept)
    out_c = None
    for l in reversed(seg.layers):
        s = net.spec(l)
        if l in kept and s.kind == "conv":
            out_c = s.cout
            break
    if out_c is None:
        return None, 8
    for l in reversed(seg.layers):
        s = net.spec(l)
        if l in kept and s.kind == "conv" and "gn" in layers[l - 1] \
                and s.cout == out_c:
            return layers[l - 1]["gn"], s.gn_groups
        if l in kept and s.kind == "conv" and s.cout != out_c:
            break
    return None, 8


def timestep_embedding(t, dim: int):
    """Sinusoidal embedding of integer timesteps (DDPM's
    ``get_timestep_embedding``): ``[sin(t f), cos(t f)]``, ``f_k =
    10000^(-k / (dim/2 - 1))``."""
    half = dim // 2
    f = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                * -(math.log(10000.0) / (half - 1)))
    a = t.astype(jnp.float32)[:, None] * f[None, :]
    return jnp.concatenate([jnp.sin(a), jnp.cos(a)], axis=1)


def time_features(p, t, dim: int):
    """``SiLU(temb)`` of a time-conditioned net, ``(N, temb_ch)``: the
    embedding through Dense → SiLU → Dense, then the SiLU every
    per-image bias Dense reads."""
    e = timestep_embedding(t, dim) @ p["w0"] + p["b0"]
    return jax.nn.silu(jax.nn.silu(e) @ p["w1"] + p["b1"])


def _condition(net: ConvNet, params, inputs):
    """(images, {layer: per-image bias (N, C)}) of a net's inputs."""
    if not net.temb_ch:
        return inputs, {}
    x, t = inputs
    s = time_features(params["temb"], t, net.temb_dim)
    return x, {l: s @ p["temb"]["w"] + p["temb"]["b"]
               for l, p in enumerate(params["layers"], 1)
               if net.spec(l).temb}


def _pre_act(net: ConvNet, layers, l: int, x):
    """The nonlinearity kept at boundary ``l - 1`` of a pre-activation
    net: conv ``l``'s GroupNorm and activation of its input."""
    s = net.spec(l)
    if not net.preact or s.kind != "conv":
        return x
    if s.norm == "gn":
        x = _gn(x, layers[l - 1]["gn"], s.gn_groups, s.gn_eps)
    return _act(x, s.act)


def upsample_nearest(x, factor: int):
    """Nearest-neighbour ×``factor`` upsampling of an NHWC batch: each
    pixel repeated into a ``factor × factor`` block, as a broadcast and a
    reshape (``jax.image.resize``'s "nearest" computes the same values
    through a gather, which the TPU compiler runs as a loop)."""
    n, h, w, c = x.shape
    x = jnp.broadcast_to(x[:, :, None, :, None, :],
                         (n, h, factor, w, factor, c))
    return x.reshape(n, h * factor, w * factor, c)


def attn_block(x, p, groups: int, eps: float):
    """DDPM's self-attention block: ``x + proj(softmax(q kᵀ/√C) v)`` on
    ``GN(x)``, one head over the H·W positions, q/k/v/proj 1×1 with
    biases."""
    n, h, w, c = x.shape
    t = _gn(x, p["gn"], groups, eps).reshape(n, h * w, c)
    q = t @ p["wq"] + p["bq"]
    k = t @ p["wk"] + p["bk"]
    v = t @ p["wv"] + p["bv"]
    a = jax.nn.softmax(jnp.einsum("nqc,nkc->nqk", q, k) * c ** -0.5,
                       axis=-1)
    o = jnp.einsum("nqk,nkc->nqc", a, v) @ p["wo"] + p["bo"]
    return x + o.reshape(n, h, w, c)


# ---------------------------------------------------------------------------
# Replaced (pruned, unmerged) forward
# ---------------------------------------------------------------------------

def apply_replaced(net: ConvNet, params, x, plan: CompressionPlan | None = None):
    """Forward pass of the pruned-but-unmerged network under ``plan``;
    ``x`` is an NHWC batch, or ``(x, t)`` for a time-conditioned net."""
    if plan is None:
        plan = identity_plan(net.L, net.layer_descs())
    x, cond = _condition(net, params, x)
    layers = params["layers"]
    add_end = {sk.end: (sk.start, i) for i, sk in enumerate(net.skips)
               if sk.kind == "add"}
    cat_end = {sk.end: sk.start for i, sk in enumerate(net.skips)
               if sk.kind == "concat"}
    need_save = {sk.start for sk in net.skips}

    saved: dict[int, jax.Array] = {}     # true boundary values (post-act)
    if 0 in need_save:
        saved[0] = x

    for seg in plan.segments:
        x = _pre_act(net, layers, seg.i + 1, x)
        Km, _ = segment_geometry(net, seg)
        lo = (Km - 1) // 2
        hi = Km - 1 - lo
        if Km > 1:
            x = jnp.pad(x, ((0, 0), (lo, hi), (lo, hi), (0, 0)))
        local: dict[int, jax.Array] = {seg.i: x}   # halo'd in-segment values
        kept = set(seg.kept)
        gn, gn_groups = (None, 8) if net.preact else _segment_gn(net, layers,
                                                                 seg)
        for l in seg.layers:
            s = net.spec(l)
            p = layers[l - 1]
            if s.kind == "conv":
                if l in kept:
                    w, b = _folded_wb(s, p)
                    x = _conv(x, w, s.stride, s.depthwise) + b
                    if s.temb:
                        x = x + cond[l][:, None, None, :]
            elif s.kind == "pool":
                x = lax.reduce_window(
                    x, 0.0, lax.add, (1, s.k, s.k, 1),
                    (1, s.stride, s.stride, 1), "SAME") / (s.k * s.k)
            elif s.kind == "upsample":
                x = upsample_nearest(x, s.stride)
            elif s.kind == "attn":
                x = attn_block(x, p, s.gn_groups, s.gn_eps)
            if l in add_end:
                src, ski = add_end[l]
                sk = net.skips[ski]
                if sk.proj:
                    # proj blocks are never Dirac-fused: src is always a true
                    # segment boundary (allowed_span guarantees it)
                    base = _apply_proj(saved[src], params["skips"][ski],
                                       _skip_stride(net, sk))
                elif net.preact:
                    base = saved[src]      # never Dirac-fused (allowed_span)
                else:
                    base = local[src] if src >= seg.i else saved[src]
                x = x + _center_crop_to(base, x)
            if l in cat_end:
                x = jnp.concatenate([x, saved[cat_end[l]]], axis=-1)
            local[l] = x
        if gn is not None:
            x = _gn(x, gn, gn_groups)
        # boundary activation σ_j (σ_L is the identity, paper §2); a
        # pre-activation net applies it at the next segment's start
        if seg.j < net.L and not net.preact:
            bspec = net.spec(seg.j)
            act = bspec.act
            if (net.act_after_merge and not seg.original
                    and bspec.kind == "conv" and act == "none"):
                act = "relu6"
            x = _act(x, act)
        if seg.j in need_save:
            saved[seg.j] = x
    return _apply_head(net, params, x)


def _apply_head(net: ConvNet, params, x):
    if net.head == "classifier":
        x = x.mean(axis=(1, 2))
        return x @ params["head"]["w"] + params["head"]["b"]
    return x


# ---------------------------------------------------------------------------
# Merge (Algorithm 2 final step)
# ---------------------------------------------------------------------------
# A merged network is no longer applied here: ``CNNHost.lower_plan``
# lowers a plan into the shared unit IR (repro.runtime.ir) using
# :func:`merge_segment` below, and repro.runtime.executor runs it.

def merge_segment(net: ConvNet, layers_params, seg: Segment):
    """Fold one segment into a single conv: returns (w, b, stride, dw)."""
    kept = set(seg.kept)
    add_blocks = {} if net.preact else {sk.start: sk.end for sk in net.skips
                                        if sk.kind == "add" and not sk.proj}

    def compose(acc, w, b, stride, dw):
        if acc is None:
            return (w, b, stride, dw)
        w_a, b_a, s_a, dw_a = acc
        w_m, dw_m = M.merge_conv_pair(w_a, w, stride1=s_a, dw1=dw_a, dw2=dw)
        b_m = M.merge_bias_through(w, b_a, b, dw2=dw)
        return (w_m, b_m, s_a * stride, dw_m)

    def chain(lo: int, hi: int, as_branch: bool = False):
        acc = None
        l = lo + 1
        while l <= hi:
            blk_end = add_blocks.get(l - 1)
            # fuse a complete block inside (lo, hi]; when this call IS the
            # block's own branch ((lo,hi) == (start,end)), compose plainly
            if blk_end is not None and blk_end <= hi and l - 1 >= lo \
                    and not (as_branch and l - 1 == lo and blk_end == hi):
                wb, bb, sb, dwb = chain(l - 1, blk_end, as_branch=True)
                assert sb == 1, "Dirac fusion requires stride-1 block"
                wb = M.fuse_skip_add(wb, depthwise=dwb)
                acc = compose(acc, wb, bb, 1, dwb)
                l = blk_end + 1
                continue
            s = net.spec(l)
            assert s.kind == "conv", f"cannot merge unit kind {s.kind}"
            if l in kept:
                w, b = _folded_wb(s, layers_params[l - 1])
                acc = compose(acc, w, b, s.stride, s.depthwise)
            l += 1
        if acc is None:   # fully pruned segment — identity conv
            c = net.boundary_shapes()[lo][2]
            w0 = M.identity_kernel(c)
            return (w0, jnp.zeros((c,), w0.dtype), 1, True)
        return acc

    return chain(seg.i, seg.j)


def segment_time_bias(net: ConvNet, layers_params, seg: Segment):
    """The per-image time-bias Dense ``{w, b}`` at a segment's output, or
    None: each kept ``temb`` conv's Dense, carried through the kept convs
    after it by their tap sums (:func:`repro.core.merge.merge_bias_through`
    — exact, since the segment pads once in front and every conv inside
    it is VALID, so a bias stays spatially constant)."""
    cond = None
    kept = set(seg.kept)
    for l in seg.layers:
        s = net.spec(l)
        if s.kind != "conv" or l not in kept:
            continue
        p = layers_params[l - 1]
        if cond is not None:
            w, _ = _folded_wb(s, p)
            cond = {k: M.merge_bias_through(w, v, None, dw2=s.depthwise)
                    for k, v in cond.items()}
        if s.temb:
            cond = dict(p["temb"]) if cond is None else {
                k: v + p["temb"][k] for k, v in cond.items()}
    return cond
