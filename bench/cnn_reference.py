"""Plain float32 reference of a compressed CNN, independent of the program.

A configuration file (``bench/configs/<name>.json``) lists the network as
data: ``layers`` (1-based chain of conv / pool units), ``skips`` (residual
adds, with or without a 1×1 projection) and a classifier head.  A plan
(``<name>.plan.json``) splits the chain into segments ``(i, j]`` and names
the convolutions kept in each.  This module computes what the plan means,
the *replaced* network of the LayerMerge paper (Kim et al., ICML 2024, §3
and Appendix A), with nothing but ``jax.lax`` convolutions:

* each segment pads its input once, by ``(K - 1) / 2`` on each side, where
  ``K`` is the merged kernel size of its kept convolutions, and then runs
  the kept convolutions unpadded (``VALID``), one after the other;
* a pruned convolution is the identity; the activations inside a segment
  are dropped, the one at its end is kept (none after the last layer);
* a residual add whose block starts inside the segment adds the centre
  crop of the padded value; one that starts before it adds the saved
  boundary value, through its projection where it has one;
* with ``act_after_merge`` (MobileNetV2), a merged segment that ends
  without an activation gets ReLU6 (Appendix A).

Batch norm is frozen and folded into its convolution.  Every product runs
at ``Precision.HIGHEST``.  ``passes=3`` instead splits each operand into
two bfloat16 halves and drops the low×low product: the three-pass
bfloat16 arithmetic of ``Precision.HIGH``, written out so that it reads
the same on any backend.  That is the control the correctness limit has
to refuse.

Weights come from :func:`init_params` and a seed, in the layout the
program's CNN host takes, so that both sides start from the same numbers
and neither takes anything from the other.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5


# ---------------------------------------------------------------------------
# The network as data
# ---------------------------------------------------------------------------

def layer(cfg, l: int) -> dict:
    """Layer ``l`` (1-based) of the chain, with defaults filled in."""
    d = cfg["layers"][l - 1]
    return {"kind": "conv", "k": 3, "stride": 1, "depthwise": False,
            "act": "none", "norm": None, "bias": True, **d}


def boundary_shapes(cfg) -> list[tuple[int, int, int]]:
    """(h, w, c) at every boundary 0..L."""
    h = w = cfg["in_hw"]
    c = cfg["in_ch"]
    shapes = [(h, w, c)]
    for l in range(1, len(cfg["layers"]) + 1):
        s = layer(cfg, l)
        if s["kind"] in ("conv", "pool"):
            h, w = -(-h // s["stride"]), -(-w // s["stride"])
        if s["kind"] == "conv":
            c = s["cout"]
        shapes.append((h, w, c))
    return shapes


def geometry(cfg, seg) -> tuple[int, int]:
    """(merged kernel size, merged stride) of a segment's kept convs."""
    K, S = 1, 1
    kept = set(seg["kept"])
    for l in range(seg["i"] + 1, seg["j"] + 1):
        s = layer(cfg, l)
        if s["kind"] != "conv":
            continue
        K += ((s["k"] if l in kept else 1) - 1) * S
        S *= s["stride"]
    return K, S


def skip_stride(cfg, sk) -> int:
    s = 1
    for l in range(sk["start"] + 1, sk["end"] + 1):
        s *= layer(cfg, l)["stride"]
    return s


# ---------------------------------------------------------------------------
# Weights from a seed
# ---------------------------------------------------------------------------

def init_params(cfg, key):
    """Seeded weights: He-normal convs, random frozen batch-norm statistics
    and biases, so that folding and bias merging are exercised.  All of it
    is carved from one normal and one uniform draw, which keeps the
    program that makes it small to compile."""
    L = len(cfg["layers"])
    shapes = boundary_shapes(cfg)
    normal, uniform = [], []          # (path, shape, scale) to carve

    def draw(kind, path, shape, scale=1.0):
        (normal if kind == "n" else uniform).append((path, shape, scale))

    for l in range(1, L + 1):
        s = layer(cfg, l)
        if s["kind"] != "conv":
            continue
        cin = 1 if s["depthwise"] else shapes[l - 1][2]
        fan_in = s["k"] * s["k"] * cin
        draw("n", ("layers", l - 1, "w"), (s["k"], s["k"], cin, s["cout"]),
             math.sqrt(2.0 / fan_in))
        if s["bias"]:
            draw("n", ("layers", l - 1, "b"), (s["cout"],), 0.05)
        if s["norm"] == "bn":
            c = (s["cout"],)
            draw("u", ("layers", l - 1, "bn", "gamma"), c)
            draw("n", ("layers", l - 1, "bn", "beta"), c, 0.1)
            draw("n", ("layers", l - 1, "bn", "mean"), c, 0.1)
            draw("u", ("layers", l - 1, "bn", "var"), c)
        elif s["norm"] is not None:
            raise ValueError(f"norm {s['norm']!r} has no reference")
    for idx, sk in enumerate(cfg["skips"]):
        if sk.get("proj"):
            cin, cout = shapes[sk["start"]][2], shapes[sk["end"]][2]
            draw("n", ("skips", idx, "w"), (1, 1, cin, cout),
                 math.sqrt(2.0 / cin))
            draw("n", ("skips", idx, "b"), (cout,), 0.05)
    c = shapes[-1][2]
    draw("n", ("head", "w"), (c, cfg["num_classes"]), math.sqrt(1.0 / c))
    draw("n", ("head", "b"), (cfg["num_classes"],), 0.05)

    params = {"layers": [{} for _ in range(L)],
              "skips": [{} for _ in cfg["skips"]], "head": {}}
    kn, ku = jax.random.split(key)
    for items, flat in (
            (normal, jax.random.normal(
                kn, (sum(math.prod(sh) for _, sh, _ in normal),))),
            (uniform, jax.random.uniform(
                ku, (sum(math.prod(sh) for _, sh, _ in uniform),),
                minval=0.5, maxval=1.5))):
        at = 0
        for path, shape, scale in items:
            n = math.prod(shape)
            node = params
            for k in path[:-1]:
                node = node.setdefault(k, {}) if isinstance(k, str) \
                    else node[k]
            node[path[-1]] = flat[at:at + n].reshape(shape) * scale
            at += n
    return params


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------

def _halves(a):
    """``a`` as a bfloat16 high half plus a bfloat16 low half, kept in
    float32 (``reduce_precision`` is never elided as a round trip through
    bfloat16 may be)."""
    hi = lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return hi, lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)


def _three_pass(f, a, b, passes):
    if passes is None:
        return f(a, b)
    if passes != 3:
        raise ValueError(f"passes={passes!r}: only 3 is emulated")
    ah, al = _halves(a)
    bh, bl = _halves(b)
    return f(ah, bh) + (f(ah, bl) + f(al, bh))


def conv(x, w, stride, depthwise, padding="VALID", passes=None):
    """A dense conv through ``lax`` at ``Precision.HIGHEST``; a depthwise
    one as a sum of its taps, each a float32 elementwise product (XLA's
    grouped convolution on the TPU was seen to depart from float32 by
    several per cent over MobileNetV2, where this form does not)."""
    if depthwise:
        if padding != "VALID":
            raise ValueError("depthwise reference convs are VALID")
        return _three_pass(lambda a, b: _taps(a, b, stride), x, w, passes)

    def f(a, b):
        return lax.conv_general_dilated(
            a, b, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST)
    return _three_pass(f, x, w, passes)


def _taps(x, w, stride):
    k = w.shape[0]
    n, h, wd, c = x.shape
    ho, wo = (h - k) // stride + 1, (wd - k) // stride + 1
    out = None
    for u in range(k):
        for v in range(k):
            xs = lax.slice(x, (0, u, v, 0),
                           (n, u + (ho - 1) * stride + 1,
                            v + (wo - 1) * stride + 1, c),
                           (1, stride, stride, 1))
            out = xs * w[u, v, 0] if out is None else out + xs * w[u, v, 0]
    return out


def matmul(a, b, passes=None):
    return _three_pass(
        lambda p, q: jnp.dot(p, q, precision=lax.Precision.HIGHEST), a, b,
        passes)


def _act(x, name):
    if name == "relu":
        return jnp.maximum(x, 0.0)
    if name == "relu6":
        return jnp.clip(x, 0.0, 6.0)
    if name == "none":
        return x
    raise ValueError(f"activation {name!r} has no reference")


def _folded(s, p):
    w = p["w"]
    b = p.get("b", jnp.zeros((s["cout"],), w.dtype))
    if "bn" in p:
        bn = p["bn"]
        scale = bn["gamma"] / jnp.sqrt(bn["var"] + BN_EPS)
        w = w * scale
        b = bn["beta"] + (b - bn["mean"]) * scale
    return w, b


def _crop_to(src, like):
    dh = (src.shape[1] - like.shape[1]) // 2
    dw = (src.shape[2] - like.shape[2]) // 2
    return src[:, dh:dh + like.shape[1], dw:dw + like.shape[2], :]


def forward(cfg, params, x, plan, passes=None):
    """Logits of the replaced network under ``plan`` (a parsed plan JSON)."""
    L = len(cfg["layers"])
    skips = cfg["skips"]
    add_end = {sk["end"]: idx for idx, sk in enumerate(skips)}
    need_save = {sk["start"] for sk in skips}
    saved = {0: x} if 0 in need_save else {}
    for seg in plan["segments"]:
        if seg.get("quant", "none") != "none":
            raise ValueError("quantized segments have no reference here")
        K, _ = geometry(cfg, seg)
        lo = (K - 1) // 2
        if K > 1:
            x = jnp.pad(x, ((0, 0), (lo, K - 1 - lo), (lo, K - 1 - lo),
                            (0, 0)))
        local = {seg["i"]: x}
        kept = set(seg["kept"])
        for l in range(seg["i"] + 1, seg["j"] + 1):
            s = layer(cfg, l)
            if s["kind"] == "conv":
                if l in kept:
                    w, b = _folded(s, params["layers"][l - 1])
                    x = conv(x, w, s["stride"], s["depthwise"],
                             passes=passes) + b
            elif s["kind"] == "pool":
                x = lax.reduce_window(
                    x, 0.0, lax.add, (1, s["k"], s["k"], 1),
                    (1, s["stride"], s["stride"], 1), "SAME") / s["k"] ** 2
            else:
                raise ValueError(f"layer kind {s['kind']!r} has no reference")
            if l in add_end:
                sk = skips[add_end[l]]
                src = sk["start"]
                if sk.get("proj"):
                    p = params["skips"][add_end[l]]
                    base = conv(saved[src], p["w"], skip_stride(cfg, sk),
                                False, padding="SAME", passes=passes) + p["b"]
                else:
                    base = local[src] if src >= seg["i"] else saved[src]
                x = x + _crop_to(base, x)
            local[l] = x
        if seg["j"] < L:
            s = layer(cfg, seg["j"])
            act = s["act"]
            if (cfg.get("act_after_merge") and not seg.get("original")
                    and s["kind"] == "conv" and act == "none"):
                act = "relu6"
            x = _act(x, act)
        if seg["j"] in need_save:
            saved[seg["j"]] = x
    x = x.mean(axis=(1, 2))
    return matmul(x, params["head"]["w"], passes) + params["head"]["b"]
