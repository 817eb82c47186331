"""Plain float32 reference of a compressed DDPM UNet and its DDIM sampler,
independent of the program.

The configuration file (``bench/configs/ddpm_cifar10.json``) lists the
network as data: ``layers`` (a 1-based chain of ``conv``, ``attn`` and
``upsample`` units), ``skips`` (residual adds, with or without a 1×1
``nin_shortcut``, and the up path's concats), the GroupNorm settings, the
time embedding's widths and the diffusion schedule.  The network is the
DDPM CIFAR-10 UNet (Ho et al. 2020, arXiv 2006.11239, App. B;
``diffusion_tf/models/unet.py``), written as a pre-activation chain:

* a conv with ``norm: "gn"`` and ``act: "silu"`` reads ``SiLU(GN(x))``
  of its input; one with ``temb`` adds ``Dense(SiLU(temb))`` per image
  after it, where ``temb`` is the sinusoidal embedding of the timestep
  through Dense → SiLU → Dense;
* an ``attn`` unit is ``x + proj(softmax(q kᵀ/√C) v)`` on ``GN(x)``, one
  head over the H·W positions; an ``upsample`` repeats each pixel 2×2;
* a residual add adds the block's raw input (through its 1×1 projection
  where it has one); a concat appends a saved boundary value.

A plan (``<name>.plan.json``) splits the chain into segments ``(i, j]``
and names the convs kept in each.  Its *replaced* network (LayerMerge,
Kim et al., ICML 2024, §3 and App. A), as this module computes it: each
segment applies the nonlinearity kept at its start boundary (layer
``i+1``'s GroupNorm and activation) to its input, pads once by
``(K - 1) / 2`` on each side for the merged kernel size ``K`` of its kept
convs, and runs them ``VALID`` one after the other; a pruned conv is the
identity and takes its time bias with it; the GroupNorms and activations
inside a segment are dropped.  The sampler is DDIM with η = 0 over the
configuration's linear β schedule, at ``steps`` timesteps ``(steps − 1 −
k)·(T // steps)``, without clipping.

Every product runs at ``Precision.HIGHEST``; ``passes=3`` runs each in
three bfloat16 passes instead (:func:`bench.cnn_reference.conv` and
``matmul``): the control the correctness limit has to refuse.  Weights
come from :func:`init_params` and a seed, in the layout the program's CNN
host takes, so that both sides start from the same numbers and neither
takes anything from the other.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.cnn_reference import _three_pass, conv, geometry, layer, matmul

HIGHEST = lax.Precision.HIGHEST


def boundary_shapes(cfg) -> list[tuple[int, int, int]]:
    """(h, w, c) at every boundary 0..L, after any concat there."""
    h = w = cfg["in_hw"]
    c = cfg["in_ch"]
    cat = {sk["end"]: sk["start"] for sk in cfg["skips"]
           if sk["kind"] == "concat"}
    shapes = [(h, w, c)]
    for l in range(1, len(cfg["layers"]) + 1):
        s = layer(cfg, l)
        if s["kind"] == "conv":
            h, w, c = -(-h // s["stride"]), -(-w // s["stride"]), s["cout"]
        elif s["kind"] == "upsample":
            h, w = h * s["stride"], w * s["stride"]
        if l in cat:
            c += shapes[cat[l]][2]
        shapes.append((h, w, c))
    return shapes


# ---------------------------------------------------------------------------
# Weights from a seed
# ---------------------------------------------------------------------------

def init_params(cfg, key):
    """Seeded weights: variance scaling (fan-avg, uniform) for every conv,
    1×1 and dense layer, none of them zero (DDPM zero-initialises each
    ResBlock's last conv and ``conv_out``, which would make every block
    the identity); small random biases; GroupNorm affines near (1, 0).
    Carved from one normal and one uniform draw."""
    L = len(cfg["layers"])
    shapes = boundary_shapes(cfg)
    E, D = cfg["temb_ch"], cfg["temb_dim"]
    normal, uniform = [], []          # (path, shape, scale) to carve

    def vs(path, shape, fan_in, fan_out):
        uniform.append((path, shape, math.sqrt(6.0 / (fan_in + fan_out))))

    def dense(at, w, b, cin, cout):
        vs(at + (w,), (cin, cout), cin, cout)
        normal.append((at + (b,), (cout,), 0.05))

    def gn(path, c):
        normal.append((path + ("gamma",), (c,), 0.1))
        normal.append((path + ("beta",), (c,), 0.1))

    for l in range(1, L + 1):
        s = layer(cfg, l)
        c = shapes[l - 1][2]
        at = ("layers", l - 1)
        if s["kind"] == "conv":
            k = s["k"]
            vs(at + ("w",), (k, k, c, s["cout"]), k * k * c,
               k * k * s["cout"])
            normal.append((at + ("b",), (s["cout"],), 0.05))
            if s["norm"] == "gn":
                gn(at + ("gn",), c)
            if s.get("temb"):
                dense(at + ("temb",), "w", "b", E, s["cout"])
        elif s["kind"] == "attn":
            gn(at + ("gn",), c)
            for n in "qkvo":
                dense(at, f"w{n}", f"b{n}", c, c)
    for idx, sk in enumerate(cfg["skips"]):
        if sk.get("proj"):
            cin, cout = shapes[sk["start"]][2], layer(cfg, sk["end"])["cout"]
            vs(("skips", idx, "w"), (1, 1, cin, cout), cin, cout)
            normal.append((("skips", idx, "b"), (cout,), 0.05))
    dense(("temb",), "w0", "b0", D, E)
    dense(("temb",), "w1", "b1", E, E)

    params = {"layers": [{} for _ in range(L)],
              "skips": [{} for _ in cfg["skips"]], "head": {}, "temb": {}}
    kn, ku = jax.random.split(key)
    for items, flat in (
            (normal, jax.random.normal(
                kn, (sum(math.prod(sh) for _, sh, _ in normal),))),
            (uniform, jax.random.uniform(
                ku, (sum(math.prod(sh) for _, sh, _ in uniform),),
                minval=-1.0, maxval=1.0))):
        at = 0
        for path, shape, scale in items:
            n = math.prod(shape)
            node = params
            for k in path[:-1]:
                node = node.setdefault(k, {}) if isinstance(k, str) \
                    else node[k]
            node[path[-1]] = flat[at:at + n].reshape(shape) * scale
            at += n
    for p in params["layers"]:
        if "gn" in p:
            p["gn"]["gamma"] = p["gn"]["gamma"] + 1.0
    return params


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------

def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _act(x, name):
    if name == "silu":
        return _silu(x)
    if name == "none":
        return x
    raise ValueError(f"activation {name!r} has no reference")


def group_norm(x, p, groups: int, eps: float):
    n, h, w, c = x.shape
    xg = x.reshape(n, h * w, groups, c // groups)
    mu = jnp.mean(xg, axis=(1, 3), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 3), keepdims=True)
    y = ((xg - mu) / jnp.sqrt(var + eps)).reshape(n, h, w, c)
    return y * p["gamma"] + p["beta"]


def time_features(cfg, p, t, passes=None):
    """``SiLU(temb)``, (N, temb_ch), of integer timesteps ``t``."""
    half = cfg["temb_dim"] // 2
    f = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                * -(math.log(10000.0) / (half - 1)))
    a = t.astype(jnp.float32)[:, None] * f[None, :]
    e = jnp.concatenate([jnp.sin(a), jnp.cos(a)], axis=1)
    e = matmul(e, p["w0"], passes) + p["b0"]
    return _silu(matmul(_silu(e), p["w1"], passes) + p["b1"])


def attention(x, p, groups, eps, passes=None):
    n, h, w, c = x.shape
    t = group_norm(x, p["gn"], groups, eps).reshape(n * h * w, c)

    def proj(a, name):
        return (matmul(a, p[f"w{name}"], passes)
                + p[f"b{name}"]).reshape(n, h * w, c)
    q, k, v = proj(t, "q"), proj(t, "k"), proj(t, "v")
    s = _three_pass(lambda a, b: jnp.einsum("nqc,nkc->nqk", a, b,
                                            precision=HIGHEST),
                    q, k, passes) / math.sqrt(c)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    a = e / jnp.sum(e, axis=-1, keepdims=True)
    o = _three_pass(lambda a, b: jnp.einsum("nqk,nkc->nqc", a, b,
                                            precision=HIGHEST),
                    a, v, passes)
    return x + proj(o.reshape(n * h * w, c), "o").reshape(n, h, w, c)


# ---------------------------------------------------------------------------
# The replaced network and the sampler
# ---------------------------------------------------------------------------

def forward(cfg, params, x, t, plan, passes=None):
    """The noise estimate of the replaced network under ``plan`` (a parsed
    plan JSON) for images ``x`` at integer timesteps ``t``."""
    skips = cfg["skips"]
    groups, eps = cfg["gn_groups"], cfg["gn_eps"]
    add_end = {sk["end"]: idx for idx, sk in enumerate(skips)
               if sk["kind"] == "add"}
    cat_end = {sk["end"]: sk["start"] for sk in skips
               if sk["kind"] == "concat"}
    need_save = {sk["start"] for sk in skips}
    feats = time_features(cfg, params["temb"], t, passes)
    saved = {0: x} if 0 in need_save else {}
    for seg in plan["segments"]:
        if seg.get("quant", "none") != "none":
            raise ValueError("quantized segments have no reference here")
        first = layer(cfg, seg["i"] + 1)
        if first["kind"] == "conv":
            if first["norm"] == "gn":
                x = group_norm(x, params["layers"][seg["i"]]["gn"], groups,
                               eps)
            x = _act(x, first["act"])
        K, _ = geometry(cfg, seg)
        lo = (K - 1) // 2
        if K > 1:
            x = jnp.pad(x, ((0, 0), (lo, K - 1 - lo), (lo, K - 1 - lo),
                            (0, 0)))
        kept = set(seg["kept"])
        for l in range(seg["i"] + 1, seg["j"] + 1):
            s = layer(cfg, l)
            p = params["layers"][l - 1]
            if s["kind"] == "conv":
                if l in kept:
                    x = conv(x, p["w"], s["stride"], False,
                             passes=passes) + p["b"]
                    if s.get("temb"):
                        c = matmul(feats, p["temb"]["w"], passes) \
                            + p["temb"]["b"]
                        x = x + c[:, None, None, :]
            elif s["kind"] == "attn":
                x = attention(x, p, groups, eps, passes)
            elif s["kind"] == "upsample":
                x = jnp.repeat(jnp.repeat(x, s["stride"], axis=1),
                               s["stride"], axis=2)
            else:
                raise ValueError(f"layer kind {s['kind']!r} has no reference")
            if l in add_end:
                sk = skips[add_end[l]]
                base = saved[sk["start"]]
                if sk.get("proj"):
                    q = params["skips"][add_end[l]]
                    base = conv(base, q["w"], 1, False, passes=passes) \
                        + q["b"]
                x = x + base
            if l in cat_end:
                x = jnp.concatenate([x, saved[cat_end[l]]], axis=-1)
        if seg["j"] in need_save:
            saved[seg["j"]] = x
    return x


def schedule(cfg, steps: int):
    """(visited timesteps, √ᾱ_t, √(1−ᾱ_t), √ᾱ_prev, √(1−ᾱ_prev)) of a
    ``steps``-step DDIM run, computed in float64."""
    d = cfg["diffusion"]
    T = d["timesteps"]
    abar = np.cumprod(1.0 - np.linspace(d["beta_start"], d["beta_end"], T,
                                        dtype=np.float64))
    ts = [(steps - 1 - k) * (T // steps) for k in range(steps)]
    a = abar[ts]
    prev = np.array([abar[s] for s in ts[1:]] + [1.0])
    return (np.array(ts, np.int32), np.sqrt(a), np.sqrt(1.0 - a),
            np.sqrt(prev), np.sqrt(1.0 - prev))


def sample(cfg, params, x, plan, steps: int, passes=None):
    """DDIM (η = 0) from noise ``x`` over ``steps`` steps of the replaced
    network: ``x0 = (x − √(1−ᾱ_t) ε) / √ᾱ_t``, then ``x ← √ᾱ_prev x0 +
    √(1−ᾱ_prev) ε``, no clipping."""
    ts, a, s, a_prev, s_prev = (jnp.asarray(v, v.dtype if v.dtype ==
                                            np.int32 else jnp.float32)
                                for v in schedule(cfg, steps))

    def step(k, x):
        eps = forward(cfg, params, x, jnp.full((x.shape[0],), ts[k]), plan,
                      passes)
        x0 = (x - s[k] * eps) / a[k]
        return a_prev[k] * x0 + s_prev[k] * eps
    return lax.fori_loop(0, steps, step, x)
