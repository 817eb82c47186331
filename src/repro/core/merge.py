"""Layer merging — the paper's ``θ_j * … * θ_i`` composition, in JAX.

Conventions: conv weights are ``(kh, kw, cin, cout)`` (HWIO) and act by
*cross-correlation* (``jax.lax.conv_general_dilated`` default) with VALID
padding inside a merged group; depthwise convs are ``(kh, kw, 1, c)`` with
``feature_group_count = c``.

Facts implemented here (each certified by an allclose test against the
composed original in ``tests/test_merge.py``):

* ``merge_conv_pair``   — Eq. 1: composing two stride-``s`` correlations is a
  single correlation with kernel ``(k2−1)·s1 + k1`` and stride ``s1·s2``; the
  merged weight is the *convolution* (flipped correlation) of the kernels
  with the middle channel contracted, with ``rhs_dilation = s1``.
* ``identity_kernel``   — the paper's ``θ_id``: 1×1 depthwise ones.
* ``fuse_skip_add``     — RepVGG-style: ``x + conv(x)`` == a single conv whose
  kernel has a centred Dirac added (valid when shapes are preserved).
* ``fold_batchnorm``    — inference-time BN folding.
* ``merge_linear_residual_pair`` — the transformer rank-merge (DESIGN §2.1):
  ``(I + V2U2)(I + V1U1) = I + [V1 V2]·[U1 ; U2(I + V1U1)]`` — an exact
  factored merge whose rank grows additively, the analogue of Eq. 1.
* ``truncate_rank``     — optional SVD truncation of a merged (U, V) at
  ``d_model`` (or any smaller rank), used when the additive rank saturates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


# ---------------------------------------------------------------------------
# Convolution composition (Eq. 1)
# ---------------------------------------------------------------------------

def identity_kernel(c: int, dtype=jnp.float32) -> jax.Array:
    """θ_id — 1×1 depthwise conv of ones ((1, 1, 1, c) HWIO grouped)."""
    return jnp.ones((1, 1, 1, c), dtype=dtype)


def _dw_to_full(w: jax.Array) -> jax.Array:
    """Expand a depthwise kernel (kh, kw, 1, c) to a full (kh, kw, c, c)."""
    kh, kw, _, c = w.shape
    eye = jnp.eye(c, dtype=w.dtype)                       # (c, c)
    return w[:, :, 0, :][:, :, None, :] * eye[None, None]  # (kh, kw, c, c)


def merge_conv_pair(w1: jax.Array, w2: jax.Array, *, stride1: int = 1,
                    dw1: bool = False, dw2: bool = False
                    ) -> tuple[jax.Array, bool]:
    """Merged kernel for ``conv2 ∘ conv1`` (correlation, VALID, HWIO).

    Returns ``(w_merged, merged_is_depthwise)``.  Merged kernel size is
    ``(k2 − 1)·stride1 + k1`` per spatial dim (paper Appendix A).  Only the
    depthwise∘depthwise composition stays depthwise.
    """
    both_dw = dw1 and dw2
    if dw1 and not both_dw:
        w1 = _dw_to_full(w1)
        dw1 = False
    if dw2 and not both_dw:
        w2 = _dw_to_full(w2)
        dw2 = False

    if both_dw:
        # per-channel 1-D composition over each spatial dim: correlate the
        # flipped second kernel over the (padded, dilated) first.
        c = w1.shape[-1]
        k1h, k1w = w1.shape[0], w1.shape[1]
        k2h, k2w = w2.shape[0], w2.shape[1]
        mh = (k2h - 1) * stride1 + k1h
        mw = (k2w - 1) * stride1 + k1w
        out = jnp.zeros((mh, mw, 1, c), w1.dtype)
        for u in range(k2h):
            for v in range(k2w):
                out = out.at[u * stride1:u * stride1 + k1h,
                             v * stride1:v * stride1 + k1w].add(
                    w1 * w2[u, v, 0, :][None, None, None, :])
        return out, True

    # General case.  Derivation (1-D, stride1=s):
    #   y1[m, p] = Σ_{c,u} x[c, s·p + u] · w1[u, c, m]
    #   y2[o, q] = Σ_{m,v} y1[m, q·s2 + v] · w2[v, m, o]
    #            = Σ_{c,s'} x[c, (s·s2)·q + s'] · wm[s', c, o],
    #   wm[s', c, o] = Σ_m Σ_{v·s + u = s'} w2[v, m, o] · w1[u, c, m].
    # I.e. a *convolution* of the kernels over space (contract m), with w2
    # spatially dilated by s.  Implemented as a correlation of w1 (as the
    # "image", batch = cin, features = mid) with the flipped w2.
    k1h, k1w, cin, mid = w1.shape
    k2h, k2w, mid2, cout = w2.shape
    assert mid == mid2, (w1.shape, w2.shape)
    lhs = jnp.transpose(w1, (2, 3, 0, 1))            # (cin, mid, k1h, k1w)
    rhs = jnp.flip(w2, axis=(0, 1))                  # flip spatial
    rhs = jnp.transpose(rhs, (3, 2, 0, 1))           # (cout, mid, k2h, k2w)
    pad_h = (k2h - 1) * stride1
    pad_w = (k2w - 1) * stride1
    out = lax.conv_general_dilated(
        lhs, rhs,
        window_strides=(1, 1),
        padding=((pad_h, pad_h), (pad_w, pad_w)),
        rhs_dilation=(stride1, stride1),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        # an offline weight fold: exact fp32 on every backend (the TPU's
        # default would round the operands to bf16)
        precision=lax.Precision.HIGHEST,
    )                                                 # (cin, cout, mh, mw)
    return jnp.transpose(out, (2, 3, 0, 1)), False


def merge_conv_chain(weights, strides, depthwise_flags):
    """Fold a whole chain ``f_n ∘ … ∘ f_1`` into one kernel.

    Args:
      weights: list of HWIO kernels (depthwise ones as (kh, kw, 1, c)).
      strides: per-layer input strides.
      depthwise_flags: per-layer bool.

    Returns ``(w_merged, total_stride, merged_is_depthwise)``.
    """
    w, dw = weights[0], depthwise_flags[0]
    s_acc = strides[0]
    for wn, sn, dn in zip(weights[1:], strides[1:], depthwise_flags[1:]):
        w, dw = merge_conv_pair(w, wn, stride1=s_acc, dw1=dw, dw2=dn)
        s_acc *= sn
    return w, s_acc, dw


def merge_bias_through(w2: jax.Array, b1: jax.Array, b2: jax.Array | None,
                       dw2: bool = False) -> jax.Array:
    """Bias of ``conv2 ∘ (conv1 + b1)``: ``b2 + S2ᵀ b1``, where ``S2 =
    Σ_spatial w2`` is conv2's tap sum.

    ``b1`` may carry leading axes: a per-image bias ``c`` of shape
    ``(N, C)`` (DDPM's time bias), or the weight ``(D, C)`` of the Dense
    that makes it.  The fold is exact wherever conv2 sees the bias as a
    spatially constant field, which holds in a merged segment: it pads
    once in front and runs every conv VALID, so
    ``conv2(conv1(x) + b1 + c[n]) + b2 = merged(x) + b2 + S2ᵀ(b1 + c[n])``.
    """
    if dw2:
        contrib = jnp.sum(w2, axis=(0, 1))[0] * b1      # (..., c)
    else:
        contrib = jnp.einsum("hwio,...i->...o", w2, b1,
                             precision=lax.Precision.HIGHEST)
    return contrib if b2 is None else b2 + contrib


def fuse_skip_add(w: jax.Array, depthwise: bool = False) -> jax.Array:
    """Fold ``x + conv(x)`` into one conv by adding a centred Dirac kernel.

    Requires odd kernel, stride 1, cin == cout (shape preserving) — exactly
    the condition under which the paper merges across a skip-addition.
    """
    kh, kw = w.shape[0], w.shape[1]
    assert kh % 2 == 1 and kw % 2 == 1, "Dirac fusion needs odd kernels"
    if depthwise:
        return w.at[kh // 2, kw // 2, 0, :].add(1.0)
    cin, cout = w.shape[2], w.shape[3]
    assert cin == cout, "skip-add fusion needs cin == cout"
    return w.at[kh // 2, kw // 2].add(jnp.eye(cin, dtype=w.dtype))


def fold_batchnorm(w: jax.Array, b: jax.Array | None, gamma, beta, mean, var,
                   eps: float = 1e-5) -> tuple[jax.Array, jax.Array]:
    """Inference-time BN folding: ``BN(conv(x))`` → one conv."""
    scale = gamma / jnp.sqrt(var + eps)                # (cout,)
    w_f = w * scale[None, None, None, :]
    b0 = jnp.zeros_like(mean) if b is None else b
    return w_f, beta + (b0 - mean) * scale


# ---------------------------------------------------------------------------
# Transformer rank-merge (DESIGN §2.1) — the TPU analogue of Eq. 1
# ---------------------------------------------------------------------------

def merge_linear_residual_pair(u1: jax.Array, v1: jax.Array,
                               u2: jax.Array, v2: jax.Array
                               ) -> tuple[jax.Array, jax.Array]:
    """Exact factored merge of ``(I + U2·V2) ∘ (I + U1·V1)``.

    Shapes: ``u: (d, r)``, ``v: (r, d)`` with the block acting as
    ``x → x + (x @ u) @ v`` on row vectors.  The merged rank is ``r1 + r2``
    (the Eq. 1 analogue) and the merge is exact — no SVD needed:

      ``x(I + U1V1)(I + U2V2) = x(I + [U1 | (I + U1V1)U2] · [V1 ; V2])``.
    """
    d = u1.shape[0]
    assert v1.shape[1] == d and u2.shape[0] == d and v2.shape[1] == d
    u2_eff = u2 + u1 @ (v1 @ u2)          # (d, r2): (I + U1V1)·U2
    u_m = jnp.concatenate([u1, u2_eff], axis=1)
    v_m = jnp.concatenate([v1, v2], axis=0)
    return u_m, v_m


def merge_linear_residual_chain(factors) -> tuple[jax.Array, jax.Array]:
    """Fold ``(I + U_nV_n)∘…∘(I + U_1V_1)`` into one ``(U, V)`` pair."""
    u, v = factors[0]
    for un, vn in factors[1:]:
        u, v = merge_linear_residual_pair(u, v, un, vn)
    return u, v


def truncate_rank(u: jax.Array, v: jax.Array, max_rank: int
                  ) -> tuple[jax.Array, jax.Array]:
    """SVD-truncate a factored residual map at ``max_rank``.

    When the additive rank exceeds ``d_model`` the factored form is wasteful;
    the paper's kernel-size cap has no analogue, but on TPU we cap at the
    numerical rank ``d`` (beyond-paper optimization, see EXPERIMENTS §Perf).
    """
    r = u.shape[1]
    if r <= max_rank:
        return u, v
    m = u @ v                                          # (d, d) exact product
    uu, ss, vv = jnp.linalg.svd(m, full_matrices=False)
    k = max_rank
    return uu[:, :k] * ss[:k][None, :], vv[:k, :]


def dense_residual(u: jax.Array, v: jax.Array) -> jax.Array:
    """Materialize ``I + U·V`` (used when rank ≥ d: one GEMM beats two)."""
    d = u.shape[0]
    return jnp.eye(d, dtype=u.dtype) + u @ v
