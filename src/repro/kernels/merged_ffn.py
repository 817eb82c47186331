"""Pallas TPU kernel: fused LayerMerge rank-r residual layer.

Computes ``y = x + (x @ U) @ V`` — the merged segment produced by the
rank-merge (DESIGN §2.1) — in ONE kernel: the intermediate ``P = x@U``
(shape bm×r) never round-trips to HBM, and the residual add is fused into
the second GEMM's epilogue.  This is the transformer analogue of the
paper's merged convolution: one launch for the whole merged segment.

Tiling: grid (i over m-tiles, j over d_out-tiles, k over rank-tiles), k
innermost.  For each m-row-panel the P panel (bm × r, fp32) is computed
once during the j==0 sweep and cached in VMEM scratch across the remaining
j sweeps (TPU grid iteration is sequential per core; scratch persists).
MXU-aligned tiles (multiples of 128), fp32 accumulation.

VMEM budget per step (bm=bn=bk=256, bd=512, r≤2048, bf16 operands):
  x panel 256×d·2 (streamed by blocks of bd), U tile d×256·2 (blocked),
  V tile 256×256·2, P scratch 256×2048·4 = 2 MiB, acc 256×256·4 = 256 KiB
  → well under the 16 MiB v5e VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, u_ref, v_ref, *rest, bd: int, n_dblocks: int, bk: int,
            bn: int, quant: bool = False, aq: bool = False):
    # Quantized path (quant=True): U/V are narrow (int8/fp8) with
    # per-channel fp32 scale operands — u_scale over the rank axis
    # (applied to the P panel at its phase-1 write; constant over the D
    # contraction) and v_scale over the output-embed axis (applied in the
    # epilogue; constant over the rank contraction).  aq=True (w8a8)
    # additionally takes an int8 activation panel ``xq`` whose per-tensor
    # scale is pre-folded into u_scale at the ops layer — the fp ``x``
    # operand stays for the exact residual add.
    if quant:
        if aq:
            us_ref, vs_ref, xq_ref, o_ref, p_ref, acc_ref = rest
        else:
            us_ref, vs_ref, o_ref, p_ref, acc_ref = rest
            xq_ref = x_ref
    else:
        us_ref = vs_ref = None
        xq_ref, (o_ref, p_ref, acc_ref) = x_ref, rest
    j = pl.program_id(1)
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    # phase 1 (j == 0): build this m-panel's P[:, k-tile] = x @ U[:, k-tile]
    @pl.when(j == 0)
    def _():
        acc = jnp.zeros((x_ref.shape[0], bk), jnp.float32)
        for d in range(n_dblocks):
            xs = xq_ref[:, d * bd:(d + 1) * bd]
            us = u_ref[d * bd:(d + 1) * bd, :]
            acc = acc + jnp.dot(xs.astype(jnp.float32),
                                us.astype(jnp.float32),
                                preferred_element_type=jnp.float32)
        if us_ref is not None:
            acc = acc * us_ref[0].astype(jnp.float32)    # dequant P panel
        p_ref[:, pl.ds(k * bk, bk)] = acc

    # phase 2: acc += P[:, k-tile] @ V[k-tile, j-tile]
    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
    pk = p_ref[:, pl.ds(k * bk, bk)]
    acc_ref[...] += jnp.dot(pk, v_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    # epilogue (last k): fused residual add + downcast
    @pl.when(k == nk - 1)
    def _():
        acc = acc_ref[...]
        if vs_ref is not None:
            acc = acc * vs_ref[0].astype(jnp.float32)    # dequant epilogue
        xj = x_ref[:, pl.ds(j * bn, bn)]
        o_ref[...] = (acc + xj.astype(jnp.float32)).astype(o_ref.dtype)


def _block(n: int, pref: int) -> int:
    """The widest block ≤ ``pref`` that tiles ``n`` in 128-lane steps
    (a padded D of 640 takes 128-wide blocks, not 256/512)."""
    b = min(pref, n)
    while n % b and b > 128:
        b -= 128
    return b


def merged_ffn(x, u, v, *, bm: int = 256, bn: int = 256, bk: int = 256,
               bd: int = 512, u_scale=None, v_scale=None, xq=None,
               interpret: bool = False):
    """x: (M, D); u: (D, R); v: (R, D) → (M, D).

    Shapes must tile evenly (``ops.merged_ffn_op`` pads); D and R should be
    multiples of 128 for MXU alignment.

    Quantized factors: pass ``u``/``v`` narrow (int8/fp8) with
    ``u_scale`` (per-rank-column, shape ``(R,)``) and ``v_scale``
    (per-output-embed-column, shape ``(D,)``) fp32 scales; both applied
    after the fp32 accumulations.  w8a8 adds ``xq`` — the int8 activation
    panel (its per-tensor scale pre-folded into ``u_scale``); the fp
    ``x`` stays the exact residual.
    """
    m, d = x.shape
    r = u.shape[1]
    assert u.shape[0] == d and v.shape == (r, d), (x.shape, u.shape, v.shape)
    quant = u_scale is not None
    assert quant == (v_scale is not None), "pass both scales or neither"
    assert xq is None or (quant and xq.shape == x.shape)
    bm, bn, bk, bd = (_block(m, bm), _block(d, bn), _block(r, bk),
                      _block(d, bd))
    assert m % bm == 0 and d % bn == 0 and r % bk == 0 and d % bd == 0, (
        "shapes must tile evenly; pad at the ops.py layer")
    grid = (m // bm, d // bn, r // bk)

    in_specs = [
        pl.BlockSpec((bm, d), lambda i, j, k: (i, 0)),       # x row panel
        pl.BlockSpec((d, bk), lambda i, j, k: (0, k)),       # U col tile
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),      # V tile
    ]
    operands = [x, u, v]
    if quant:
        in_specs += [pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),
                     pl.BlockSpec((1, bn), lambda i, j, k: (0, j))]
        operands += [u_scale.reshape(1, r).astype(jnp.float32),
                     v_scale.reshape(1, d).astype(jnp.float32)]
        if xq is not None:
            in_specs.append(pl.BlockSpec((bm, d), lambda i, j, k: (i, 0)))
            operands.append(xq)

    kernel = functools.partial(_kernel, bd=bd, n_dblocks=d // bd, bk=bk,
                               bn=bn, quant=quant, aq=xq is not None)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, r), jnp.float32),     # P panel, persists over j
            pltpu.VMEM((bm, bn), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(*operands)
