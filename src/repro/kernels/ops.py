"""jit'd public wrappers for the Pallas kernels.

Each ``*_op``:
* pads inputs to tile boundaries, calls the kernel, slices back;
* dispatches to the Pallas path on TPU and to the jnp oracle elsewhere
  (``pl.pallas_call`` does not lower on the CPU backend; interpret=True is
  for tests only — far too slow inside real models);
* is differentiable: ``flash_attention_op`` uses ``jax.custom_vjp`` with the
  Pallas forward and the reference backward (recompute-style, consistent
  with the training remat policy); the other ops are linear/elementwise and
  get transparent AD via the oracle path off-TPU.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from . import quant, ref
from .depthwise_conv import choose_group_block, depthwise_conv
from .flash_attention import flash_attention
from .merged_conv import LANE, merged_conv, plan_tiles, round_up
from .merged_ffn import merged_ffn
from .rglru_scan import rglru_scan
from .rmsnorm import rmsnorm

_FORCE = {"mode": None}       # tests can force 'pallas' | 'ref'


def _use_pallas() -> bool:
    if _FORCE["mode"] == "pallas":
        return True
    if _FORCE["mode"] == "ref":
        return False
    return jax.default_backend() == "tpu"


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


# ---------------------------------------------------------------------------

def merged_ffn_op(x, u, v, *, u_scale=None, v_scale=None,
                  act_quant: str = "none", interpret: bool = False):
    """(..., D) rank-r residual; pads tokens/rank/features to 128.

    Quantized factors: ``u_scale`` (per-rank-column) + ``v_scale``
    (per-output-column) mark ``u``/``v`` as narrow (int8/fp8);
    ``act_quant="w8a8"`` additionally quantizes the activation panel
    per-tensor at the call site (its scale folds into ``u_scale`` —
    the kernel sees ONE scale pair; the residual stays exact fp).
    """
    if not (_use_pallas() or interpret):
        if u_scale is not None:
            return ref.merged_ffn_qref(x, u, v, u_scale, v_scale,
                                       act_quant=act_quant)
        return ref.merged_ffn_ref(x, u, v)
    shape = x.shape
    d = shape[-1]
    n = x.size // d
    x2 = x.reshape(n, d)
    x2, _ = _pad_to(x2, 0, 128)       # token rows
    x2, pd = _pad_to(x2, 1, 128)      # feature dim
    u_p, pr = _pad_to(u, 1, 128)      # rank
    v_p, _ = _pad_to(v, 0, 128)
    if pd:
        u_p = jnp.pad(u_p, ((0, pd), (0, 0)))
        v_p = jnp.pad(v_p, ((0, 0), (0, pd)))
    bm = 256 if x2.shape[0] % 256 == 0 else 128
    us = vs = xq = None
    if u_scale is not None:
        us = jnp.pad(u_scale.astype(jnp.float32), (0, pr))
        vs = jnp.pad(v_scale.astype(jnp.float32), (0, pd))
        if act_quant == "w8a8":
            xq, x_scale = quant.quantize_int8(x2)
            us = us * x_scale
    y = merged_ffn(x2, u_p, v_p, bm=bm, u_scale=us, v_scale=vs, xq=xq,
                   interpret=interpret)
    return y[:n, :d].reshape(shape)


def rmsnorm_op(x, g, *, eps: float = 1e-6, interpret: bool = False):
    if not (_use_pallas() or interpret):
        return ref.rmsnorm_ref(x, g, eps)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    x2, pm = _pad_to(x2, 0, 128)
    bm = 128 if shape[-1] >= 8192 else 256
    bm = min(bm, x2.shape[0])
    y = rmsnorm(x2, g, eps=eps, bm=bm, interpret=interpret)
    if pm:
        y = y[:-pm]
    return y.reshape(shape)


def channel_tile(cout: int, requested: int | None) -> int:
    """Lane-friendly output-channel tile: always a multiple of 8.

    The old divisor walk (``while cout_p % bc: bc -= 1``) could degrade to
    lane-hostile tiles like ``bc=1`` on odd channel counts; instead the
    channel axis is padded *up* to a multiple of the chosen tile (ideally
    the full 128-lane width), never searched down.  Explicit requests are
    rounded to [8, 128] — one lane width is the widest useful block.
    """
    if requested is not None:
        return max(8, min(-(-requested // 8) * 8, 128))
    if cout >= 128:
        return 128
    return -(-max(cout, 8) // 8) * 8


#: Trace-time count of the convs :func:`merged_conv_op` ran folded, by
#: ``(kh, kw, Cin, stride)``: one per folded unit per trace.
FOLDED: collections.Counter = collections.Counter()


def fold_taps(x_shape, w_shape, stride: int) -> bool:
    """Whether a dense conv runs as one ``kh·kw·Cin`` contraction.

    The tap path pads Cin to whole 128-lane tiles and contracts them at
    each of the ``kh·kw`` taps, so a narrow input mostly multiplies zero
    lanes.  Folded, the patch is gathered once in HBM and contracted in
    ``K = round_up(kh·kw·Cin, 128)`` lanes by a 1×1 stride-1 conv.  Fold
    when there is more than one tap, the MXU then contracts fewer lanes,
    and the patch is no larger than the lane-padded image the tap path
    writes.  ``x_shape`` is the spatially padded NHWC input, ``w_shape``
    the HWIO weight.
    """
    _, h, w, cin = x_shape
    kh, kw = w_shape[:2]
    s = max(stride, 1)
    taps = kh * kw
    k = round_up(taps * cin, LANE)
    cin_p = round_up(cin, LANE)
    ho, wo = (h - kh) // s + 1, (w - kw) // s + 1
    return taps > 1 and k < taps * cin_p and k * ho * wo <= cin_p * h * w


def _lane_sum(pieces, width: int):
    """``pieces`` concatenated on the channel axis and zero-padded to
    ``width`` lanes, written as a sum of zero-padded pieces: XLA fuses
    that, where for a concatenate it would lay each piece out on its own
    with its few channels on the lanes."""
    out, at = None, 0
    for p in pieces:
        c = p.shape[-1]
        p = jnp.pad(p, ((0, 0),) * (p.ndim - 1) + ((at, width - at - c),))
        out = p if out is None else out + p
        at += c
    return out


def _taps(x, k: int, s: int, n_out: int, axis: int):
    """The ``k`` stride-``s`` windows of ``x`` along ``axis``, ``n_out``
    long each, as contiguous slices of its phase-major reshape."""
    n_in = s * (n_out - 1) + k
    idx = (slice(None),) * axis
    x = x[idx + (slice(0, n_in),)]
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, round_up(n_in, s) - x.shape[axis])
    x = jnp.pad(x, pad)
    x = x.reshape(x.shape[:axis] + (-1, s) + x.shape[axis + 1:])
    return [x[idx + (slice(u // s, u // s + n_out), u % s)]
            for u in range(k)]


def _tap_patches(x, kh: int, kw: int, stride: int, ho: int, wo: int):
    """``x_col[n, t, r, (u·kw + v)·Cin + c] = x[n, s·t + u, s·r + v, c]``
    for ``t < ho``, ``r < wo`` (rows and columns past the image read
    zeros), channels zero-padded to whole lanes: the input of a folded
    conv, in the row order of ``w.reshape(kh·kw·Cin, Cout)`` for HWIO
    weights.  Built as the ``kw`` column taps (``kw·Cin`` channels),
    then the ``kh`` row taps of those."""
    cin = x.shape[3]
    s = max(stride, 1)
    xw = _lane_sum(_taps(x, kw, s, wo, 2), kw * cin)
    return _lane_sum(_taps(xw, kh, s, ho, 1), round_up(kh * kw * cin, LANE))


def _conv_patches(x, kh: int, kw: int, stride: int, ho: int, wo: int):
    """:func:`_tap_patches` as one convolution with a 0/1 kernel.  Each
    output is one input times 1.0 plus zeros, so at ``HIGHEST`` precision
    the patch is exact.  XLA runs it on the MXU, which writes the patch
    with its channels on the lanes in one pass; at small batches its
    convolution is slower than the taps' sum."""
    cin = x.shape[3]
    s = max(stride, 1)
    hn, wn = s * (ho - 1) + kh, s * (wo - 1) + kw
    x = x[:, :hn, :wn]
    x = jnp.pad(x, ((0, 0), (0, hn - x.shape[1]), (0, wn - x.shape[2]),
                    (0, 0)))
    k = round_up(kh * kw * cin, LANE)
    eye = jnp.eye(kh * kw * cin, k, dtype=x.dtype).reshape(kh, kw, cin, k)
    return jax.lax.conv_general_dilated(
        x, eye, (s, s), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def fold_batch_major(x_shape, dtype) -> bool:
    """Whether a folded conv builds its patch batch-major
    (:func:`_conv_patches`) rather than as a sum of taps
    (:func:`_tap_patches`): for float activations at a batch of 32 or
    more.  On a v5e the batch-major forwards of ResNet-34 and MobileNetV2
    are the faster at every batch from 32 to 256; at batch 8 ResNet-34's
    is 30% slower."""
    return x_shape[0] >= 32 and jnp.issubdtype(dtype, jnp.floating)


def _folded_image(x, kh: int, kw: int, stride: int, bcout: int,
                  tile_ho: int | None, tile_wo: int | None):
    """The image a folded conv's kernel runs on, built at whole tiles so
    that the kernel pads nothing: ``(image, tile_ho, tile_wo,
    batch_major)``.

    Batch-major (:func:`fold_batch_major`), XLA's convolution
    (:func:`_conv_patches`) lays the patch out as ``(Ho, Wo, N, K)``,
    and a 1×1 conv does not care which axes are spatial, so that is the
    kernel's image and the patch is written once, in place; the tiles
    are then of ``(Wo, N)``.  Otherwise the taps are gathered
    (:func:`_tap_patches`) into ``(N, Ho, Wo, K)``.
    """
    n, h, w, cin = x.shape
    ho, wo = (h - kh) // stride + 1, (w - kw) // stride + 1
    k = round_up(kh * kw * cin, LANE)
    if fold_batch_major(x.shape, x.dtype):
        tile_ho, tile_wo = plan_tiles(wo, n, k, 1, 1, 1, x.dtype.itemsize,
                                      bcout, tile_ho, tile_wo)
        x = _conv_patches(x, kh, kw, stride, ho, round_up(wo, tile_ho))
        return x.transpose(1, 2, 0, 3), tile_ho, tile_wo, True
    tile_ho, tile_wo = plan_tiles(ho, wo, k, 1, 1, 1, x.dtype.itemsize, bcout,
                                  tile_ho, tile_wo)
    x = _tap_patches(x, kh, kw, stride, round_up(ho, tile_ho),
                     round_up(wo, tile_wo))
    return x, tile_ho, tile_wo, False


def merged_conv_op(x, w, b=None, *, stride: int = 1,
                   activation: str | None = None,
                   tile_ho: int | None = None, tile_wo: int | None = None,
                   bcout: int | None = None, w_scale=None,
                   act_quant: str = "none", interpret: bool = False):
    """Merged-segment conv (VALID, stride ``s``) with fused bias + boundary
    activation.

    ``tile_ho``/``tile_wo`` (output tile) and ``bcout`` (output-channel
    tile) default to the kernel's 2-D VMEM planner; pass explicit values to
    sweep.  Strided segments run through the Pallas kernel too — no
    jnp-oracle fallback on TPU.  A narrow input (:func:`fold_taps`) runs
    folded: its patches through the same kernel as a 1×1 stride-1 conv
    (:func:`_folded_image`, whose view the tiles then refer to), counted
    in :data:`FOLDED`.

    Quantized weights: ``w_scale`` (per-output-channel, ``(Cout,)``)
    marks ``w`` as narrow (int8/fp8); ``act_quant="w8a8"`` quantizes the
    activation per-tensor here, folding its scale into ``w_scale`` so
    the kernel applies ONE scale in the fp32 epilogue.
    """
    if not (_use_pallas() or interpret):
        with jax.named_scope("kernel"):
            if w_scale is not None:
                y = ref.merged_conv_qref(x, w, b, w_scale, stride=stride,
                                         act_quant=act_quant)
            else:
                y = ref.merged_conv_ref(x, w, b, stride=stride)
            return ref.apply_activation(y, activation)
    kh, kw, cin, cout = w.shape
    fold = fold_taps(x.shape, w.shape, stride)
    bc = channel_tile(cout, bcout)
    ws = out_dtype = None
    with jax.named_scope("weight_prep"):
        if fold:
            w = w.reshape(1, 1, kh * kw * cin, cout)
        w_p, pc = _pad_to(w, 3, bc)
        b_p = None if b is None else jnp.pad(b, (0, pc))
        if w_scale is not None:
            ws = jnp.pad(w_scale.astype(jnp.float32), (0, pc))
            out_dtype = x.dtype
    # the per-tensor activation scale is taken before any padding or
    # gather, which leave its maximum as it is
    if act_quant == "w8a8" and ws is not None:
        with jax.named_scope("epilogue"):
            x, x_scale = quant.quantize_int8(x)
            ws = ws * x_scale
    ho, wo = (x.shape[1] - kh) // stride + 1, (x.shape[2] - kw) // stride + 1
    batch_major = False
    if fold:
        FOLDED[(kh, kw, cin, stride)] += 1
        with jax.named_scope("relayout"), jax.named_scope("fold_taps"):
            x, tile_ho, tile_wo, batch_major = _folded_image(
                x, kh, kw, stride, bc, tile_ho, tile_wo)
        stride = 1
    # Cin rides the lane axis of the kernel's DMA windows, which Mosaic
    # slices only at whole 128-lane tiles: zero input channels against
    # zero weight rows leave every output exact.
    with jax.named_scope("lane_pad"):
        x, _ = _pad_to(x, 3, LANE)
    with jax.named_scope("weight_prep"):
        w_p, _ = _pad_to(w_p, 2, LANE)
    y = merged_conv(x, w_p, b_p, stride=stride, bcout=bc, tile_ho=tile_ho,
                    tile_wo=tile_wo, activation=activation, w_scale=ws,
                    out_dtype=out_dtype, interpret=interpret)
    if batch_major:
        with jax.named_scope("relayout"):
            y = y.transpose(2, 0, 1, 3)
    if y.shape[1:] != (ho, wo, cout):
        with jax.named_scope("crop"):
            y = y[:, :ho, :wo, :cout]
    return y


def depthwise_conv_op(x, w, b=None, *, stride: int = 1,
                      groups: int | None = None,
                      activation: str | None = None,
                      tile_ho: int | None = None, tile_wo: int | None = None,
                      bgroups: int | None = None, w_scale=None,
                      act_quant: str = "none", interpret: bool = False):
    """Grouped/depthwise merged-segment conv (VALID, stride ``s``) with
    fused bias + boundary activation.

    ``groups`` is the ``feature_group_count``; it defaults to the
    depthwise reading ``Cin // Cin_g`` from the HWIO weight shape
    (``Cin_g = w.shape[2]``), so plain depthwise calls pass just
    ``(x, w, b, stride=s)``.  ``bgroups`` (groups per grid step) defaults
    to ``choose_group_block`` — a lane-friendly channel tile for
    depthwise shapes, one group per step for ``Cin_g > 1``.  The group
    axis is padded up inside the kernel wrapper; no fallback to lax on
    the TPU path.  ``w_scale``/``act_quant``: quantized path, same
    contract as :func:`merged_conv_op`.
    """
    if groups is None:
        groups = x.shape[-1] // w.shape[2]
    if not (_use_pallas() or interpret):
        with jax.named_scope("kernel"):
            if w_scale is not None:
                y = ref.depthwise_conv_qref(x, w, b, w_scale, stride=stride,
                                            groups=groups,
                                            act_quant=act_quant)
            else:
                y = ref.depthwise_conv_ref(x, w, b, stride=stride,
                                           groups=groups)
            return ref.apply_activation(y, activation)
    cin_g = w.shape[2]
    cout_g = w.shape[3] // groups
    bg = choose_group_block(groups, cin_g, cout_g, bgroups)
    ws = out_dtype = None
    if w_scale is not None:
        with jax.named_scope("weight_prep"):
            ws = w_scale.astype(jnp.float32)
        out_dtype = x.dtype
        if act_quant == "w8a8":
            with jax.named_scope("epilogue"):
                x, x_scale = quant.quantize_int8(x)
                ws = ws * x_scale
    return depthwise_conv(x, w, b, stride=stride, groups=groups, bgroups=bg,
                          tile_ho=tile_ho, tile_wo=tile_wo,
                          activation=activation, w_scale=ws,
                          out_dtype=out_dtype, interpret=interpret)


def rglru_scan_op(a, b, *, interpret: bool = False):
    if not (_use_pallas() or interpret):
        return ref.rglru_scan_ref(a, b)
    bsz, s, c = a.shape
    a_p, pc = _pad_to(a, 2, 128)
    b_p, _ = _pad_to(b, 2, 128)
    # pad a with ones in time? channel padding only: zeros fine (h stays 0)
    bt = 256
    pt = (-s) % bt
    if pt:
        a_p = jnp.pad(a_p, ((0, 0), (0, pt), (0, 0)))
        b_p = jnp.pad(b_p, ((0, 0), (0, pt), (0, 0)))
    h = rglru_scan(a_p, b_p, bt=min(bt, a_p.shape[1]), interpret=interpret)
    return h[:, :s, :c]


# ---------------------------------------------------------------------------
# Flash attention with custom VJP (Pallas fwd, reference bwd)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_op(q, k, v, causal: bool = True,
                       interpret: bool = False):
    """(B, S, H, D) causal attention; same heads for q/k/v (GQA expanded
    at the call site via repeat — see models/layers for the grouping)."""
    return _fa_fwd(q, k, v, causal, interpret)[0]


def _fa_fwd(q, k, v, causal, interpret):
    if not (_use_pallas() or interpret):
        return ref.flash_attention_ref(q, k, v, causal=causal), (q, k, v)
    b, s, h, d = q.shape
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, s, d)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h, s, d)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h, s, d)
    bq = 512 if s % 512 == 0 else (256 if s % 256 == 0 else s)
    o = flash_attention(qf, kf, vf, causal=causal, bq=bq, bk=bq,
                        interpret=interpret)
    o = jnp.moveaxis(o.reshape(b, h, s, d), 1, 2)
    return o, (q, k, v)


def _fa_bwd(causal, interpret, saved, g):
    q, k, v = saved
    # recompute-style backward via the reference implementation's VJP
    _, vjp = jax.vjp(lambda q, k, v: ref.flash_attention_ref(
        q, k, v, causal=causal), q, k, v)
    return vjp(g)


flash_attention_op.defvjp(_fa_fwd, _fa_bwd)


def force_backend(mode):
    """Context for tests: force 'pallas' (interpret on CPU) or 'ref'."""
    import contextlib

    @contextlib.contextmanager
    def ctx():
        prev = _FORCE["mode"]
        _FORCE["mode"] = mode
        try:
            yield
        finally:
            _FORCE["mode"] = prev
    return ctx()
