"""Pallas TPU kernel: depthwise/grouped merged-segment conv (VALID, NHWC).

LayerMerge's headline efficiency results are on MobileNetV2 — inverted-
residual networks whose merged segments are dominated by *depthwise*
convolutions (``feature_group_count == channels``), which the dense
merged-conv kernel cannot express (its MXU contraction mixes every input
channel into every output channel).  This kernel runs those segments —
and general grouped convs with ``feature_group_count > 1`` — on the
fast path, reusing the zero-copy double-buffered DMA-halo pipeline and
the **phase-major input layout** of :mod:`repro.kernels.merged_conv`
(see that module's docstring for the layout contract; the tap loop here
is the same static-slice phase selection).

Grid and accumulators.  Because a grouped conv never mixes channels
across groups, the channel axis is *blocked jointly with the input*:

    grid ``(batch, ho-tiles, wo-tiles, group-blocks)``

with ``bgroups`` groups per block (``choose_group_block``: for
depthwise, a lane-friendly channel tile via ``ops.channel_tile``; for
``cin_g > 1`` one group per step so each tap is one dense
``(tile·tile, cin_g) @ (cin_g, cout_g)`` MXU contraction).  Unlike the
dense kernel — where one input tile is reused across every
output-channel block — each grid step here DMAs its *own* channel slice
of the halo'd window (``bgroups·cin_g`` channels), so the channel axis
rides in the innermost grid position purely to keep the double-buffered
pipeline dense; aggregate input traffic is identical to the dense
kernel's (each channel of each window read exactly once — the
group-blocking invariance ``input_traffic_model`` relies on).

Per-group fp32 accumulators.  The accumulator is
``(tile_ho·tile_wo, bgroups·cout_g)`` in fp32; each tap contributes

* depthwise (``cin_g == cout_g == 1``): a VPU broadcast
  multiply-accumulate ``acc += x_tap · w[u, v]`` — no MXU, no
  channel-mixing GEMM;
* channel-multiplier depthwise (``cin_g == 1, cout_g > 1``): the same
  broadcast against ``(bgroups, cout_g)`` weights;
* grouped (``cin_g > 1``): one small MXU dot per group in the block,
  accumulated into the group's column slice.

Bias + boundary activation σ_j fuse into the epilogue exactly as in the
dense kernel.  VMEM per step is bounded by :func:`choose_tiles_grouped`
— the 2-D planner extended to the grouped footprint: double-buffered
input scratch carries only the block's ``bgroups·cin_g`` channels, the
weight block is ``k_h·k_w·bgroups·cin_g·cout_g`` (a factor ``groups``
smaller than the dense kernel's ``k²·Cin·bCout``), and the fp32
accumulator + output block is ``tho·two·bgroups·cout_g``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .merged_conv import (_VMEM_BUDGET, LANE, SUBLANE, _round8,
                          align_tile_wo, phase_extents, phase_major,
                          round_up, vmem_limit)
from .ref import apply_activation


def choose_group_block(groups: int, cin_g: int, cout_g: int,
                       requested: int | None = None) -> int:
    """Groups per grid step (the channel-block width in group units).

    Depthwise-shaped convs (``cin_g == 1``) take one 128-lane channel
    block: each grid step DMAs its own channel slice of the image, and
    Mosaic only slices the lane axis at whole 128-lane tiles, so the
    group axis is padded *up* to a multiple of 128 (never searched
    down).  An explicit ``requested`` block is rounded by
    :func:`repro.kernels.ops.channel_tile` (interpret-mode sweeps).
    General grouped convs (``cin_g > 1``) take one group per step — each
    group is its own dense MXU contraction, so blocking more would only
    serialize python-unrolled dots inside the kernel.
    """
    if cin_g == 1:
        if requested is None:
            return LANE
        from .ops import channel_tile                 # lazy: ops imports us
        return max(1, channel_tile(groups * cout_g, requested) // cout_g)
    return 1


def choose_tiles_grouped(h: int, w: int, cin_g: int, cout_g: int,
                         kh: int, kw: int, stride: int, itemsize: int,
                         bgroups: int = 1,
                         budget_bytes: float = _VMEM_BUDGET
                         ) -> tuple[int, int]:
    """``(tile_ho, tile_wo)`` planner for the grouped kernel's footprint.

    Same two-branch structure as ``merged_conv.choose_tiles`` (grow the
    row tile at full output width; shrink ``tile_wo`` only for panorama
    images), with the working set re-derived for the grouped grid: the
    double-buffered input scratch holds the block's ``bgroups·cin_g``
    channels (dense-window upper bound on the phase-major scratch), the
    weight block is ``k_h·k_w·bgroups·cin_g·cout_g`` and the fp32
    accumulator + output block ``tho·two·bgroups·cout_g·(4+itemsize)``.
    """
    s = max(stride, 1)
    ho = max((h - kh) // s + 1, 1)
    wo = max((w - kw) // s + 1, 1)
    bcin = bgroups * cin_g
    fixed = kh * kw * bgroups * cin_g * cout_g * itemsize   # weight block
    acc_b = bgroups * cout_g * (4 + itemsize)               # per output elem
    tiles = max(budget_bytes - fixed, budget_bytes / 4)

    shi1 = s + kh - 1
    a_w = 2 * shi1 * s * bcin * itemsize + acc_b
    b_w = 2 * shi1 * (kw - 1) * bcin * itemsize
    if a_w * wo + b_w > tiles:
        tile_wo = int((tiles - b_w) // a_w)
        return 1, _round8(tile_wo, wo)

    swi = s * wo + kw - 1
    a_h = 2 * s * swi * bcin * itemsize + wo * acc_b
    b_h = 2 * (kh - 1) * swi * bcin * itemsize
    tile_ho = int((tiles - b_h) // a_h)
    return _round8(tile_ho, ho), wo


def _kernel(x_hbm, w_ref, b_ref, *rest, kh: int, kw: int,
            stride: int, n_th: int, n_tw: int, n_tc: int, cin_g: int,
            cout_g: int, activation: str | None, quant: bool = False):
    # Quantized path: one extra (1, bc) fp32 per-output-channel weight
    # scale operand, applied after the fp32 accumulation (see
    # merged_conv._kernel — same contract, group-blocked layout).
    if quant:
        ws_ref, o_ref, xs, sem = rest
    else:
        ws_ref, (o_ref, xs, sem) = None, rest
    tho, two, bc = o_ref.shape
    bgroups = bc // cout_g
    bcin = bgroups * cin_g
    s = stride
    shp, swp = xs.shape[3], xs.shape[4]       # per-phase halo'd tile extents
    bb, th, tw, tc = (pl.program_id(i) for i in range(4))
    tiles = n_th * n_tw * n_tc
    step = ((bb * n_th + th) * n_tw + tw) * n_tc + tc
    n_steps = pl.num_programs(0) * tiles

    def dma(step_idx, slot):
        b2 = step_idx // tiles
        r = step_idx % tiles
        rs, rc = r // n_tc, r % n_tc
        return pltpu.make_async_copy(
            x_hbm.at[b2, :, :, pl.ds((rs // n_tw) * tho, shp),
                     pl.ds((rs % n_tw) * two, swp),
                     pl.ds(rc * bcin, bcin)],
            xs.at[slot], sem.at[slot])

    # Every step owns its (spatial tile, channel block) window — there is
    # no cross-step reuse to exploit, so the pipeline double-buffers over
    # the flat step counter directly.
    @pl.when(step == 0)
    def _():                                   # pipeline prologue
        dma(0, 0).start()

    @pl.when(step + 1 < n_steps)
    def _():                                   # prefetch next window
        dma(step + 1, (step + 1) % 2).start()

    dma(step, step % 2).wait()                 # await this step's window

    p = tho * two
    acc = jnp.zeros((p, bc), jnp.float32)
    for u in range(kh):
        for v in range(kw):
            # Phase-major tap selection (static slice — see merged_conv).
            xsel = xs[step % 2, u % s, v % s, pl.ds(u // s, tho),
                      pl.ds(v // s, two), :]              # (tho, two, bcin)
            xsel = xsel.reshape(p, bcin).astype(jnp.float32)
            wtap = w_ref[u, v].astype(jnp.float32)  # (bgroups, cin_g·cout_g)
            if cin_g == 1 and cout_g == 1:
                # depthwise: per-channel VPU multiply-accumulate
                acc = acc + xsel * wtap.reshape(1, bc)
            elif cin_g == 1:
                # channel-multiplier depthwise: broadcast over cout_g
                acc = acc + (xsel.reshape(p, bgroups, 1)
                             * wtap.reshape(bgroups, cout_g)[None]
                             ).reshape(p, bc)
            else:
                # grouped: one dense contraction per group in the block
                # (concatenated, not scatter-updated — Pallas tracing
                # rejects the constant index arrays `.at[].add` captures)
                xg = xsel.reshape(p, bgroups, cin_g)
                blks = [jnp.dot(xg[:, g], wtap[g].reshape(cin_g, cout_g),
                                preferred_element_type=jnp.float32)
                        for g in range(bgroups)]
                acc = acc + (blks[0] if bgroups == 1
                             else jnp.concatenate(blks, axis=1))
    if ws_ref is not None:
        acc = acc * ws_ref[0].astype(jnp.float32)        # dequant epilogue
    acc = acc + b_ref[0].astype(jnp.float32)             # (bc,) broadcast
    # fused epilogue: σ_j on the fp32 accumulator, shared with the oracle
    acc = apply_activation(acc, activation)
    o_ref[...] = acc.reshape(tho, two, bc).astype(o_ref.dtype)


def depthwise_conv(x, w, b=None, *, stride: int = 1, groups: int,
                   bgroups: int = 1, tile_ho: int | None = None,
                   tile_wo: int | None = None,
                   activation: str | None = None, w_scale=None,
                   out_dtype=None, interpret: bool = False):
    """x: (N, H, W, Cin); w: (kh, kw, Cin/g, Cout) → (N, Ho, Wo, Cout).

    VALID grouped convolution with ``feature_group_count = groups`` and
    ``stride`` on both spatial axes (depthwise = ``groups == Cin`` with
    a ``(kh, kw, 1, Cin)`` kernel).  ``bgroups`` groups execute per grid
    step (default: :func:`choose_group_block` at the ops layer); the
    group axis is zero-padded up to a ``bgroups`` multiple here, and the
    padded output channels sliced back off.  ``tile_ho``/``tile_wo``
    default to :func:`choose_tiles_grouped`; ``b``/``activation`` fuse
    the segment epilogue.  ``w_scale``/``out_dtype``: quantized-weight
    path, same contract as :func:`repro.kernels.merged_conv.merged_conv`
    (``w_scale`` is per-output-channel ``(Cout,)``, re-laid group-blocked
    alongside the bias).
    """
    n, h, wdt, cin = x.shape
    kh, kw, cin_g, cout = w.shape
    s = stride
    assert s >= 1 and h >= kh and wdt >= kw, (x.shape, w.shape, s)
    assert cin == groups * cin_g and cout % groups == 0, \
        (x.shape, w.shape, groups)
    cout_g = cout // groups
    ho = (h - kh) // s + 1
    wo = (wdt - kw) // s + 1
    if tile_ho is None or tile_wo is None:
        a_ho, a_wo = choose_tiles_grouped(h, wdt, cin_g, cout_g, kh, kw, s,
                                          x.dtype.itemsize, bgroups)
        tile_ho = a_ho if tile_ho is None else tile_ho
        tile_wo = a_wo if tile_wo is None else tile_wo
    tile_ho = max(1, min(tile_ho, ho))
    tile_wo = align_tile_wo(tile_wo, wo)
    n_th, n_tw = -(-ho // tile_ho), -(-wo // tile_wo)
    ho_p, wo_p = n_th * tile_ho, n_tw * tile_wo
    ph, pw, dh, dw = phase_extents(kh, kw, s)
    shp, swp = tile_ho + dh, round_up(tile_wo + dw, SUBLANE)

    # Pad the group axis to a bgroups multiple.  Channels are group-major
    # (lax HWIO grouped layout), so padded input channels and padded
    # output channels are one contiguous tail each.
    pad_g = (-groups) % bgroups
    g_p = groups + pad_g
    if pad_g:
        with jax.named_scope("lane_pad"):
            x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad_g * cin_g)))
    with jax.named_scope("weight_prep"):
        # (kh, kw, cin_g, G·cout_g) → (kh, kw, G_p, cin_g·cout_g): the 4-D
        # group-blocked weight layout the kernel's BlockSpec tiles over.
        w4 = w.reshape(kh, kw, cin_g, groups, cout_g).transpose(
            0, 1, 3, 2, 4)
        if pad_g:
            w4 = jnp.pad(w4, ((0, 0), (0, 0), (0, pad_g), (0, 0), (0, 0)))
        w4 = w4.reshape(kh, kw, g_p, cin_g * cout_g)
        bias = jnp.zeros((groups, cout_g), jnp.float32) if b is None \
            else b.reshape(groups, cout_g)
        bias = jnp.pad(bias, ((0, pad_g), (0, 0))).reshape(1, g_p * cout_g)
        if w_scale is not None:
            # per-cout scale follows the bias's group-blocked layout
            scale_b = w_scale.astype(jnp.float32).reshape(groups, cout_g)
            scale_b = jnp.pad(scale_b,
                              ((0, pad_g), (0, 0))).reshape(1, g_p * cout_g)

    # Phase-major relayout (shared contract with merged_conv; free at
    # stride 1, one XLA transpose otherwise).
    hs = max(n_th * tile_ho + dh, -(-h // s))
    ws = max((n_tw - 1) * tile_wo + swp, -(-wdt // s))
    with jax.named_scope("relayout"):
        x = phase_major(x, kh, kw, s, hs, ws)

    bcin = bgroups * cin_g
    bc = bgroups * cout_g
    n_tc = g_p // bgroups
    odt = jnp.dtype(out_dtype) if out_dtype is not None else x.dtype
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),        # HBM phase-major image
        pl.BlockSpec((kh, kw, bgroups, cin_g * cout_g),
                     lambda bb, th, tw, tc: (0, 0, tc, 0)),
        pl.BlockSpec((1, bc), lambda bb, th, tw, tc: (0, tc)),
    ]
    operands = [x, w4, bias]
    if w_scale is not None:
        in_specs.append(pl.BlockSpec((1, bc),
                                     lambda bb, th, tw, tc: (0, tc)))
        operands.append(scale_b)
    grid = (n, n_th, n_tw, n_tc)
    with jax.named_scope("kernel"):
        out = pl.pallas_call(
            functools.partial(_kernel, kh=kh, kw=kw, stride=s, n_th=n_th,
                              n_tw=n_tw, n_tc=n_tc, cin_g=cin_g,
                              cout_g=cout_g, activation=activation,
                              quant=w_scale is not None),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, tile_ho, tile_wo, bc),
                                   lambda bb, th, tw, tc: (bb, th, tw, tc)),
            out_shape=jax.ShapeDtypeStruct((n, ho_p, wo_p, g_p * cout_g),
                                           odt),
            scratch_shapes=[pltpu.VMEM((2, ph, pw, shp, swp, bcin), x.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(
                2 * ph * pw * shp * swp * bcin * x.dtype.itemsize,
                2 * kh * kw * bgroups * cin_g * cout_g * w.dtype.itemsize,
                tile_ho * tile_wo * bc * (4 + 2 * odt.itemsize))),
            interpret=interpret,
            name="depthwise_conv",
        )(*operands)
    if (ho_p, wo_p) != (ho, wo) or g_p != groups:
        with jax.named_scope("crop"):
            out = out[:, :ho, :wo, :cout]
    return out
