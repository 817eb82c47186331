"""Pallas TPU kernel: merged-segment convolution (VALID, stride s, NHWC).

The paper's hot spot: after LayerMerge, a segment executes as ONE conv
whose kernel has grown (Eq. 1) and whose stride is the product of the
segment's strides.  TPU adaptation: instead of im2col (which materializes
the k²-unrolled input in HBM), each grid step keeps one *output tile* of
the image in VMEM and accumulates the k_h·k_w shifted GEMMs —
(tile_ho·tile_wo, Cin) @ (Cin, bCout) per tap — on the MXU, so the grown
kernel costs FLOPs but no extra HBM traffic (exactly the trade the DP's
latency table models).

Grid: ``(batch, ho-tiles, wo-tiles, cout-tiles)`` with the channel axis
innermost so one input tile serves every output-channel block.

Phase-major input layout (the stride-s contract, shared with the
depthwise kernel in :mod:`repro.kernels.depthwise_conv`).  A stride-s
VALID conv reads, for output row ``t`` and tap ``u``, input row
``s·t + u`` — row *phase* ``u mod s``, phase-local index ``t + u//s``.
The wrapper therefore re-lays the image out **phase-major** before the
kernel::

    x (N, H, W, C)  →  x_pm (N, pʜ, p𝑤, H/s, W/s, C)
    x_pm[n, p, q, t, r, c] = x[n, s·t + p, s·r + q, c]

(pʜ = min(s, k_h), p𝑤 = min(s, k_w): taps can only touch the first
``k`` phases, so unused phases are never laid out or copied.)  Under
this layout each tap ``(u, v)`` of each tile is a *contiguous* window —
``x_pm[p, q][t₀ + u//s : t₀ + u//s + tile_ho, …]`` — so phase selection
is a static VMEM slice instead of the former reshape-and-index
decimation, and the tile's DMA is one rectangular window per step
covering every phase at once.  For s = 1 the layout is the identity
(pʜ = p𝑤 = 1) and the kernel degenerates bit-for-bit to the dense path;
the relayout itself is one XLA transpose (HBM read + write of the
image) charged by :func:`input_traffic_model` as ``relayout_bytes`` and
priced by ``conv2d_cost`` — only strided segments pay it.

Zero-copy halos.  The phase-major input stays HBM-resident
(``memory_space=ANY``); each grid step DMAs its halo'd window straight
into VMEM scratch with ``pltpu.make_async_copy`` over ``pl.ds``
windows::

    step t   (co == 0):  start DMA[t+1] → slot (t+1)%2     (prefetch)
                         wait  DMA[t]   ← slot t%2
    step t   (co  > 0):  reuse slot t%2 (already resident)

    HBM x_pm ───DMA──▶ VMEM xs[2, pʜ, p𝑤, tile_ho+δʜ, tile_wo+δ𝑤, Cin]
    HBM w ──spec──▶ VMEM (kh, kw, Cin, bCout)
                    fp32 acc (tile_ho·tile_wo, bCout) ──▶ out block

where ``δʜ = (k_h−1)//s`` / ``δ𝑤 = (k_w−1)//s`` are the per-phase halo
extents.  Input HBM traffic per call is one read of the image plus the
halo rows/cols re-read at tile seams (see :func:`input_traffic_model`).

VMEM per step (bounded by :func:`choose_tiles` regardless of image
size): double-buffered input scratch ``2·pʜ·p𝑤·(tile_ho + δʜ)·
(tile_wo + δ𝑤)·Cin`` — never larger than the dense-window bound
``2·(s·tile_ho + k_h − 1)·(s·tile_wo + k_w − 1)·Cin`` the planner
accounts — plus the weight block ``k²·Cin·bCout`` and the fp32
accumulator + output block ``tile_ho·tile_wo·bCout``.  Very wide
single-row images (panorama / NLP-grid) shrink ``tile_wo`` instead of
overflowing VMEM.  Bias add and the boundary activation σ_j run in the
kernel epilogue (fp32, before the store), eliminating the extra HBM
round-trip the unfused epilogue paid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import apply_activation

# Full working-set budget for the 2-D planner: double-buffered input
# scratch + weight block + fp32 accumulator + output block, inside
# ~16 MiB/core with room for Mosaic's own spills.
_VMEM_BUDGET = 6 * 2 ** 20
# Upper bound on the scoped VMEM a conv kernel may request (v5e has
# 128 MiB of VMEM per core; the rest stays with XLA).
_VMEM_CAP = 100 * 2 ** 20
# Mosaic's (8, 128) fp32 tiling: the width axis of every DMA window and
# output block is a multiple of 8 sublanes, the channel axis of every
# DMA window a multiple of 128 lanes (the ops layer pads channels).
SUBLANE = 8
LANE = 128


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def align_tile_wo(tile_wo: int, wo: int) -> int:
    """Output-width tile as a multiple of 8 sublanes (the output width is
    padded up to a whole number of tiles and sliced back off)."""
    return round_up(max(1, min(tile_wo, wo)), SUBLANE)


def vmem_limit(*block_bytes: float) -> int:
    """Scoped-VMEM request for a kernel whose pipelined blocks and scratch
    take ``block_bytes``: twice their sum (Mosaic's own temporaries —
    the tap copies and the fp32 accumulator — are of the same order),
    at least the 32 MiB default and at most :data:`_VMEM_CAP`."""
    return int(min(max(2 * sum(block_bytes), 32 * 2 ** 20), _VMEM_CAP))


def phase_extents(kh: int, kw: int, stride: int) -> tuple[int, int, int, int]:
    """``(pʜ, p𝑤, δʜ, δ𝑤)`` of the phase-major layout: phases touched per
    spatial axis (``min(s, k)``) and per-phase halo extents
    (``(k−1)//s``).  For s = 1 this is ``(1, 1, k_h−1, k_w−1)`` — the
    dense window."""
    s = max(stride, 1)
    return min(s, kh), min(s, kw), (kh - 1) // s, (kw - 1) // s


def phase_major(x, kh: int, kw: int, stride: int, hs: int, ws: int):
    """Lay an NHWC image out phase-major: ``(N, pʜ, p𝑤, hs, ws, C)``.

    ``hs``/``ws`` are the per-phase spatial extents the kernel's tiling
    requires; the image is zero-padded up to ``(s·hs, s·ws)`` first
    (ragged last tiles / s∤H).  One XLA transpose — the only HBM
    relayout a strided segment pays; s = 1 is a free reshape.
    """
    n, h, w, c = x.shape
    s = max(stride, 1)
    ph, pw, _, _ = phase_extents(kh, kw, s)
    pad_h, pad_w = s * hs - h, s * ws - w
    assert pad_h >= 0 and pad_w >= 0, (x.shape, hs, ws, s)
    if pad_h or pad_w:
        x = jnp.pad(x, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)))
    x = x.reshape(n, hs, s, ws, s, c).transpose(0, 2, 4, 1, 3, 5)
    return x[:, :ph, :pw]


def _round8(t: int, cap: int) -> int:
    """Clamp a tile extent to [1, cap], preferring multiples of 8."""
    t = max(min(t, cap), 1)
    if t < cap and t > 8:
        t -= t % 8
    return t


def choose_tiles(h: int, w: int, cin: int, kh: int, kw: int, stride: int,
                 itemsize: int, bcout: int = 128,
                 budget_bytes: float = _VMEM_BUDGET) -> tuple[int, int]:
    """2-D ``(tile_ho, tile_wo)`` VMEM planner for the merged conv.

    Accounts the whole per-step working set: double-buffered input
    scratch via the dense-window bound ``2·(s·tho + k_h − 1)·(s·two +
    k_w − 1)·Cin·itemsize`` (an upper bound on the phase-major scratch
    ``2·pʜ·p𝑤·(tho + δʜ)·(two + δ𝑤)·Cin`` actually allocated — equal
    whenever ``s | k−1``, e.g. every odd kernel at stride 2), the weight
    block ``k_h·k_w·Cin·bCout·itemsize`` and the fp32 accumulator plus
    output block ``tho·two·bCout·(4 + itemsize)``.  Starts from the full
    output width and grows the row tile; only when a single full-width
    output row overflows (very wide images) does it shrink ``tile_wo``
    with ``tile_ho = 1``.  Prefers multiples of 8 on the tiled axis.

    A weight block larger than the budget (deep merged kernels at wide
    channels, e.g. 7×7×512 fp32) does not starve the tiles: they keep a
    quarter of the budget, and the kernel asks Mosaic for the extra
    VMEM (:func:`vmem_limit`).
    """
    s = max(stride, 1)
    ho = max((h - kh) // s + 1, 1)
    wo = max((w - kw) // s + 1, 1)
    fixed = kh * kw * cin * bcout * itemsize          # weight block
    acc_b = bcout * (4 + itemsize)                    # per output element
    tiles = max(budget_bytes - fixed, budget_bytes / 4)

    # Single full-width output row: does it fit?
    shi1 = s + kh - 1
    a_w = 2 * shi1 * s * cin * itemsize + acc_b
    b_w = 2 * shi1 * (kw - 1) * cin * itemsize
    if a_w * wo + b_w > tiles:
        tile_wo = int((tiles - b_w) // a_w)
        return 1, _round8(tile_wo, wo)

    # Full width fits: grow the row tile.
    swi = s * wo + kw - 1
    a_h = 2 * s * swi * cin * itemsize + wo * acc_b
    b_h = 2 * (kh - 1) * swi * cin * itemsize
    tile_ho = int((tiles - b_h) // a_h)
    return _round8(tile_ho, ho), wo


def plan_tiles(h: int, w: int, cin: int, kh: int, kw: int, stride: int,
               itemsize: int, bcout: int, tile_ho: int | None = None,
               tile_wo: int | None = None) -> tuple[int, int]:
    """The ``(tile_ho, tile_wo)`` :func:`merged_conv` runs: the requested
    tiles, or :func:`choose_tiles`' where none is given, with the row tile
    at most the output height and the width tile in whole sublanes."""
    s = max(stride, 1)
    if tile_ho is None or tile_wo is None:
        a_ho, a_wo = choose_tiles(h, w, cin, kh, kw, s, itemsize, bcout)
        tile_ho = a_ho if tile_ho is None else tile_ho
        tile_wo = a_wo if tile_wo is None else tile_wo
    ho, wo = (h - kh) // s + 1, (w - kw) // s + 1
    return max(1, min(tile_ho, ho)), align_tile_wo(tile_wo, wo)


def input_traffic_model(h: int, w: int, cin: int, kh: int, kw: int,
                        stride: int, itemsize: int,
                        tile_ho: int | None = None,
                        tile_wo: int | None = None,
                        bcout: int = 128,
                        groups: int = 1) -> dict[str, float]:
    """Per-image input HBM bytes of the DMA kernel vs the PR-1 host gather.

    ``dma_bytes`` is what the zero-copy kernel moves: every tile's
    phase-major halo'd window read once straight out of the HBM-resident
    image (one image read plus the halo rows/cols re-read at tile seams).
    The total is *group-blocking invariant*: the depthwise/grouped kernel
    DMAs each spatial window once per channel block, but each block
    carries only its own channels, so the aggregate equals the dense
    kernel's — ``groups`` only affects which tile planner picks the
    default tiles.  ``relayout_bytes`` is the one-off phase-major
    transpose strided segments pay (HBM read + write of the padded
    image; zero at stride 1).  ``gather_bytes`` is what the deleted
    host-side gather paid whenever more than one row tile was needed:
    read the image, write the halo'd row-tile tensor, read it back in
    the kernel.  ``saved_bytes`` is the reclaimed bandwidth net of the
    relayout.
    """
    s = max(stride, 1)
    if tile_ho is None or tile_wo is None:
        if groups > 1:
            # grouped/depthwise path: channel-blocked tiles from the
            # grouped planner (cost queries are always pure depthwise,
            # cin_g = cout_g = 1; the layering note in conv2d_cost
            # applies — kernels never import core, no cycle)
            from .depthwise_conv import choose_tiles_grouped
            from .ops import channel_tile
            a_ho, a_wo = choose_tiles_grouped(
                h, w, 1, 1, kh, kw, s, itemsize,
                bgroups=channel_tile(groups, None))
        else:
            a_ho, a_wo = choose_tiles(h, w, cin, kh, kw, s, itemsize, bcout)
        tile_ho = tile_ho or a_ho
        tile_wo = tile_wo or a_wo
    ho = max((h - kh) // s + 1, 1)
    wo = max((w - kw) // s + 1, 1)
    tile_ho = max(1, min(tile_ho, ho))
    tile_wo = max(1, min(tile_wo, wo))
    n_th, n_tw = -(-ho // tile_ho), -(-wo // tile_wo)
    ph, pw, dh, dw = phase_extents(kh, kw, s)
    tile_elems = ph * pw * (tile_ho + dh) * (tile_wo + dw)
    image = h * w * cin * itemsize
    dma = n_th * n_tw * tile_elems * cin * itemsize
    relayout = 0.0
    if s > 1:
        hs = max(n_th * tile_ho + dh, -(-h // s))
        ws = max(n_tw * tile_wo + dw, -(-w // s))
        relayout = 2.0 * s * hs * s * ws * cin * itemsize
    # PR-1 path: stride-1 only, full-width row tiles; xt was materialized
    # (and re-read) whenever n_th > 1.
    tile_hi = s * (tile_ho - 1) + kh
    xt = n_th * tile_hi * w * cin * itemsize
    gather = image + 2 * xt if n_th > 1 else xt
    return {"image_bytes": float(image), "dma_bytes": float(dma),
            "relayout_bytes": float(relayout),
            "gather_bytes": float(gather),
            # halo-gather traffic reclaimed (dense and depthwise rows
            # alike; group-blocking invariant), before the relayout charge
            "halo_bytes_saved": float(gather - dma),
            "saved_bytes": float(gather - dma - relayout),
            "tile_ho": tile_ho, "tile_wo": tile_wo}


def _kernel(x_hbm, w_ref, b_ref, *rest, kh: int, kw: int,
            stride: int, n_th: int, n_tw: int, activation: str | None,
            quant: bool = False):
    # Quantized path: one extra (1, bCout) fp32 scale operand (per-output-
    # channel symmetric weight scale; w8a8 folds the activation scale in
    # at the ops layer).  Applied AFTER the fp32 accumulation — exactly
    # equal to dequantizing each weight before the dot, since the scale
    # is constant over the (kh, kw, Cin) contraction.
    if quant:
        ws_ref, o_ref, xs, sem = rest
    else:
        ws_ref, (o_ref, xs, sem) = None, rest
    tho, two, bcout = o_ref.shape
    cin = w_ref.shape[2]
    s = stride
    shp, swp = xs.shape[3], xs.shape[4]       # per-phase halo'd tile extents
    bb, th, tw, co = (pl.program_id(i) for i in range(4))
    step = (bb * n_th + th) * n_tw + tw
    n_steps = pl.num_programs(0) * n_th * n_tw

    def dma(step_idx, slot):
        b2 = step_idx // (n_th * n_tw)
        r = step_idx % (n_th * n_tw)
        return pltpu.make_async_copy(
            x_hbm.at[b2, :, :, pl.ds((r // n_tw) * tho, shp),
                     pl.ds((r % n_tw) * two, swp), :],
            xs.at[slot], sem.at[slot])

    @pl.when((step == 0) & (co == 0))
    def _():                                   # pipeline prologue
        dma(0, 0).start()

    @pl.when((co == 0) & (step + 1 < n_steps))
    def _():                                   # prefetch next tile window
        dma(step + 1, (step + 1) % 2).start()

    @pl.when(co == 0)
    def _():                                   # await this step's window
        dma(step, step % 2).wait()

    acc = jnp.zeros((tho * two, bcout), jnp.float32)
    for u in range(kh):
        for v in range(kw):
            # Phase-major tap selection: tap (u, v) is the contiguous
            # window [u//s : u//s + tho, v//s : v//s + two] of phase
            # (u % s, v % s) — a static VMEM slice, no reshape-and-index.
            xsel = xs[step % 2, u % s, v % s, pl.ds(u // s, tho),
                      pl.ds(v // s, two), :]              # (tho, two, Cin)
            acc = acc + jnp.dot(
                xsel.reshape(tho * two, cin).astype(jnp.float32),
                w_ref[u, v].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    if ws_ref is not None:
        acc = acc * ws_ref[0].astype(jnp.float32)        # dequant epilogue
    acc = acc + b_ref[0].astype(jnp.float32)             # (bCout,) broadcast
    # fused epilogue: σ_j on the fp32 accumulator, shared with the oracle
    acc = apply_activation(acc, activation)
    o_ref[...] = acc.reshape(tho, two, bcout).astype(o_ref.dtype)


def merged_conv(x, w, b=None, *, stride: int = 1, bcout: int = 128,
                tile_ho: int | None = None, tile_wo: int | None = None,
                activation: str | None = None, w_scale=None,
                out_dtype=None, interpret: bool = False):
    """x: (N, H, W, Cin); w: (kh, kw, Cin, Cout) → (N, Ho, Wo, Cout).

    VALID convolution with ``stride`` on both spatial axes.  ``tile_ho`` /
    ``tile_wo`` are the output tile dims (default: the 2-D VMEM planner);
    ``b``/``activation`` fuse the segment epilogue.  The input is laid
    out phase-major (see module docstring) before the kernel; at stride 1
    that is a free reshape.

    Quantized weights: pass ``w`` narrow (int8 / fp8) with ``w_scale`` —
    a per-output-channel ``(Cout,)`` fp32 scale applied in the fp32
    epilogue.  w8a8 additionally passes ``x`` int8 with the activation
    scale pre-folded into ``w_scale``; set ``out_dtype`` to keep the
    output fp.  The narrow blocks ride the same zero-copy DMA pipeline
    (VMEM scratch takes its dtype from ``x``).
    """
    n, h, wdt, cin = x.shape
    kh, kw, _, cout = w.shape
    s = stride
    assert s >= 1 and h >= kh and wdt >= kw, (x.shape, w.shape, s)
    ho = (h - kh) // s + 1
    wo = (wdt - kw) // s + 1
    bcout = min(bcout, cout)
    assert cout % bcout == 0, "pad channels at the ops layer"
    tile_ho, tile_wo = plan_tiles(h, wdt, cin, kh, kw, s, x.dtype.itemsize,
                                  bcout, tile_ho, tile_wo)
    n_th, n_tw = -(-ho // tile_ho), -(-wo // tile_wo)
    ho_p, wo_p = n_th * tile_ho, n_tw * tile_wo
    ph, pw, dh, dw = phase_extents(kh, kw, s)
    # per-phase halo'd tile extents; the width is the DMA window's
    # second-minor axis, so it is padded to whole sublane tiles
    shp, swp = tile_ho + dh, round_up(tile_wo + dw, SUBLANE)

    # Phase-major relayout; per-phase extents padded so every DMA window
    # is full (static copy sizes) — ragged last tiles read zero rows/cols
    # whose outputs are sliced off below.
    hs = max(n_th * tile_ho + dh, -(-h // s))
    ws = max((n_tw - 1) * tile_wo + swp, -(-wdt // s))
    with jax.named_scope("relayout"):
        x = phase_major(x, kh, kw, s, hs, ws)

    with jax.named_scope("weight_prep"):
        bias = (jnp.zeros((1, cout), jnp.float32) if b is None
                else b.reshape(1, cout))
    odt = jnp.dtype(out_dtype) if out_dtype is not None else x.dtype

    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),        # HBM phase-major image
        pl.BlockSpec((kh, kw, cin, bcout),
                     lambda bb, th, tw, co: (0, 0, 0, co)),
        pl.BlockSpec((1, bcout), lambda bb, th, tw, co: (0, co)),
    ]
    operands = [x, w, bias]
    if w_scale is not None:
        in_specs.append(pl.BlockSpec((1, bcout),
                                     lambda bb, th, tw, co: (0, co)))
        with jax.named_scope("weight_prep"):
            operands.append(w_scale.reshape(1, cout).astype(jnp.float32))

    grid = (n, n_th, n_tw, cout // bcout)
    with jax.named_scope("kernel"):
        out = pl.pallas_call(
            functools.partial(_kernel, kh=kh, kw=kw, stride=s, n_th=n_th,
                              n_tw=n_tw, activation=activation,
                              quant=w_scale is not None),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, tile_ho, tile_wo, bcout),
                                   lambda bb, th, tw, co: (bb, th, tw, co)),
            out_shape=jax.ShapeDtypeStruct((n, ho_p, wo_p, cout), odt),
            scratch_shapes=[pltpu.VMEM((2, ph, pw, shp, swp, cin), x.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(
                2 * ph * pw * shp * swp * cin * x.dtype.itemsize,
                2 * kh * kw * cin * bcout * w.dtype.itemsize,
                tile_ho * tile_wo * bcout * (4 + 2 * odt.itemsize))),
            interpret=interpret,
            name="merged_conv",
        )(*operands)
    if (ho_p, wo_p) != (ho, wo):
        with jax.named_scope("crop"):
            out = out[:, :ho, :wo]
    return out
