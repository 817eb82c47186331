"""Builders for the paper's CNN families (full + reduced variants).

``resnet34`` / ``mobilenetv2`` / ``ddpm_unet`` mirror the paper's networks
at full scale (used by the analytic latency tables and entry-count
benchmarks); the ``tiny_*`` variants, and ``ddpm_unet`` at a small ``ch``,
keep the same *structure* (skip kinds, strides, depthwise patterns, norms)
at toy width/depth so that the measured pipeline — importance fine-tuning,
wall-clock latency tables, DP, merging — runs on CPU in seconds.
"""
from __future__ import annotations

from .cnn import ConvNet, ConvSpec, SkipSpec


def _res_block(specs, skips, c, stride=1, cin=None, norm="bn"):
    cin = cin or c
    start = len(specs)
    specs.append(ConvSpec(cin, c, 3, stride, act="relu", norm=norm))
    specs.append(ConvSpec(c, c, 3, 1, act="relu", norm=norm))
    skips.append(SkipSpec("add", start, start + 2,
                          proj=(stride != 1 or cin != c)))


def resnet34(num_classes: int = 1000, in_hw: int = 224,
             width: int = 64, norm: str = "bn") -> ConvNet:
    specs: list[ConvSpec] = []
    skips: list[SkipSpec] = []
    w = width
    specs.append(ConvSpec(3, w, 7, 2, act="relu", norm=norm))      # stem
    specs.append(ConvSpec(w, w, 3, 2, kind="pool", act="none"))    # maxpool→avg
    for n, (c, s) in zip((3, 4, 6, 3),
                         ((w, 1), (2 * w, 2), (4 * w, 2), (8 * w, 2))):
        for b in range(n):
            _res_block(specs, skips, c, s if b == 0 else 1,
                       cin=None if b else specs[-1].cout, norm=norm)
    return ConvNet(tuple(specs), tuple(skips), in_hw=in_hw, in_ch=3,
                   head="classifier", num_classes=num_classes)


def tiny_resnet(num_classes: int = 10, in_hw: int = 16, width: int = 8,
                blocks=(2, 2), norm=None) -> ConvNet:
    specs: list[ConvSpec] = []
    skips: list[SkipSpec] = []
    w = width
    specs.append(ConvSpec(3, w, 3, 1, act="relu", norm=norm))
    for stage, n in enumerate(blocks):
        c = w * (2 ** stage)
        for b in range(n):
            stride = 2 if (stage > 0 and b == 0) else 1
            _res_block(specs, skips, c, stride,
                       cin=specs[-1].cout, norm=norm)
    return ConvNet(tuple(specs), tuple(skips), in_hw=in_hw, in_ch=3,
                   head="classifier", num_classes=num_classes)


def _inverted_residual(specs, skips, cin, cout, stride, expand, norm="bn"):
    mid = cin * expand
    start = len(specs)
    if expand != 1:
        specs.append(ConvSpec(cin, mid, 1, 1, act="relu6", norm=norm))
    specs.append(ConvSpec(mid, mid, 3, stride, depthwise=True, act="relu6",
                          norm=norm))
    specs.append(ConvSpec(mid, cout, 1, 1, act="none", norm=norm))
    if stride == 1 and cin == cout:
        skips.append(SkipSpec("add", start, len(specs)))


def mobilenetv2(num_classes: int = 1000, in_hw: int = 224,
                width_mult: float = 1.0, norm: str = "bn") -> ConvNet:
    def c(ch):
        return max(8, int(ch * width_mult + 4) // 8 * 8)
    cfg = [  # t, c, n, s  (paper table)
        (1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
        (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1)]
    specs: list[ConvSpec] = []
    skips: list[SkipSpec] = []
    specs.append(ConvSpec(3, c(32), 3, 2, act="relu6", norm=norm))
    cin = c(32)
    for t, ch, n, s in cfg:
        for b in range(n):
            _inverted_residual(specs, skips, cin, c(ch), s if b == 0 else 1,
                               t, norm=norm)
            cin = c(ch)
    specs.append(ConvSpec(cin, c(1280), 1, 1, act="relu6", norm=norm))
    return ConvNet(tuple(specs), tuple(skips), in_hw=in_hw, in_ch=3,
                   head="classifier", num_classes=num_classes,
                   act_after_merge=True)


def tiny_mobilenet(num_classes: int = 10, in_hw: int = 16, width: int = 8,
                   norm=None) -> ConvNet:
    specs: list[ConvSpec] = []
    skips: list[SkipSpec] = []
    specs.append(ConvSpec(3, width, 3, 1, act="relu6", norm=norm))
    cin = width
    for t, ch, n, s in [(2, width, 2, 1), (2, 2 * width, 2, 2)]:
        for b in range(n):
            _inverted_residual(specs, skips, cin, ch, s if b == 0 else 1, t,
                               norm=norm)
            cin = ch
    return ConvNet(tuple(specs), tuple(skips), in_hw=in_hw, in_ch=3,
                   head="classifier", num_classes=num_classes,
                   act_after_merge=True)


def ddpm_unet(in_hw: int = 32, ch: int = 128, ch_mult=(1, 2, 2, 2),
              num_res_blocks: int = 2, attn_resolutions=(16,),
              gn_groups: int = 32, gn_eps: float = 1e-6,
              out_ch: int = 3) -> ConvNet:
    """The DDPM CIFAR-10 UNet (Ho et al. 2020, arXiv 2006.11239, App. B;
    ``diffusion_tf/models/unet.py``), defaults at its published sizes.

    A pre-activation chain (``ConvNet.preact``): ``conv_in``; per level
    ``num_res_blocks`` ResBlocks (each followed by self-attention at the
    ``attn_resolutions``) and a 3×3 stride-2 downsample; a middle
    ResBlock–attention–ResBlock; per level up ``num_res_blocks + 1``
    ResBlocks, each on the concat of the stream with the matching
    down-path output, and a nearest ×2 upsample with a 3×3 conv; then
    GN → SiLU → ``conv_out``.  A ResBlock is two specs,
    ``conv1(SiLU(GN1(x))) + Dense(SiLU(temb))`` and ``conv2(SiLU(GN2(h)))``,
    with a residual add (a 1×1 ``nin_shortcut`` where channels change).
    The timestep embedding is ``ch`` wide, its MLP ``4·ch``.
    """
    specs: list[ConvSpec] = []
    skips: list[SkipSpec] = []

    def conv(cin, cout, stride=1, pre=False, temb=False):
        specs.append(ConvSpec(cin, cout, 3, stride,
                              act="silu" if pre else "none",
                              norm="gn" if pre else None,
                              gn_groups=gn_groups, gn_eps=gn_eps, temb=temb))

    def res_block(cin, cout):
        start = len(specs)
        conv(cin, cout, pre=True, temb=True)
        conv(cout, cout, pre=True)
        skips.append(SkipSpec("add", start, start + 2, proj=cin != cout))

    def attn(c):
        specs.append(ConvSpec(c, c, 1, 1, act="none", norm="gn",
                              gn_groups=gn_groups, gn_eps=gn_eps,
                              kind="attn"))

    conv(3, ch)                                   # conv_in
    c, hw = ch, in_hw
    hs = [(len(specs), c)]                        # down-path outputs
    for level, m in enumerate(ch_mult):
        for _ in range(num_res_blocks):
            res_block(c, ch * m)
            c = ch * m
            if hw in attn_resolutions:
                attn(c)
            hs.append((len(specs), c))
        if level != len(ch_mult) - 1:
            conv(c, c, stride=2)                  # downsample
            hw //= 2
            hs.append((len(specs), c))
    res_block(c, c)                               # middle
    attn(c)
    res_block(c, c)
    for level, m in reversed(list(enumerate(ch_mult))):
        for _ in range(num_res_blocks + 1):
            src, c_src = hs.pop()
            skips.append(SkipSpec("concat", src, len(specs)))
            res_block(c + c_src, ch * m)
            c = ch * m
            if hw in attn_resolutions:
                attn(c)
        if level != 0:
            specs.append(ConvSpec(c, c, 2, 2, kind="upsample", act="none"))
            conv(c, c)                            # upsample conv
            hw *= 2
    specs.append(ConvSpec(c, out_ch, 3, 1, act="silu", norm="gn",
                          gn_groups=gn_groups, gn_eps=gn_eps))  # conv_out
    return ConvNet(tuple(specs), tuple(skips), in_hw=in_hw, in_ch=3,
                   head="none", preact=True, temb_dim=ch, temb_ch=4 * ch)
