"""depthwise_conv_roofline: the share of its roofline reached by the depthwise_conv kernels
(``bench.readers.kernel_roofline``), moving ``images_per_s``."""
from bench import readers


def read(ctx):
    return readers.kernel_roofline(ctx, "depthwise_conv")
