"""depthwise_conv_roofline.online: the share of its roofline reached by the depthwise_conv kernels
(``bench.readers.kernel_roofline``), moving ``latency_ms_p95``."""
from bench import readers


def read(ctx):
    return readers.kernel_roofline(ctx, "depthwise_conv")
