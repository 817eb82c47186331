"""executor.outside_kernel_share: the share of device-busy time outside the Pallas kernels
(``bench.readers.outside_kernel_share``), moving ``images_per_s``."""
from bench import readers


def read(ctx):
    return readers.outside_kernel_share(ctx)
