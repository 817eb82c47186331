"""executor.outside_kernel_share.online: the share of device-busy time outside the Pallas kernels
(``bench.readers.outside_kernel_share``), moving ``latency_ms_p95``."""
from bench import readers


def read(ctx):
    return readers.outside_kernel_share(ctx)
