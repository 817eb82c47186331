"""device.idle_share.online: the share of the traced window with the device idle
(``bench.readers.idle_share``), moving ``latency_ms_p95``."""
from bench import readers


def read(ctx):
    return readers.idle_share(ctx)
