"""mfu.online: the share of peak FLOP/s reached by the whole forward
(``bench.readers.mfu``), moving ``latency_ms_p95``."""
from bench import readers


def read(ctx):
    return readers.mfu(ctx)
