"""executor.dispatch_ms.online: the median ``executor.apply`` host span
(``bench.readers.dispatch_ms``), moving ``latency_ms_p95``."""
from bench import readers


def read(ctx):
    return readers.dispatch_ms(ctx)
