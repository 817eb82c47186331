"""executor.layout_share.online: the share of device-op time in layout roles:
pads, lane pads, weight preparation, relayouts, crops
(``bench.readers.layout_share``), moving ``latency_ms_p95``."""
from bench import readers


def read(ctx):
    return readers.layout_share(ctx)
