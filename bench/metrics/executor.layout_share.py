"""executor.layout_share: the share of device-op time in layout roles: pads,
lane pads, weight preparation, relayouts, crops
(``bench.readers.layout_share``), moving ``images_per_s``."""
from bench import readers


def read(ctx):
    return readers.layout_share(ctx)
