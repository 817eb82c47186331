"""device.idle_share: the share of the traced window with the device idle
(``bench.readers.idle_share``), moving ``images_per_s``."""
from bench import readers


def read(ctx):
    return readers.idle_share(ctx)
