"""mfu: the share of peak FLOP/s reached by the whole forward
(``bench.readers.mfu``), moving ``images_per_s``."""
from bench import readers


def read(ctx):
    return readers.mfu(ctx)
