"""executor.epilogue_share: the share of device-op time in the units'
epilogues (``bench.readers.epilogue_share``), moving ``images_per_s``."""
from bench import readers


def read(ctx):
    return readers.epilogue_share(ctx)
