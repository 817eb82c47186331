"""executor.epilogue_share.online: the share of device-op time in the units'
epilogues (``bench.readers.epilogue_share``), moving ``latency_ms_p95``."""
from bench import readers


def read(ctx):
    return readers.epilogue_share(ctx)
