"""ddpm.attention_share: the share of device-op time in the DDPM UNet's
attention units (``bench.ddpm.attention_share``), moving
``images_per_s``."""
from bench import ddpm


def read(ctx):
    return ddpm.attention_share(ctx)
