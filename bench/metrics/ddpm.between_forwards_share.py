"""ddpm.between_forwards_share: the share of device-op time outside every
unit of the sampled UNet — the time MLP, the DDIM update
(``bench.ddpm.between_forwards_share``) — moving ``images_per_s``."""
from bench import ddpm


def read(ctx):
    return ddpm.between_forwards_share(ctx)
