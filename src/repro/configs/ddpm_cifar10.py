"""The paper's own DDPM CIFAR-10 UNet (Ho et al. 2020, App. B) — CNN path:
ch 128, ch_mult (1, 2, 2, 2), two ResBlocks a level, attention at 16×16."""
from repro.models import zoo

CONFIG = zoo.ddpm_unet()
