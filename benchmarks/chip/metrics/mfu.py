"""Percent of the chips' peak reached by useful model work: admitted
frames through their model, over the summed fused-dispatch time."""
from chipbench import layers


def read(run):
    return layers.mfu(run)
