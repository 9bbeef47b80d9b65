"""Percent of the traced window in which no operation ran on the device."""
from chipbench import layers


def read(run):
    return layers.device_idle_share(run)
