"""Mean ``FleetStep.last_dispatch_s``, in ms: the host clock around one
fused dispatch, covering the staging upload, the device work and the
mask read-back."""
from chipbench import layers


def read(run):
    return layers.dispatch_ms(run)
