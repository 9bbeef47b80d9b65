"""Mean wall time of ``gw.tick()`` less its fused dispatch
(``FleetStep.last_dispatch_s``), in ms: the gateway's and engines' host
phases."""
from chipbench import layers


def read(run):
    return layers.host_ms_per_tick(run)
