"""The 95th percentile of every window frame's turnaround, in ms: kept
beside the judged median, because stalls inside the fused dispatch (150
ms in most 20 s windows, 1-2 s in about one in ten) move it by tens of ms
to seconds."""
from chipbench import layers


def read(run):
    return layers.turnaround_ms(run, 95)
