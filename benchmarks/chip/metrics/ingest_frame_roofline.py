"""Percent of the roofline reached by the ingest kernel (trace name
``_ingest_frame_jit``): the least time the window's ingest work needs over
the kernel's device time."""
from chipbench import layers


def read(run):
    return layers.ingest_roofline(run, "_ingest_frame_jit")
