"""Device time of every operation other than the ingest kernel (trace
name ``_ingest_frame_jit``), in ms a fused dispatch and a chip: both
models and their decodes, the admission scatter and the batch pools'
relayouts, over the window's dispatching ticks."""

INGEST = "_ingest_frame_jit"


def read(run):
    if run.red is None:
        return None
    dispatches = sum(1 for _, d in run.rec.ticks if d > 0)
    if dispatches == 0:
        return None
    rest = sum(t for name, t in run.red.op_s.items() if INGEST not in name)
    return 1e3 * rest / (dispatches * run.red.devices)
