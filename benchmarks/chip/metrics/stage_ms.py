"""Mean per window tick of the summed ``stage`` spans of every replica,
in ms: the deadline trim, the backlog pop and the copy of each frame into
the pinned staging buffer (``VisionServeEngine.stage_class``), from the
program's own spans (``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    return spans.stage_ms(run)
