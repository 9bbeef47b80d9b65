"""Mean over the window's dispatches of the device's ``jit_fused`` start
less its ``fused_dispatch`` span's start, in ms, on the clock the program
shares with the device trace: the argument upload, the host transposes
into the chip's layout and the launch (``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    return spans.upload_lag_ms(run)
