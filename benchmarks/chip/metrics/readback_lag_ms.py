"""Mean over the window's dispatches of the ``fused_dispatch`` span's end
less its device ``jit_fused`` end, in ms, on the clock the program shares
with the device trace: the host's wake-up after the device finishes
(``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    return spans.readback_lag_ms(run)
