"""Mean per window tick of the ``fleet.commit`` span, in ms: the fused
call's state handed back to each replica and every ``commit_class`` (the
gate controller's replay, counters, ledger), from the program's own spans
(``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    return spans.commit_ms(run)
