"""The calibrated replay's scalar share: 100 x requests replayed by the
scalar bridge (``fna_cal.bridged``) / requests replayed
(``fna_cal.requests``), from the program's counters
(``repro.cachesim.fna_cal_fast``).

A counter is a process total; it covers the window alone because set-up
(``warm_up``) runs no replay and the reference imports nothing of the
program.  None where the program has no such counter."""
from bench.metrics._counters import fna_cal


def read(ctx):
    c = fna_cal(ctx)
    if c is None or not c["requests"]:
        return None
    return 100.0 * c["bridged"] / c["requests"]
