"""The calibrated replay's useful verification: 100 x requests committed
by verification (``fna_cal.spec_committed``) / rows passed to the
verifier (``fna_cal.verified_rows``), from the program's counters
(``repro.cachesim.fna_cal_fast``).

A counter is a process total; it covers the window alone because set-up
(``warm_up``) runs no replay and the reference imports nothing of the
program.  None where the program has no such counter."""
from bench.metrics._counters import fna_cal


def read(ctx):
    c = fna_cal(ctx)
    if c is None or not c["verified_rows"]:
        return None
    return 100.0 * c["spec_committed"] / c["verified_rows"]
