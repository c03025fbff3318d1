"""The calibrated replay's build phase: seconds per job of the speculation
table builds, from the program's ``fna_cal.build_ns`` counter
(``repro.cachesim.fna_cal_fast``).

A counter is a process total; it covers the window alone because set-up
(``warm_up``) runs no replay and the reference imports nothing of the
program.  None where the program has no such counter."""
from bench.metrics._counters import fna_cal


def read(ctx):
    c = fna_cal(ctx)
    return None if c is None else c["build_ns"] * 1e-9 / ctx.jobs
