"""How far the calibrated replay runs its cells in lockstep: the mean lanes
per verifier call, ``fna_cal.lane_chunks`` / ``fna_cal.rounds``, from the
program's counters (``repro.cachesim.fna_cal_fast``).  1 where every
``fna_cal`` cell replays alone; up to the group's ``fna_cal`` cells where
they share one sweep.

A counter is a process total; it covers the window alone because set-up
(``warm_up``) runs no replay and the reference imports nothing of the
program.  None where the program has no such counter."""
from bench.metrics._counters import fna_cal


def read(ctx):
    c = fna_cal(ctx)
    if c is None or not c.get("rounds"):
        return None
    return c["lane_chunks"] / c["rounds"]
