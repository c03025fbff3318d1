"""The calibrated replay's trajectory phase: seconds per job of the
exact-state trajectories (probe cumsums and EWMA paths up to the rho
matrix), from the program's ``fna_cal.trajectory_ns`` counter
(``repro.cachesim.fna_cal_fast``).

A counter is a process total; it covers the window alone because set-up
(``warm_up``) runs no replay and the reference imports nothing of the
program.  None where the program has no such counter."""
from bench.metrics._counters import fna_cal


def read(ctx):
    c = fna_cal(ctx)
    return None if c is None else c["trajectory_ns"] * 1e-9 / ctx.jobs
