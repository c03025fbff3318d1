"""Phase-3 replay of the calibrated policy: seconds per job of the
program's ``replay.fna_cal`` spans (``repro.cachesim.engine``
``DecisionPlan.replay`` around ``fna_cal_fast.fna_cal_selections``;
they hold no span of their own).  None where the program has no such
span."""
from bench.tracereduce import self_seconds


def read(ctx):
    s = self_seconds(ctx.trace, "replay.fna_cal", ())
    return None if s is None else s / ctx.jobs
