"""Phase-3 cost fold: seconds per job of the program's ``replay.fold``
spans, every policy's (``repro.cachesim.fastpath.accumulate_replay``,
beside each ``replay.<policy>`` span).  None where the program has no
such span."""
from bench.tracereduce import self_seconds


def read(ctx):
    s = self_seconds(ctx.trace, "replay.fold", ())
    return None if s is None else s / ctx.jobs
