"""Phase-1 CBF event walks: seconds per job of the program's
``sweep.cbf_walk`` spans, one per cache per chunk
(``repro.cachesim.systemstate`` ``_cbf_event_walk``).  None where the
program has no such span."""
from bench.tracereduce import self_seconds


def read(ctx):
    s = self_seconds(ctx.trace, "sweep.cbf_walk", ())
    return None if s is None else s / ctx.jobs
