"""Phase-1 LRU passes: seconds per job of the program's ``sweep.lru``
spans, one per cache per chunk (``repro.cachesim.systemstate``
``_lru_sweep``).  None where the program has no such span."""
from bench.tracereduce import self_seconds


def read(ctx):
    s = self_seconds(ctx.trace, "sweep.lru", ())
    return None if s is None else s / ctx.jobs
