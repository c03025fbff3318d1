"""Phase-1 sweep: self seconds per job of the ``SystemTrace.compute``
spans (``repro.cachesim.systemstate``)."""
from bench.tracereduce import self_seconds


def read(ctx):
    s = self_seconds(ctx.trace, "SystemTrace.compute", ctx.span_names)
    return None if s is None else s / ctx.jobs
