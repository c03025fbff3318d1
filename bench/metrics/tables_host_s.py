"""Phase-2 tables on the host: self seconds per job of the
``prefetch_tables`` spans (``repro.cachesim.engine``): staging, the wait
for the chip, the transfer back and the packing into selection codes."""
from bench.tracereduce import self_seconds


def read(ctx):
    s = self_seconds(ctx.trace, "prefetch_tables", ctx.span_names)
    return None if s is None else s / ctx.jobs
