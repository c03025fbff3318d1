"""Phase-3 replay: summed self seconds per job of the ``Simulator.run``
spans (``repro.cachesim.engine``, ``fastpath``, ``fna_cal_fast``)."""
from bench.tracereduce import self_seconds


def read(ctx):
    s = self_seconds(ctx.trace, "Simulator.run", ctx.span_names)
    return None if s is None else s / ctx.jobs
