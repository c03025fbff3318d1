"""Phase-2 wait for the chip: seconds per job of the program's
``tables.device`` spans, the table kernel's call from dispatch to the
host array (``repro.core.batched.selection_tables_cells_jax``).  None
where the program has no such span."""
from bench.tracereduce import self_seconds


def read(ctx):
    s = self_seconds(ctx.trace, "tables.device", ())
    return None if s is None else s / ctx.jobs
