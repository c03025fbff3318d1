"""Table kernel: device milliseconds per job of the operations run
inside the ``jit__cells_tables_kernel`` program."""
from bench.tracereduce import module_op_seconds


def read(ctx):
    s = module_op_seconds(ctx.trace, ctx.tables_module)
    return None if not s else s / ctx.jobs * 1e3
