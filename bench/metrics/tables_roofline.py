"""Table kernel: share of its roofline, in percent.  The least time is
the least bytes the window's table builds require
(``bench.roofline.table_bytes``) over the chip's peak HBM bandwidth
(``bench/peaks.json``); it is divided by the device time of the
``jit__cells_tables_kernel`` program's operations."""
from bench.roofline import table_seconds
from bench.tracereduce import module_op_seconds


def read(ctx):
    kernel = module_op_seconds(ctx.trace, ctx.tables_module)
    if not kernel or not ctx.tables:
        return None
    least = sum(table_seconds(c, v, n, ctx.device_kind)
                for c, v, n in ctx.tables)
    return 100.0 * least / kernel
