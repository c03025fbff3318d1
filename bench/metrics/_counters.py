"""The program's ``fna_cal.*`` counters (``repro.obs``), for the
readers of counters."""

PREFIX = "fna_cal."


def fna_cal(ctx):
    """{name without the prefix: total} of the process, or None where
    the program keeps no such counters (a program without ``repro.obs``,
    or a window that replayed no ``fna_cal``)."""
    try:
        from repro.obs import counters
    except ImportError:
        return None
    c = {k[len(PREFIX):]: v for k, v in counters().items()
         if k.startswith(PREFIX)}
    return c or None
