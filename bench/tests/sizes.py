"""Each cell's test sizes, kept as data: ``bench/tests/cells/<workload>.json``
holds the trace lengths at which the cell's tests drive it, so a new cell
brings its sizes in a file of its own."""
import json
from pathlib import Path

CELLS = Path(__file__).resolve().parent / "cells"


def cell_sizes(workload: str) -> dict:
    """``{"correct": n, "control": n, "why": ...}`` of one cell: the trace
    length of its sound and faulted runs (``test_bench_correct``) and of
    its float32 control (``test_bench_control``).  A cell without a file
    fails, naming the file to add: a skip would hide an unchecked cell."""
    path = CELLS / f"{workload}.json"
    if not path.is_file():
        raise FileNotFoundError(
            f"no test sizes for cell {workload!r}: add "
            f"bench/tests/cells/{workload}.json with its \"correct\" and "
            f"\"control\" trace lengths")
    return json.loads(path.read_text())
