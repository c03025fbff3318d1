#!/usr/bin/env python3
"""Readings behind the limit of ``correct``: the program's and the
control's rows against the plain reference, seed by seed.

    python3 bench/control.py --workload secv3.full --seeds 1 2 3 \
        --control-seeds 3

For each seed it builds the cell's trace, runs one job of the timed path
(``bench.harness.run_job``) and counts the rows that differ from the
reference (the lower reading).  For the first ``--control-seeds`` seeds
it also runs the control -- the same job with the phase-2 table program
computed in float32, the precision below the float64 that the
configurations state -- and counts its differing rows (the upper
reading).  One JSON line per seed.  It needs a TPU; the benchmark's own
runs never run it.
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


@contextlib.contextmanager
def float32_tables():
    """The program's stacked table build, run in float32: the jitted
    ``_cells_tables_kernel`` on float32 / int32 arguments in place of
    its float64 ones."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import batched

    def tables_f32(costs_cells, pi, nu, penalties, fno_cells, *, mesh=None):
        pi = np.atleast_2d(np.asarray(pi, np.float64))
        v, n = pi.shape
        c = np.atleast_2d(np.asarray(costs_cells)).shape[0]
        args = batched.cells_tables_args(costs_cells, pi, nu, penalties,
                                         fno_cells)
        args = [jnp.asarray(np.asarray(a), jnp.float32 if
                            np.asarray(a).dtype.kind == "f" else None)
                for a in args]
        out = np.asarray(batched._cells_tables_kernel(*args))
        return out[:c].reshape(c, v, 1 << n, n)

    saved = batched.selection_tables_cells_jax
    batched.selection_tables_cells_jax = tables_f32
    try:
        yield
    finally:
        batched.selection_tables_cells_jax = saved


def readings(workload: str, seeds, control_seeds: int, *,
             require_tpu: bool = True, requests=None, ref_workers=None):
    """Yield one dict per seed: rows compared, and the rows of the
    program and (for the first ``control_seeds`` seeds) of the control
    that differ from the reference."""
    from bench import harness
    from bench.gen import make_trace
    cell = harness.resolve(harness.load_manifest(), workload)
    if requests is not None:
        cell.requests = int(requests)
    device = harness.device_info(require_tpu, cell.chips)
    for k, seed in enumerate(seeds):
        t0 = time.perf_counter()
        tr = make_trace(cell.mix, cell.requests, seed,
                        residues=int(cell.system["n_caches"]))
        jobs = [harness.run_job(cell, tr)]
        if k < control_seeds:
            with float32_tables():
                jobs.append(harness.run_job(cell, tr))
        ref = harness.reference_rows(cell, tr, workers=ref_workers)
        off = harness.compare(jobs, ref)
        yield {"workload": workload, "seed": seed, "rows": len(ref),
               "program_rows_off": off[0],
               "control_rows_off": off[1] if len(off) > 1 else None,
               "device": device["kind"],
               "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root / "src"))
    from bench.harness import enable_compile_cache
    enable_compile_cache()
    for line in readings(args.workload, args.seeds, args.control_seeds):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
