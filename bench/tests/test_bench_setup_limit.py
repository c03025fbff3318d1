"""The set-up limit: a run whose set-up overruns it ends there, with exit
code 1, its threads' stacks on standard error and no result line; a run
whose set-up fits is not cut in its window.  And a cell's test sizes are
data: a cell without its file fails, naming the file.

Each run is a child process on the CPU (``require_tpu=False``) with a
3-s limit: ``faulthandler``'s watchdog ends the whole process, and is
one per process."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench.tests.sizes import cell_sizes

ROOT = Path(__file__).resolve().parents[2]
LIMIT_S = 3
SEED = 5

#: argv: workload, seed, window seconds, "slow" or "fast"
CHILD = """
import json, sys, time
import jax
from bench import harness
from bench.tests.sizes import cell_sizes

workload, seed, seconds, setup = sys.argv[1], int(sys.argv[2]), \\
    float(sys.argv[3]), sys.argv[4]
requests = cell_sizes(workload)["correct"]
if setup == "slow":
    def warm_up(cell, trace):
        time.sleep(60)
    harness.warm_up = warm_up
else:
    # compile the cell's program once, so that the timed set-up is fast
    from bench.gen import make_trace
    cell = harness.resolve(harness.load_manifest(), workload)
    harness.warm_up(cell, make_trace(cell.mix, requests, seed,
                                     residues=int(cell.system["n_caches"])))
out = harness.run_cell(workload, seed, seconds, False,
                       started=time.perf_counter(), require_tpu=False,
                       requests=requests, ref_workers=0,
                       setup_limit=%d)
print(json.dumps(out), flush=True)
""" % LIMIT_S


def run_child(setup: str, seconds: float, timeout: float):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, "secv3.full", str(SEED), str(seconds),
         setup], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=timeout)
    return proc, time.perf_counter() - t0


def result_lines(stdout: str) -> list:
    return [line for line in stdout.splitlines() if line.startswith("{")]


def test_overrun_set_up_ends_at_the_limit():
    proc, elapsed = run_child("slow", 1.0, timeout=120)
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert elapsed < 20
    assert f"bench: set-up must end within {LIMIT_S} s of the start" \
        in proc.stderr
    assert "Timeout" in proc.stderr
    assert "in warm_up" in proc.stderr and "in run_cell" in proc.stderr
    assert result_lines(proc.stdout) == []


def test_limit_is_cancelled_before_the_window():
    seconds = 2 * LIMIT_S
    proc, elapsed = run_child("fast", seconds, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "Timeout" not in proc.stderr
    (line,) = result_lines(proc.stdout)
    out = json.loads(line)
    # the window outlasts what the limit leaves after set-up
    assert out["metrics"]["setup_s"]["value"] < LIMIT_S < seconds
    assert out["correct"] and out["attempted"] >= 1
    assert out["compared"]["rows_off"] == {"value": 0, "limit": 0}


def test_cell_without_sizes_fails_naming_its_file():
    with pytest.raises(FileNotFoundError,
                       match=r"bench/tests/cells/no_such\.cell\.json"):
        cell_sizes("no_such.cell")
    assert cell_sizes("secv3.full")["correct"] == 2000
    assert cell_sizes("secv3.full")["control"] == 3000
