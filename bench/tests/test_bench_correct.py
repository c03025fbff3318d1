"""``correct`` has teeth: a sound run of each cell passes, and the run
fails with the timed path broken underneath, at a size a test run can
hold.

The harness's look for a chip is skipped (``require_tpu=False``); the
rest of a run is driven as on the chip: set-up job, window, reference in
this process."""
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness
from bench.tests.sizes import cell_sizes

WORKLOADS = tuple(w["name"] for w in harness.load_manifest()["workloads"])


def run(workload, seed=5):
    return harness.run_cell(workload, seed, 0.0, False,
                            started=time.perf_counter(), require_tpu=False,
                            requests=cell_sizes(workload)["correct"],
                            ref_workers=0)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload):
    out = run(workload)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] == 1
    assert list(out)[-1] == "compared"
    assert out["compared"]["rows_off"] == {"value": 0, "limit": 0}
    assert {"sim_req_per_s", "host_peak_mb", "setup_s"} <= set(out["metrics"])


def test_warm_up_leaves_nothing_to_compile():
    """Set-up's sweep and table build compile all a job runs, and count
    the sweeps a job computes."""
    from bench.gen import make_trace
    cell = harness.resolve(harness.load_manifest(), "secv3.full")
    cell.requests = cell_sizes("secv3.full")["correct"]
    tr = make_trace(cell.mix, cell.requests, 5,
                    residues=int(cell.system["n_caches"]))
    sweeps = harness.warm_up(cell, tr)
    assert sweeps == 1                  # a decision-side grid: one group
    before = harness.sweeps_computed()
    with harness.CompileCounter() as compiles:
        rows = harness.run_job(cell, tr)
    assert compiles.count == 0
    assert harness.sweeps_computed() - before == sweeps
    assert len(rows) == len(cell.values) * len(cell.policies)


def _state_unchanged(monkeypatch):
    """The sweep hands back a view history that never moves."""
    from repro.cachesim.systemstate import SystemTrace
    orig = SystemTrace.__dict__["compute"].__func__

    def frozen(cls, *args, **kwargs):
        st = orig(cls, *args, **kwargs)
        st.ver_per_req[:] = 0
        return st
    monkeypatch.setattr(SystemTrace, "compute", classmethod(frozen))


def _half_batch(monkeypatch):
    """Replays fold only the first half of the requests and scale their
    totals up to the whole trace."""
    from repro.cachesim import fastpath
    from repro.cachesim.simulator import SimResult
    orig = fastpath.accumulate_replay

    def half(res, st, selm, costs, miss_penalty):
        h = st.trace_len // 2
        part = SimpleNamespace(n=st.n, in_dj=st.in_dj[:h],
                               dj_all=st.dj_all[:h], pats=st.pats[:h],
                               trace_len=h)
        sub = orig(SimResult(policy=res.policy), part, selm[:h], costs,
                   miss_penalty)
        scale = st.trace_len / h
        res.total_cost += sub.total_cost * scale
        res.hits += round(sub.hits * scale)
        res.pos_accesses += round(sub.pos_accesses * scale)
        res.neg_accesses += round(sub.neg_accesses * scale)
        res.n_requests += st.trace_len
        return res
    monkeypatch.setattr(fastpath, "accumulate_replay", half)


def _answer_altered(monkeypatch):
    """The chip's table masks come back with one cell's rows inverted."""
    from repro.core import batched
    orig = batched.selection_tables_cells_jax

    def altered(*args, **kwargs):
        out = np.array(orig(*args, **kwargs))
        out[0] = ~out[0]
        return out
    monkeypatch.setattr(batched, "selection_tables_cells_jax", altered)


def _sweep_reused(monkeypatch):
    """A job hands back the previous job's sweep instead of its own."""
    from repro.cachesim.systemstate import SystemTrace
    orig = SystemTrace.__dict__["compute"].__func__
    memo = {}

    def reused(cls, sim, trace, *args, **kwargs):
        key = (trace.tobytes(), SystemTrace.system_key(sim.cfg))
        if key not in memo:
            memo[key] = orig(cls, sim, trace, *args, **kwargs)
        memo[key].plan_cache.clear()
        return memo[key]
    monkeypatch.setattr(SystemTrace, "compute", classmethod(reused))


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(workload, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run(workload)
    assert out["correct"] is False
    assert out["compared"]["rows_off"]["value"] > 0
    assert out["failed"] == out["attempted"]


def test_reused_sweep_fails_the_job(monkeypatch):
    _sweep_reused(monkeypatch)
    out = harness.run_cell("secv3.full", 5, 0.5, False,
                           started=time.perf_counter(), require_tpu=False,
                           requests=cell_sizes("secv3.full")["correct"],
                           ref_workers=0)
    assert out["attempted"] >= 1 and out["failed"] == out["attempted"]
    assert out["compared"]["sweep_or_compile_faults"]["value"] == \
        out["attempted"]
    assert out["correct"] is False
