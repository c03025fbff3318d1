"""Calibrated-policy fast engine + shared-SystemTrace tests.

The speculative segmented replay (``repro.cachesim.fna_cal_fast``) must
be a BIT-EXACT twin of the reference scalar loop for ``fna_cal`` across
workloads and calibration settings, and ``run_policies`` must compute the
policy-independent system sweep exactly once while leaving every result
unchanged.
"""
import dataclasses

import numpy as np
import pytest

from repro.cachesim import SimConfig, Simulator, SystemTrace, get_trace
from repro.cachesim.simulator import run_policies
from repro.cachesim.sweep import run_sweep, sweep_records
from repro.cachesim.traces import TRACES
import repro.cachesim.systemstate as systemstate

N = 8_000
ALL_POLICIES = ("fna", "fno", "pi", "hocs", "fna_cal")


def _assert_results_identical(ref, fast):
    assert fast.to_dict() == ref.to_dict()
    assert fast.total_cost == ref.total_cost
    for f in ("n_requests", "hits", "pos_accesses", "neg_accesses",
              "fn_events", "fn_opportunities", "fp_events",
              "fp_opportunities", "resident"):
        assert getattr(fast, f) == getattr(ref, f), f


def _run_pair(trace, **cfg_kw):
    base = SimConfig(cache_size=1_000, policy="fna_cal", **cfg_kw)
    ref = Simulator(dataclasses.replace(base, engine="reference")).run(trace)
    fast = Simulator(dataclasses.replace(base, engine="fast")).run(trace)
    return ref, fast


@pytest.mark.parametrize("trace_name", TRACES)
def test_fna_cal_fast_reference_parity(trace_name):
    trace = get_trace(trace_name, N, seed=7)
    ref, fast = _run_pair(trace, update_interval=200, est_interval=25)
    _assert_results_identical(ref, fast)


@pytest.mark.parametrize("trace_name", ("gradle", "wiki"))
@pytest.mark.parametrize("cfg_kw", [
    dict(update_interval=1_000, est_interval=50, cal_epsilon=0.005),
    dict(update_interval=64, est_interval=16, cal_epsilon=0.05,
         cal_min_obs=5),
    dict(update_interval=200, est_interval=25, cal_epsilon=0.0,
         cal_min_obs=1_000_000),   # pure-model blend: never leaves warmup
])
def test_fna_cal_parity_across_settings(trace_name, cfg_kw):
    """Exactness must hold from fresh to very stale indicators, across
    exploration rates, and in both blend regimes (the all-empirical steady
    state AND the model-blended warmup that never ends)."""
    trace = get_trace(trace_name, N, seed=3)
    ref, fast = _run_pair(trace, **cfg_kw)
    _assert_results_identical(ref, fast)


@pytest.mark.parametrize("n_caches", (3, 4))
def test_fna_cal_exhaustive_runs_fast_engine(n_caches):
    """The segmented engine's verification pass now has an exhaustive
    twin (the batched 2^n-subset enumeration), so ``alg="exhaustive"``
    runs the fast engine for n <= 8 — bit-exactly."""
    trace = get_trace("gradle", 3_000, seed=2)
    base = SimConfig(n_caches=n_caches, cache_size=1_000, policy="fna_cal",
                     alg="exhaustive", update_interval=200)
    ref = Simulator(dataclasses.replace(base, engine="reference")).run(trace)
    sim = Simulator(dataclasses.replace(base, engine="fast"))
    fast = sim.run(trace)
    _assert_results_identical(ref, fast)
    # the speculative replay really ran: the shared artifact is published
    assert isinstance(sim.last_system, SystemTrace)


def test_fna_cal_exhaustive_many_caches_stays_fast():
    """The chunked subset DP raised the exhaustive budget to the full
    table cap (n <= 12): a 9-cache calibrated+exhaustive run — which used
    to fall back to the reference loop — now runs the segmented fast path
    with identical results and a shared SystemTrace artifact.  (Past the
    cap, n > 12 still dispatches to None — pinned in
    ``tests/test_engine_providers.py::test_registry_dispatch``.)"""
    trace = get_trace("gradle", 1_500, seed=2)
    base = SimConfig(n_caches=9, cache_size=200, policy="fna_cal",
                     alg="exhaustive", update_interval=100)
    ref = Simulator(dataclasses.replace(base, engine="reference")).run(trace)
    sim = Simulator(dataclasses.replace(base, engine="fast"))
    fast = sim.run(trace)
    _assert_results_identical(ref, fast)
    assert getattr(sim, "last_system", None) is not None


def test_run_policies_single_sweep():
    """A multi-policy comparison performs EXACTLY ONE system sweep, and
    sharing changes no result: every policy matches both its independent
    fast run and the reference loop."""
    trace = get_trace("gradle", N, seed=7)
    base = SimConfig(cache_size=1_000, costs=(2.0, 2.0, 2.0),
                     update_interval=200, est_interval=25)
    before = systemstate.SWEEPS_COMPUTED
    shared = run_policies(trace, base, policies=ALL_POLICIES)
    assert systemstate.SWEEPS_COMPUTED - before == 1
    before = systemstate.SWEEPS_COMPUTED
    independent = run_policies(trace, base, policies=ALL_POLICIES,
                               share_system=False)
    assert systemstate.SWEEPS_COMPUTED - before == len(ALL_POLICIES)
    reference = run_policies(
        trace, dataclasses.replace(base, engine="reference"),
        policies=ALL_POLICIES)
    for p in ALL_POLICIES:
        _assert_results_identical(independent[p], shared[p])
        _assert_results_identical(reference[p], shared[p])


def test_system_trace_install_state_parity():
    """A simulator that consumes a shared SystemTrace finishes in exactly
    the end-of-run system state of the simulator that computed it."""
    trace = get_trace("gradle", N, seed=3)
    base = SimConfig(cache_size=1_000, update_interval=200, policy="fna")
    donor = Simulator(base)
    donor.run(trace)
    other = Simulator(dataclasses.replace(base, policy="fno"))
    other.run(trace, system=donor.last_system)
    for dn, on in zip(donor.nodes, other.nodes):
        assert list(dn.lru.keys()) == list(on.lru.keys())
        assert np.array_equal(dn.ind.cbf.counters, on.ind.cbf.counters)
        assert np.array_equal(dn.ind.stale, on.ind.stale)
        assert dn.ind.fp_est == on.ind.fp_est
        assert dn.ind.fn_est == on.ind.fn_est
        assert dn.version == on.version
        assert (dn._since_adv, dn._since_est) == \
            (on._since_adv, on._since_est)
    for dq, oq in zip(donor.q_est, other.q_est):
        assert (dq.q, dq.version, dq._count, dq._positives) == \
            (oq.q, oq.version, oq._count, oq._positives)


def test_system_trace_rejects_mismatches():
    trace = get_trace("gradle", 2_000, seed=1)
    base = SimConfig(cache_size=500, update_interval=200)
    donor = Simulator(base)
    donor.run(trace)
    st = donor.last_system
    # different system config
    with pytest.raises(ValueError):
        st.install(Simulator(dataclasses.replace(base, cache_size=100)),
                   trace)
    # different trace
    with pytest.raises(ValueError):
        st.install(Simulator(base), trace[:-1])
    # non-fresh target
    used = Simulator(base)
    used.run(trace)
    with pytest.raises(ValueError):
        st.install(used, trace)


def test_run_sweep_grid_matches_reference():
    """The sweep runner's grid cells equal independent reference runs."""
    trace = get_trace("gradle", 5_000, seed=4)
    base = SimConfig(cache_size=1_000)
    grid = run_sweep({"gradle": trace}, base, update_intervals=(100, 800),
                     policies=("fna", "fno", "fna_cal"))
    assert set(grid) == {("gradle", 100), ("gradle", 800)}
    for (name, interval), cell in grid.items():
        ref_cfg = dataclasses.replace(base, engine="reference",
                                      update_interval=interval)
        for p, res in cell.items():
            ref = Simulator(
                dataclasses.replace(ref_cfg, policy=p)).run(trace)
            _assert_results_identical(ref, res)
    recs = sweep_records(grid)
    assert len(recs) == 6
    assert {r["update_interval"] for r in recs} == {100, 800}


LANE_COUNTS = ("requests", "spec_committed", "verified_rows", "bridged")


def _counted(fn):
    """``fn()`` and the ``fna_cal.*`` counts it added."""
    from repro import obs
    before = obs.counters()
    out = fn()
    after = obs.counters()
    return out, {c: after.get(f"fna_cal.{c}", 0) -
                 before.get(f"fna_cal.{c}", 0)
                 for c in LANE_COUNTS + ("rounds", "lane_chunks")}


@pytest.mark.parametrize("n_req, alg, lanes", [
    (6_000, "ds_pgm", [dict(miss_penalty=p) for p in (25.0, 100.0, 1000.0)]),
    (3_000, "exhaustive", [dict(miss_penalty=p) for p in (25.0, 250.0)]),
    (3_000, "exhaustive", [dict(costs=(1.0, 2.0, 3.0)),
                           dict(costs=(2.0, 2.0, 2.0)),
                           dict(costs=(1.0, 2.0, 3.0), miss_penalty=500.0)]),
    (6_000, "ds_pgm", [dict(costs=(1.0, 2.0, 3.0)),
                       dict(costs=(3.0, 1.0, 2.0), miss_penalty=40.0),
                       dict(costs=(2.0, 2.0, 2.0), miss_penalty=400.0)]),
    (6_000, "ds_pgm", [dict(cal_min_obs=5), dict(cal_gamma=0.2),
                       dict(cal_epsilon=0.05), dict(cal_epsilon=0.0)]),
    (6_000, "ds_pgm", [dict(miss_penalty=25.0), dict(miss_penalty=1000.0)]),
    (1_500, "ds_pgm", [dict(cal_min_obs=1_000_000), dict(),
                       dict(miss_penalty=500.0)]),
], ids=["penalties", "exhaustive_penalties", "exhaustive_costs", "costs",
        "calibration", "far_apart", "model_view_lane"])
def test_lockstep_lanes_equal_lone_lanes(n_req, alg, lanes):
    """The lockstep engine over R lanes equals R lone runs bit for bit:
    every lane's masks, and the summed counts — whatever the lanes differ
    in, however many rounds apart they finish, and with a lane that never
    leaves the model-view blend."""
    from repro.cachesim.fna_cal_fast import fna_cal_lanes, fna_cal_selections
    trace = get_trace("gradle", n_req, seed=3)
    base = SimConfig(cache_size=500, policy="fna_cal", alg=alg,
                     update_interval=200, est_interval=25)
    donor = Simulator(base)
    donor.run(trace)
    st = donor.last_system
    cfgs = [dataclasses.replace(base, **kw) for kw in lanes]
    alone, alone_n = _counted(lambda: [
        fna_cal_selections(Simulator(cfg), st) for cfg in cfgs])
    together, together_n = _counted(lambda: fna_cal_lanes(cfgs, st))
    for a, t in zip(alone, together):
        assert np.array_equal(a, t)
    assert {c: together_n[c] for c in LANE_COUNTS} == \
        {c: alone_n[c] for c in LANE_COUNTS}
    assert together_n["spec_committed"] + together_n["bridged"] == \
        len(cfgs) * n_req
    assert alone_n["rounds"] == alone_n["lane_chunks"] == \
        together_n["lane_chunks"] > together_n["rounds"]


def test_run_cells_lockstep_group_matches_reference(tmp_path, monkeypatch):
    """A ``run_cells`` group with several fna_cal cells replays them in
    lockstep, gives the reference engine's rows, and stores the group's
    decision tables and nothing else: no selections, no hand-over."""
    from repro.cachesim.engine import (FnaCalSegmented, run_cells,
                                       table_keys_for)
    from repro.cachesim.store import ArtifactStore
    trace = get_trace("gradle", 4_000, seed=6)
    base = SimConfig(cache_size=500, update_interval=200, est_interval=25)
    cfgs = [dataclasses.replace(base, miss_penalty=p)
            for p in (25.0, 100.0, 1000.0)]
    policies = ("fna", "fna_cal", "hocs")
    seen = []
    lone = FnaCalSegmented.selections

    def spy(self, sim, st):
        seen.append((len(st.fna_cal_lanes), len(st.fna_cal_selections)))
        return lone(self, sim, st)

    monkeypatch.setattr(FnaCalSegmented, "selections", spy)
    store = ArtifactStore(tmp_path)
    out = run_cells(trace, cfgs, policies, store=store)
    # the first cell finds all three pending and runs them; the others
    # take what it left
    assert seen == [(3, 0), (0, 2), (0, 1)]
    for cfg, cell in zip(cfgs, out):
        for p in policies:
            ref = Simulator(dataclasses.replace(
                cfg, policy=p, engine="reference")).run(trace)
            _assert_results_identical(ref, cell[p])
    tables = [e for e in store.entries() if e[1] == "tables"]
    assert len(tables) == len(table_keys_for(cfgs, policies)) == 6
    digest = store.trace_digest(trace)
    sys_key = SystemTrace.system_key(base)
    for cfg in cfgs:
        key = FnaCalSegmented.lane_key(cfg)
        assert store.load_table(digest, sys_key, key) is None
    st = store.load_sweep(trace, sys_key)
    assert not st.fna_cal_lanes and not st.fna_cal_selections


def test_ewma_path_matches_scalar_recurrence():
    """Several trajectories in one pass, empty ones included, each
    bit-identical to the per-probe scalar recurrence."""
    from repro.core.estimator import ewma_paths
    rng = np.random.default_rng(0)
    counts = [500, 0, 37, 1, 0, 120]
    gammas = [0.05, 0.2, 0.05, 0.5, 0.01, 0.3]
    e0s = [0.9, 0.5, 0.1, 0.77, 0.3, 0.42]
    outcomes = (rng.random(sum(counts)) < 0.4).astype(np.float64)
    path = ewma_paths(e0s, outcomes, counts, gammas)
    want, lo = [], 0
    for e, c, g in zip(e0s, counts, gammas):
        want.append(e)
        for a in outcomes[lo:lo + c].tolist():
            e = (1 - g) * e + g * a
            want.append(e)
        lo += c
    assert path.tolist() == want    # bit-identical, not approximately


def test_rho_selection_tables_matches_scalar_and_jax():
    """The NumPy float64 verification path agrees with both the scalar
    DS_PGM and the JAX batched path on random rho matrices."""
    import jax
    import jax.numpy as jnp

    from repro.core.batched import ds_pgm_batched, rho_selection_tables
    from repro.core.policies import ds_pgm

    rng = np.random.default_rng(5)
    costs = [1.0, 2.0, 3.0, 1.5]
    rhos = rng.uniform(0.0, 1.0, (257, 4))
    m = 100.0
    mask = rho_selection_tables(costs, rhos, m)
    for i in range(rhos.shape[0]):
        assert sorted(np.nonzero(mask[i])[0]) == \
            ds_pgm(costs, rhos[i].tolist(), m), i
    with jax.enable_x64(True):
        jmask = np.asarray(ds_pgm_batched(
            jnp.asarray(np.asarray(costs, np.float64)),
            jnp.asarray(rhos), m))
    assert np.array_equal(mask, jmask)


def test_recency_trace_vectorisation_bit_identical():
    from repro.cachesim.traces import _recency_trace_ref, recency_trace
    for n, seed, kw in ((1, 0, {}), (4_000, 7, {}),
                        (6_000, 1, dict(p_new=0.35, window=2048)),
                        (3_000, 9, dict(p_new=0.05, window=512)),
                        (3_000, 2, dict(p_new=0.9, window=128))):
        assert np.array_equal(recency_trace(n, seed=seed, **kw),
                              _recency_trace_ref(n, seed=seed, **kw)), \
            (n, seed, kw)
