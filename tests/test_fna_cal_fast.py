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


def test_ewma_path_matches_scalar_recurrence():
    from repro.core.estimator import ewma_path
    rng = np.random.default_rng(0)
    outcomes = (rng.random(500) < 0.4).astype(np.float64)
    g = 0.05
    e = 0.9
    path = ewma_path(e, outcomes, g)
    for t, a in enumerate(outcomes.tolist()):
        e = (1 - g) * e + g * a
        assert path[t] == e    # bit-identical, not approximately


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
