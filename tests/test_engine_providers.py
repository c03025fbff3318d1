"""Decision-plan layer tests: registry dispatch + batched-table parity.

The provider registry (``repro.cachesim.engine``) replaced the fast
engine's ``if/elif`` policy ladder; these tests pin

  * which provider each configuration dispatches to (and that
    out-of-budget configurations dispatch to ``None`` — the reference
    fallback), plus registry extensibility;
  * seeded-random parity of the batched table builders against the
    scalar loops they replaced: ``hocs_fna_batched`` vs the scalar
    Algorithm-1 version loop, and the calibrated engine's batched bridge
    tables (``selection_tables`` backend="numpy" /
    ``exhaustive_tables``) vs per-pattern scalar ``mask_fn`` rows (the
    hypothesis-driven versions of these properties live in
    ``tests/test_policy_properties.py`` and skip when hypothesis is
    absent — these backstops always run);
  * the stacked cross-cell build (``selection_tables_cells``) slicing
    bit-identically to per-cell calls;
  * the ``sweep_records`` axis-name collision fix.
"""
import numpy as np

from repro.cachesim import SimConfig, SimResult
from repro.cachesim.engine import (
    DecisionPlan,
    PROVIDERS,
    plan_for,
    register_provider,
)
from repro.cachesim.sweep import axis_column, sweep_records
from repro.core.batched import (
    exhaustive_tables,
    exhaustive_tables_cells,
    hocs_fna_batched,
    hocs_selection_tables,
    hocs_selection_tables_cells,
    selection_tables,
    selection_tables_cells,
)
from repro.core.policies import ds_pgm_mask, exhaustive_mask, hocs_fna



# ---------------------------------------------------------------------------
# Registry dispatch
# ---------------------------------------------------------------------------

def _plan_name(**kw):
    plan = plan_for(SimConfig(**kw))
    return None if plan is None else plan.name


def test_registry_dispatch():
    """Every configuration lands on the documented provider; anything
    outside every budget lands on None (the reference fallback)."""
    assert _plan_name(policy="fna") == "ds_pgm"
    assert _plan_name(policy="fno") == "ds_pgm"
    assert _plan_name(policy="hocs", costs=(2.0, 2.0, 2.0)) == "hocs"
    assert _plan_name(policy="pi") == "pi"
    assert _plan_name(policy="fna_cal") == "fna_cal"
    assert _plan_name(policy="fna_cal", alg="exhaustive") == "fna_cal"
    assert _plan_name(policy="fna", alg="exhaustive", n_caches=4) == \
        "exhaustive"
    # the chunked batched build covers the full table budget (n <= 12):
    # configurations that used to fall through to the scalar loop at
    # 8 < n <= 12 now dispatch to the batched enumeration
    assert _plan_name(policy="fna", alg="exhaustive", n_caches=9) == \
        "exhaustive"
    assert _plan_name(policy="fno", alg="exhaustive", n_caches=12) == \
        "exhaustive"
    assert _plan_name(policy="fna_cal", alg="exhaustive", n_caches=9) == \
        "fna_cal"
    # out of every budget -> reference loop
    assert _plan_name(policy="fna", n_caches=13) is None
    assert _plan_name(policy="pi", n_caches=13) is None
    assert _plan_name(policy="fna_cal", alg="exhaustive", n_caches=13) is None


def test_register_provider_shadows_builtin():
    class Shadow(DecisionPlan):
        name = "shadow"

        def matches(self, cfg):
            return cfg.policy == "pi"

    shadow = Shadow()
    register_provider(shadow)
    try:
        assert plan_for(SimConfig(policy="pi")) is shadow
        assert _plan_name(policy="fna") == "ds_pgm"
    finally:
        PROVIDERS.remove(shadow)
    assert _plan_name(policy="pi") == "pi"


# ---------------------------------------------------------------------------
# Seeded-random parity backstops (the hypothesis-driven versions live in
# tests/test_policy_properties.py; these run even without hypothesis)
# ---------------------------------------------------------------------------

def test_hocs_batched_mirror_matches_scalar_seeded():
    rng = np.random.default_rng(11)
    for _ in range(200):
        n = int(rng.integers(1, 10))
        pi, nu = float(rng.uniform(0, 1)), float(rng.uniform(0, 1))
        M = float(rng.uniform(1.5, 1000.0))
        nx = np.arange(n + 1, dtype=np.int64)
        r0b, r1b = hocs_fna_batched(nx, n, pi, nu, M)
        for x in range(n + 1):
            assert (int(r0b[x]), int(r1b[x])) == hocs_fna(x, n, pi, nu, M), \
                (n, pi, nu, M, x)


def test_fna_cal_bridge_tables_match_scalar_seeded():
    rng = np.random.default_rng(12)
    for _ in range(60):
        n = int(rng.integers(1, 5))
        costs = rng.uniform(0.05, 5.0, n).tolist()
        rp = rng.uniform(0.0, 1.0, n).tolist()
        rn = rng.uniform(0.0, 1.0, n).tolist()
        M = float(rng.uniform(1.5, 1000.0))
        pow2 = (1 << np.arange(n)).astype(np.int64)
        ds_tab = (selection_tables(costs, [rp], [rn], M, backend="numpy")
                  .reshape(-1, n) @ pow2)
        ex_tab = exhaustive_tables(costs, [rp], [rn], M).reshape(-1)
        for p in range(1 << n):
            rhos = [rp[j] if (p >> j) & 1 else rn[j] for j in range(n)]
            assert ds_tab[p] == ds_pgm_mask(costs, rhos, M), (p, costs, M)
            assert ex_tab[p] == exhaustive_mask(costs, rhos, M), (p, costs, M)


# ---------------------------------------------------------------------------
# Stacked cross-cell build == per-cell builds
# ---------------------------------------------------------------------------

def test_selection_tables_cells_bit_identical_to_per_cell():
    rng = np.random.default_rng(3)
    n, v = 3, 23
    pi = rng.uniform(0.0, 1.0, (v, n))
    nu = rng.uniform(0.0, 1.0, (v, n))
    cells = [(rng.uniform(0.5, 5.0, n).tolist(),
              float(rng.uniform(10.0, 800.0)), bool(i % 2))
             for i in range(7)]
    stacked = selection_tables_cells(
        [c for c, _, _ in cells], pi, nu,
        [m for _, m, _ in cells], [f for _, _, f in cells])
    for i, (c, m, f) in enumerate(cells):
        assert np.array_equal(stacked[i], selection_tables(
            c, pi, nu, m, fno=f, backend="numpy")), i


def test_selection_tables_cells_chunked_matches_unchunked():
    """Tiny max_rows forces the per-chunk path; rows are independent so
    the output must not change."""
    rng = np.random.default_rng(4)
    n, v = 3, 5
    pi = rng.uniform(0.0, 1.0, (v, n))
    nu = rng.uniform(0.0, 1.0, (v, n))
    costs = [rng.uniform(0.5, 5.0, n).tolist() for _ in range(4)]
    pens = [50.0, 100.0, 200.0, 400.0]
    fnos = [False, True, False, True]
    full = selection_tables_cells(costs, pi, nu, pens, fnos)
    tiny = selection_tables_cells(costs, pi, nu, pens, fnos, max_rows=1)
    assert np.array_equal(full, tiny)


def test_exhaustive_tables_cells_bit_identical_to_per_cell():
    """The stacked subset-DP build (per-row penalty seeded into the DP
    product) must reproduce each per-cell exhaustive_tables call exactly
    — rows are independent and the penalty enters only the seed, so the
    IEEE operation order per row is unchanged."""
    rng = np.random.default_rng(5)
    n, v = 3, 11
    pi = rng.uniform(0.0, 1.0, (v, n))
    nu = rng.uniform(0.0, 1.0, (v, n))
    costs = rng.uniform(0.5, 5.0, n).tolist()
    pens = [10.0, 50.0, 100.0, 400.0, 900.0]
    for fno in (False, True):
        stacked = exhaustive_tables_cells(costs, pi, nu, pens, fno=fno)
        assert stacked.shape == (len(pens), v, 1 << n)
        for i, m in enumerate(pens):
            assert np.array_equal(
                stacked[i], exhaustive_tables(costs, pi, nu, m, fno=fno)), \
                (fno, i)


def test_exhaustive_tables_cells_chunked_matches_unchunked():
    rng = np.random.default_rng(6)
    n, v = 3, 4
    pi = rng.uniform(0.0, 1.0, (v, n))
    nu = rng.uniform(0.0, 1.0, (v, n))
    costs = rng.uniform(0.5, 5.0, n).tolist()
    pens = [25.0, 100.0, 500.0]
    full = exhaustive_tables_cells(costs, pi, nu, pens)
    tiny = exhaustive_tables_cells(costs, pi, nu, pens, chunk=1)
    assert np.array_equal(full, tiny)


def test_hocs_selection_tables_cells_matches_single_cell():
    """The C-cell tiling (np.tile/np.repeat row layout) must place each
    penalty's rows exactly where the single-cell build computes them."""
    rng = np.random.default_rng(7)
    n, v = 4, 9
    pi = rng.uniform(0.0, 1.0, (v, n))
    nu = rng.uniform(0.0, 1.0, (v, n))
    pens = [10.0, 75.0, 300.0, 1000.0]
    stacked = hocs_selection_tables_cells(pi, nu, pens)
    assert stacked.shape == (len(pens), v, 1 << n)
    for i, m in enumerate(pens):
        assert np.array_equal(stacked[i],
                              hocs_selection_tables(pi, nu, m)), i


# ---------------------------------------------------------------------------
# sweep_records: axis-name collision (satellite fix)
# ---------------------------------------------------------------------------

def test_sweep_records_prefixes_colliding_axis():
    """An axis label that collides with a SimResult.to_dict() key (or the
    trace column) must not be silently overwritten — it lands in a
    prefixed column instead."""
    res = SimResult(policy="fna", n_requests=7, total_cost=21.0, hits=3)
    grid = {("gradle", 123): {"fna": res}}
    for axis in ("mean_cost", "policy", "n", "trace"):
        assert axis_column(axis) == f"axis_{axis}"
        recs = sweep_records(grid, axis=axis)
        assert recs[0][f"axis_{axis}"] == 123
        # the result field keeps its own value
        assert recs[0]["policy"] == "fna"
        assert recs[0]["n"] == 7
        assert recs[0]["trace"] == "gradle"
    # a non-colliding axis keeps its bare name
    assert axis_column("miss_penalty") == "miss_penalty"
    assert sweep_records(grid, axis="miss_penalty")[0]["miss_penalty"] == 123
