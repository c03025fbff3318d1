"""Property-based policy invariants (hypothesis).

Analytic properties of the selection layer, checked on random small
instances (n <= 6) rather than fixed fixtures:

  * DS_PGM is EXACTLY the best prefix of the potential-gain order
    (including the empty prefix) — its by-construction guarantee, which
    holds unconditionally;
  * against the exact Eq. (10) minimiser it is never better than
    ``exhaustive`` and, in the paper's operating regime (unit-scale
    access costs, miss penalty orders of magnitude larger), never worse
    than the log(M) approximation factor.  The multiplicative factor is
    a REGIME bound, not universal: with access costs far below 1 or M
    comparable to a single access cost, adversarial instances exceed it
    (a cheap useless cache can head the potential-gain order and block
    the one good prefix), which is why the draws below mirror the
    paper's cost normalisation;
  * Theorem-7 degeneracy: with FN = 0 the false-negative-AWARE selector
    collapses onto the false-negative-OBLIVIOUS one (nu = 1, so
    negative-indication caches can never pay for themselves).

The bitmask twins (``ds_pgm_mask`` / ``exhaustive_mask``) are asserted
decision-identical to their list-returning originals on the same draws —
they are the scalar inner loop of the calibrated fast engine.

The module also carries the decision-plan layer's provider parity
properties: the exact batched HOCS mirror
(``repro.core.batched.hocs_fna_batched`` / ``hocs_selection_tables``)
against the scalar Algorithm-1 version loop it replaced, and the
calibrated engine's batched bridge tables (``selection_tables``
backend="numpy" / ``exhaustive_tables``) against per-pattern scalar
``mask_fn`` rows, across random (costs, rhos, M).  Seeded-random
backstops that run without hypothesis live in
``tests/test_engine_providers.py``.
"""
import math

import numpy as np
import pytest

hyp = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.batched import (  # noqa: E402
    exhaustive_tables,
    hocs_fna_batched,
    hocs_selection_tables,
    selection_tables,
)
from repro.core.model import EPS, CacheView, service_cost  # noqa: E402
from repro.core.policies import (  # noqa: E402
    cs_fna,
    cs_fno,
    ds_pgm,
    ds_pgm_mask,
    exhaustive,
    exhaustive_mask,
    hocs_fna,
)

MAX_N = 6

rhos_st = st.floats(0.0, 1.0, allow_nan=False, allow_infinity=False)


@st.composite
def instances(draw, cost_lo=0.05, cost_hi=5.0, m_lo=1.5, m_hi=1_000.0):
    n = draw(st.integers(1, MAX_N))
    cost_st = st.floats(cost_lo, cost_hi, allow_nan=False,
                        allow_infinity=False)
    costs = draw(st.lists(cost_st, min_size=n, max_size=n))
    rhos = draw(st.lists(rhos_st, min_size=n, max_size=n))
    M = draw(st.floats(m_lo, m_hi, allow_nan=False, allow_infinity=False))
    return costs, rhos, M


def _mask(sel) -> int:
    m = 0
    for j in sel:
        m |= 1 << j
    return m


@settings(max_examples=300, deadline=None)
@given(instances())
def test_ds_pgm_is_best_prefix(inst):
    """Unconditional, exact: DS_PGM returns the cheapest prefix of the
    potential-gain order (empty prefix included), it never beats the
    exhaustive optimum, and the optimum never beats skipping every
    cache."""
    costs, rhos, M = inst
    order = sorted(range(len(costs)),
                   key=lambda j: costs[j] /
                   -math.log(min(max(rhos[j], EPS), 1.0 - EPS)))
    best_prefix = min([M] + [service_cost(costs, rhos, M, order[:i + 1])
                             for i in range(len(order))])
    pgm = service_cost(costs, rhos, M, ds_pgm(costs, rhos, M))
    opt = service_cost(costs, rhos, M, exhaustive(costs, rhos, M))
    assert abs(pgm - best_prefix) <= 1e-9
    assert opt <= pgm + 1e-9
    assert opt <= M + 1e-9


@settings(max_examples=300, deadline=None)
@given(instances(cost_lo=1.0, cost_hi=5.0, m_lo=50.0, m_hi=1_000.0))
def test_ds_pgm_within_paper_bound_of_exhaustive(inst):
    """In the paper's regime — access costs on the unit scale, miss
    penalty orders of magnitude larger (Sec. V uses costs 1..3 against
    M = 50..500) — the prefix scan stays within the log(M) factor of
    the exact minimiser (empirical worst over 10^6 random draws: ~1.9x
    vs a 1 + ln M >= 4.9 budget)."""
    costs, rhos, M = inst
    opt = service_cost(costs, rhos, M, exhaustive(costs, rhos, M))
    pgm = service_cost(costs, rhos, M, ds_pgm(costs, rhos, M))
    assert pgm <= opt * (1.0 + math.log(M)) + 1e-9, (costs, rhos, M, pgm, opt)


@settings(max_examples=300, deadline=None)
@given(instances())
def test_mask_variants_decision_identical(inst):
    """The overhead-stripped bitmask twins pick the same subsets."""
    costs, rhos, M = inst
    assert ds_pgm_mask(costs, rhos, M) == _mask(ds_pgm(costs, rhos, M))
    assert exhaustive_mask(costs, rhos, M) == _mask(exhaustive(costs, rhos, M))


@st.composite
def zero_fn_views(draw):
    n = draw(st.integers(1, MAX_N))
    views = [CacheView(cost=draw(st.floats(0.05, 5.0)),
                       fp=draw(st.floats(0.0, 0.6)),
                       fn=0.0,
                       q=draw(st.floats(0.0, 0.95)))
             for _ in range(n)]
    inds = [draw(st.booleans()) for _ in range(n)]
    M = draw(st.floats(1.5, 1_000.0, allow_nan=False, allow_infinity=False))
    return views, inds, M


# ---------------------------------------------------------------------------
# Decision-plan providers: batched builders == the scalar loops they
# replaced (the fast engine's table layer, see repro.cachesim.engine)
#
# The batched builders carry the engine's documented near-tie caveat
# (float64 argmin / 1-ulp log differences vs the scalar EPS dead-band).
# Data-derived estimates never land in that measure-zero region, but
# hypothesis hunts for it with exact "nice" fractions — so each draw
# ASSUMEs away instances whose decision margin is inside the caveat
# (< 1e-9), and asserts EXACT parity on everything else.
# ---------------------------------------------------------------------------

def _geo_boundary_safe(m_eff: float, rho: float) -> bool:
    """The _argmin_geometric candidate shortlist {0, 1, floor(r*),
    ceil(r*), r_max} is log-derived; a continuous optimum within 1e-6 of
    an integer could flip floor/ceil under a 1-ulp log difference."""
    if rho <= EPS or rho >= 1.0 - EPS:
        return True                    # branch uses exact comparisons only
    l = math.log(1.0 / rho)
    r_cont = math.log(max(m_eff * l, EPS)) / l
    return abs(r_cont - round(r_cont)) > 1e-6


def _hocs_instance_safe(n: int, pi: float, nu: float, M: float) -> bool:
    if not _geo_boundary_safe(M, pi):
        return False
    for x in range(n + 1):
        r1 = hocs_fna(x, n, pi, nu, M)[1]
        residual = M * pi ** r1
        if residual > 1.0 and not _geo_boundary_safe(residual, nu):
            return False
    return True


@settings(max_examples=300, deadline=None)
@given(st.integers(1, 9), rhos_st, rhos_st,
       st.floats(1.5, 1_000.0, allow_nan=False, allow_infinity=False))
def test_hocs_fna_batched_matches_scalar_version_loop(n, pi, nu, M):
    """The float64 NumPy mirror reproduces the scalar Algorithm 1
    EXACTLY over every positive-indication count — it is the fast
    engine's HOCS table builder, so near-enough is not enough."""
    hyp.assume(_hocs_instance_safe(n, pi, nu, M))
    nx = np.arange(n + 1, dtype=np.int64)
    r0b, r1b = hocs_fna_batched(nx, n, pi, nu, M)
    for x in range(n + 1):
        assert (int(r0b[x]), int(r1b[x])) == hocs_fna(x, n, pi, nu, M), \
            (n, pi, nu, M, x)


@st.composite
def view_histories(draw, max_n=5, max_v=4):
    n = draw(st.integers(1, max_n))
    v = draw(st.integers(1, max_v))
    rows = st.lists(rhos_st, min_size=n, max_size=n)
    pi_v = draw(st.lists(rows, min_size=v, max_size=v))
    nu_v = draw(st.lists(rows, min_size=v, max_size=v))
    M = draw(st.floats(1.5, 1_000.0, allow_nan=False, allow_infinity=False))
    return np.asarray(pi_v), np.asarray(nu_v), M


@settings(max_examples=150, deadline=None)
@given(view_histories())
def test_hocs_selection_tables_match_scalar_version_loop(case):
    """Row (v, p) of the batched HOCS build == the scalar version loop
    the fast engine used to run: left-to-right pooled means,
    per-popcount (r0*, r1*), then the r1* cheapest positive plus r0*
    cheapest negative caches."""
    pi_v, nu_v, M = case
    v, n = pi_v.shape
    for vi in range(v):
        hyp.assume(_hocs_instance_safe(
            n, sum(pi_v[vi].tolist()) / n, sum(nu_v[vi].tolist()) / n, M))
    tab = hocs_selection_tables(pi_v, nu_v, M)
    for vi in range(v):
        pi_h = sum(pi_v[vi].tolist()) / n
        nu_h = sum(nu_v[vi].tolist()) / n
        r_by_nx = [hocs_fna(x, n, pi_h, nu_h, M) for x in range(n + 1)]
        for p in range(1 << n):
            pos = [j for j in range(n) if (p >> j) & 1]
            neg = [j for j in range(n) if not (p >> j) & 1]
            r0, r1 = r_by_nx[len(pos)]
            want = 0
            for j in pos[:r1] + neg[:r0]:
                want |= 1 << j
            assert tab[vi, p] == want, (vi, p)


def _clip(r: float) -> float:
    return min(max(r, EPS), 1.0 - EPS)


def _ds_pgm_row_safe(costs, rhos, M) -> bool:
    """Potential-gain keys separated (order stable under 1-ulp log
    drift) and a unique Eq. (10) winner by > 1e-9 (outside both the
    scalar dead-band and the batched evaluation error)."""
    n = len(costs)
    keys = sorted(costs[j] / -math.log(_clip(rhos[j])) for j in range(n))
    for a, b in zip(keys, keys[1:]):
        if 0.0 < b - a <= 1e-9 * max(abs(a), 1.0):
            return False
    order = sorted(range(n), key=lambda j: costs[j] / -math.log(_clip(rhos[j])))
    vals = [M]
    run_c, run_p = 0.0, 1.0
    for j in order:
        run_c += costs[j]
        run_p *= rhos[j]
        vals.append(run_c + M * run_p)
    vals = sorted(vals)
    return vals[1] - vals[0] > 1e-9


def _exhaustive_row_safe(costs, rhos, M) -> bool:
    """Unique-or-exactly-tied Eq. (10) minimum: subset values are
    evaluated IEEE-identically by the batched DP, so exact ties resolve
    to the same lowest mask on both sides; only near-ties inside the
    dead-band can diverge."""
    n = len(costs)
    vals = [M]
    for mask in range(1, 1 << n):
        c, p = 0.0, M
        for j in range(n):
            if mask >> j & 1:
                c += costs[j]
                p *= rhos[j]
        vals.append(c + p)
    vals = sorted(vals)
    gap = vals[1] - vals[0]
    return gap == 0.0 or gap > 1e-9


@st.composite
def bridge_instances(draw, max_n=4):
    n = draw(st.integers(1, max_n))
    cost_st = st.floats(0.05, 5.0, allow_nan=False, allow_infinity=False)
    costs = draw(st.lists(cost_st, min_size=n, max_size=n))
    rp = draw(st.lists(rhos_st, min_size=n, max_size=n))
    rn = draw(st.lists(rhos_st, min_size=n, max_size=n))
    M = draw(st.floats(1.5, 1_000.0, allow_nan=False, allow_infinity=False))
    return costs, rp, rn, M


@settings(max_examples=300, deadline=None)
@given(bridge_instances())
def test_batched_fna_cal_bridge_tables_match_scalar_mask_rows(inst):
    """The calibrated engine's batched speculation/bridge tables
    row-match the per-pattern scalar ``mask_fn`` calls they replaced,
    for both subroutines."""
    costs, rp, rn, M = inst
    n = len(costs)
    rows = []
    for p in range(1 << n):
        rhos = [rp[j] if (p >> j) & 1 else rn[j] for j in range(n)]
        hyp.assume(_ds_pgm_row_safe(costs, rhos, M))
        hyp.assume(_exhaustive_row_safe(costs, rhos, M))
        rows.append(rhos)
    pow2 = (1 << np.arange(n)).astype(np.int64)
    ds_tab = (selection_tables(costs, [rp], [rn], M, backend="numpy")
              .reshape(-1, n) @ pow2)
    ex_tab = exhaustive_tables(costs, [rp], [rn], M).reshape(-1)
    for p, rhos in enumerate(rows):
        assert ds_tab[p] == ds_pgm_mask(costs, rhos, M), (p, inst)
        assert ex_tab[p] == exhaustive_mask(costs, rhos, M), (p, inst)


@st.composite
def rho_matrix_instances(draw, max_n=5, max_b=6):
    n = draw(st.integers(1, max_n))
    b = draw(st.integers(1, max_b))
    cost_st = st.floats(0.05, 5.0, allow_nan=False, allow_infinity=False)
    costs = draw(st.lists(cost_st, min_size=n, max_size=n))
    rows = st.lists(rhos_st, min_size=n, max_size=n)
    rhos = draw(st.lists(rows, min_size=b, max_size=b))
    allowed = draw(st.lists(st.lists(st.booleans(), min_size=n, max_size=n),
                            min_size=b, max_size=b))
    M = draw(st.floats(1.5, 1_000.0, allow_nan=False, allow_infinity=False))
    return costs, rhos, allowed, M


def _restricted_row_safe(costs, rhos, allowed, M) -> bool:
    sub = [j for j in range(len(costs)) if allowed[j]]
    if not sub:
        return True                    # empty candidate set: both pick {}
    return _ds_pgm_row_safe([costs[j] for j in sub],
                            [rhos[j] for j in sub], M)


@settings(max_examples=200, deadline=None)
@given(rho_matrix_instances())
def test_rho_selection_tables_matches_ds_pgm_batched_x64(inst):
    """The NumPy float64 mirror and the jitted x64 ``ds_pgm_batched``
    agree EXACTLY on every row away from the ~1e-12 near-tie dead-band —
    the contract that lets the fast engine route any table build through
    either backend.  Checked with and without the CS_FNO candidate
    restriction (``allowed`` mask vs ``fno_mask``)."""
    import jax
    import jax.numpy as jnp

    from repro.core.batched import ds_pgm_batched, rho_selection_tables
    costs, rhos, allowed, M = inst
    for row, arow in zip(rhos, allowed):
        hyp.assume(_ds_pgm_row_safe(costs, row, M))
        hyp.assume(_restricted_row_safe(costs, row, arow, M))
    costs_a = np.asarray(costs, np.float64)
    rhos_a = np.asarray(rhos, np.float64)
    allow_a = np.asarray(allowed, bool)
    with jax.enable_x64(True):
        free = np.asarray(ds_pgm_batched(
            jnp.asarray(costs_a), jnp.asarray(rhos_a), float(M)))
        restricted = np.asarray(ds_pgm_batched(
            jnp.asarray(costs_a), jnp.asarray(rhos_a), float(M),
            fno_mask=jnp.asarray(allow_a.astype(np.int64))))
    assert np.array_equal(
        rho_selection_tables(costs_a, rhos_a, M), free), inst
    assert np.array_equal(
        rho_selection_tables(costs_a, rhos_a, M, allowed=allow_a),
        restricted), inst


@settings(max_examples=300, deadline=None)
@given(zero_fn_views())
def test_cs_fna_degenerates_to_cs_fno_without_false_negatives(case):
    """With FN = 0 every negative indication is truthful, nu = 1, and
    Algorithm 2's extra candidates can never reduce Eq. (10): CS_FNA's
    selection equals CS_FNO's on every instance (both subroutines)."""
    views, inds, M = case
    for alg in (ds_pgm, exhaustive):
        fna = cs_fna(views, inds, M, alg=alg)
        fno = cs_fno(views, inds, M, alg=alg)
        assert fna == fno, (views, inds, M, alg.__name__)
        # and the selection only ever touches positive-indication caches
        assert all(inds[j] for j in fna)
