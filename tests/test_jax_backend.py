"""JAX/Pallas table-core differential suite.

The fast engine's table layer gained a jitted/Pallas backend this PR:

  * ``repro.kernels.subsetdp`` — the Eq. (10) subset-DP product as a
    row-tiled Pallas kernel (+ jnp mirror), BIT-EXACT with the NumPy
    oracle ``repro.core.batched._subset_dp`` by construction (the
    ascending-index sweep argument in ``kernels/subsetdp/ref.py``);
  * ``selection_tables_cells_jax`` — one jitted ``vmap(ds_pgm_batched)``
    over whole sweep-cell stacks, optionally sharded over the devices of
    ``launch.mesh.make_sweep_mesh()``.

NumPy stays the golden oracle.  The subset-DP paths assert tobytes-level
equality; the ds_pgm paths assert EXACT mask agreement away from the
~1e-12 near-tie dead-band (XLA FMA contraction can shift a prefix cost
by 1 ulp — see ``selection_tables_cells_jax``), and the end-to-end
differential replays every golden scenario through
``run_grid(backend="jax")`` expecting bit-identical SimResults.
"""
import dataclasses

import numpy as np
import pytest

from repro.cachesim import SimResult, get_scenario
from repro.cachesim.scenarios import GOLDEN_SCENARIOS
from repro.cachesim.sweep import run_grid
from repro.core.batched import (
    EPS,
    _subset_dp,
    ds_pgm_batched,
    exhaustive_tables,
    rho_exhaustive_tables,
    selection_tables,
    selection_tables_cells,
    selection_tables_cells_jax,
)
from repro.kernels.subsetdp import (
    default_row_block,
    subset_argmin,
    subset_dp,
    subset_dp_ref,
)

RESULT_FIELDS = tuple(f.name for f in dataclasses.fields(SimResult))


def _instance(rng, n, b):
    costs = rng.uniform(0.05, 5.0, n)
    rhos = rng.uniform(0.0, 1.0, (b, n))
    M = float(rng.uniform(1.5, 1000.0))
    return costs, rhos, M


# ---------------------------------------------------------------------------
# Subset-DP kernel: bit-exact vs the NumPy oracle (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 3, 5, 8, 11])
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_subset_dp_bit_exact_vs_oracle(n, backend):
    """Every [B, 2^n] subset value from the jitted mirror and the Pallas
    kernel (interpret mode) equals ``_subset_dp`` BIT-FOR-BIT — the
    ascending-sweep restructure makes the IEEE operation chains
    identical, so this is tobytes equality, not a tolerance."""
    rng = np.random.default_rng(100 + n)
    b = 3 if n > 8 else 37                  # off row-block sizes: pad path
    costs, rhos, M = _instance(rng, n, b)
    ref = _subset_dp(costs, rhos, M)
    got = subset_dp(costs, rhos, M, backend=backend, interpret=True)
    assert got.shape == ref.shape
    assert got.tobytes() == ref.tobytes(), (n, backend)


def test_subset_dp_eager_ref_bit_exact():
    """The eager jnp mirror itself (no jit, no pallas) is bit-exact —
    pinning the ascending-sweep argument independently of the kernel
    plumbing."""
    import jax
    rng = np.random.default_rng(7)
    costs, rhos, M = _instance(rng, 6, 19)
    ref = _subset_dp(costs, rhos, M)
    with jax.enable_x64(True):
        got = np.asarray(subset_dp_ref(costs, rhos, M))
    assert got.tobytes() == ref.tobytes()


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_subset_argmin_matches_rho_exhaustive_tables(backend):
    """The on-device masked argmin reproduces the NumPy enumeration's
    winning subset per row, with and without the CS_FNO ``allowed``
    restriction (subset values are bit-identical, and both argmins take
    the FIRST minimum in ascending-mask order)."""
    rng = np.random.default_rng(8)
    for n in (1, 3, 6, 9):
        costs, rhos, M = _instance(rng, n, 41)
        k = 1 << n
        want = rho_exhaustive_tables(costs, rhos, M)
        got = subset_argmin(costs, rhos, M, backend=backend, interpret=True)
        assert np.array_equal(
            ((got[:, None] >> np.arange(n)[None, :]) & 1).astype(bool),
            want), (n, backend)
        allowed = rng.integers(0, k, 41, dtype=np.int64)
        want = rho_exhaustive_tables(costs, rhos, M, allowed=allowed)
        got = subset_argmin(costs, rhos, M, allowed=allowed,
                            backend=backend, interpret=True)
        assert np.array_equal(
            ((got[:, None] >> np.arange(n)[None, :]) & 1).astype(bool),
            want), (n, backend, "allowed")


def test_rho_exhaustive_tables_backend_param():
    """``rho_exhaustive_tables(backend=...)`` routes through the kernel
    package and returns the same masks as the NumPy oracle."""
    rng = np.random.default_rng(9)
    costs, rhos, M = _instance(rng, 5, 23)
    ref = rho_exhaustive_tables(costs, rhos, M)
    for backend in ("jax", "pallas"):
        assert np.array_equal(
            rho_exhaustive_tables(costs, rhos, M, backend=backend), ref)


def test_exhaustive_tables_chunk_and_backend():
    """The chunked pattern-grid build is invariant to chunk size and
    backend (n = 10 exercises the raised n <= 12 dispatch tier), and
    the auto-sized default chunk is reachable from the engine provider
    via ``ExhaustiveTables.chunk_rows``."""
    from repro.cachesim.engine import ExhaustiveTables
    assert ExhaustiveTables.chunk_rows is None   # auto-size by default
    rng = np.random.default_rng(10)
    n, v = 10, 2
    costs = rng.uniform(0.05, 5.0, n)
    pi = rng.uniform(0.0, 1.0, (v, n))
    nu = rng.uniform(0.0, 1.0, (v, n))
    M = 250.0
    ref = exhaustive_tables(costs, pi, nu, M, fno=True)
    assert np.array_equal(
        exhaustive_tables(costs, pi, nu, M, fno=True, chunk=777), ref)
    assert np.array_equal(
        exhaustive_tables(costs, pi, nu, M, fno=True, backend="jax"), ref)
    # per-row twin agrees on the same grid (the n <= 16 tier)
    k = 1 << n
    pats = ((np.arange(k)[:, None] >> np.arange(n)[None, :]) & 1)
    rhos = np.where(pats[None, :, :] > 0,
                    pi[:, None, :], nu[:, None, :]).reshape(v * k, n)
    allowed = np.tile(np.arange(k, dtype=np.int64), v)
    pow2 = (1 << np.arange(n)).astype(np.int64)
    per_row = rho_exhaustive_tables(costs, rhos, M, allowed=allowed) @ pow2
    assert np.array_equal(per_row.reshape(v, k), ref)


def test_default_row_block_scales_down_with_n():
    assert default_row_block(1) == 256
    assert default_row_block(8) == 256
    assert default_row_block(12) == 16
    assert default_row_block(16) == 1
    assert default_row_block(20) == 1


# ---------------------------------------------------------------------------
# Stacked cells kernel: near-tie-gated mask agreement vs the NumPy mirror
# ---------------------------------------------------------------------------

def _near_tie_rows(costs_cells, pi, nu, penalties, margin=1e-9):
    """[C, V*K] bool: rows whose two best DS_PGM prefix values are
    within ``margin`` of each other (the only rows where the jitted
    path's 1-ulp FMA drift may legitimately flip the argmin)."""
    v, n = pi.shape
    k = 1 << n
    pats = ((np.arange(k)[:, None] >> np.arange(n)[None, :]) & 1)
    rhos = np.where(pats[None, :, :] > 0,
                    pi[:, None, :], nu[:, None, :]).reshape(v * k, n)
    out = np.zeros((len(costs_cells), v * k), bool)
    for ci, (costs, M) in enumerate(zip(costs_cells, penalties)):
        r = np.clip(rhos, EPS, 1.0 - EPS)
        order = np.argsort(costs[None, :] / -np.log(r), axis=1, kind="stable")
        csum = np.cumsum(np.take_along_axis(
            np.broadcast_to(costs, r.shape), order, 1), axis=1)
        lprod = np.cumsum(np.log(np.take_along_axis(r, order, 1)), axis=1)
        phi = np.concatenate(
            [np.full((v * k, 1), M), csum + M * np.exp(lprod)], axis=1)
        two = np.sort(phi, axis=1)[:, :2]
        out[ci] = (two[:, 1] - two[:, 0]) <= margin * np.maximum(
            np.abs(two[:, 0]), 1.0)
    return out


def test_cells_jax_matches_numpy_mirror_away_from_ties():
    """Every (cell, version, pattern) mask from the jitted stacked build
    equals the NumPy mirror except (at most) on rows flagged as near-tie
    dead-band — the tolerance-based differential of the issue."""
    rng = np.random.default_rng(11)
    n, v, c = 4, 6, 9
    pi = rng.uniform(0.0, 1.0, (v, n))
    nu = rng.uniform(0.0, 1.0, (v, n))
    costs_cells = rng.uniform(0.05, 5.0, (c, n))
    penalties = rng.uniform(5.0, 500.0, c)
    fno_cells = (np.arange(c) % 2).astype(bool)
    a = selection_tables_cells(costs_cells, pi, nu, penalties, fno_cells)
    b = selection_tables_cells_jax(costs_cells, pi, nu, penalties, fno_cells)
    assert a.shape == b.shape == (c, v, 1 << n, n)
    diff_rows = (a != b).any(axis=3).reshape(c, -1)
    ties = _near_tie_rows(costs_cells, pi, nu, penalties)
    assert not np.any(diff_rows & ~ties), \
        f"{int((diff_rows & ~ties).sum())} rows differ outside the dead-band"


def test_cells_jax_single_and_empty_cells():
    rng = np.random.default_rng(12)
    n, v = 3, 4
    pi = rng.uniform(0.0, 1.0, (v, n))
    nu = rng.uniform(0.0, 1.0, (v, n))
    empty = selection_tables_cells_jax(
        np.empty((0, n)), pi, nu, np.empty(0), np.empty(0, bool))
    assert empty.shape == (0, v, 1 << n, n)
    one = selection_tables_cells_jax(
        np.full((1, n), 2.0), pi, nu, [100.0], [True])
    ref = selection_tables(np.full(n, 2.0), pi, nu, 100.0, fno=True)
    assert np.array_equal(one[0], ref)


def test_cells_jax_sharded_equals_unsharded():
    """With a (possibly host-faked) multi-device mesh the sharded build
    returns exactly the single-device answer — cells are row-independent,
    so sharding (and its repeat-last-row padding) must be invisible.
    On a 1-device host ``make_sweep_mesh()`` is None and this reduces to
    a smoke test of the auto-selection path."""
    from repro.launch.mesh import make_sweep_mesh
    rng = np.random.default_rng(13)
    n, v, c = 3, 5, 7                 # 7 cells never divide a mesh evenly
    pi = rng.uniform(0.0, 1.0, (v, n))
    nu = rng.uniform(0.0, 1.0, (v, n))
    costs_cells = rng.uniform(0.05, 5.0, (c, n))
    penalties = rng.uniform(5.0, 500.0, c)
    fno_cells = (np.arange(c) % 2).astype(bool)
    plain = selection_tables_cells_jax(
        costs_cells, pi, nu, penalties, fno_cells)
    mesh = make_sweep_mesh()
    sharded = selection_tables_cells_jax(
        costs_cells, pi, nu, penalties, fno_cells, mesh=mesh)
    assert np.array_equal(plain, sharded)


def test_shard_cells_pads_and_reports_count():
    from repro.launch.mesh import make_sweep_mesh
    mesh = make_sweep_mesh()
    if mesh is None:
        pytest.skip("single-device host (set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    from repro.distributed.sharding import shard_cells
    size = mesh.shape["cells"]
    arrs = [np.arange(size + 1, dtype=np.float64),
            np.arange(2 * (size + 1), dtype=np.float64).reshape(size + 1, 2)]
    (a, b), count = shard_cells(arrs, mesh)
    assert count == size + 1
    assert a.shape[0] == b.shape[0] == 2 * size    # padded to a multiple
    assert np.asarray(a)[size + 1] == np.asarray(a)[size]  # repeat-last pad


# ---------------------------------------------------------------------------
# End-to-end golden differential: run_grid(backend="jax") == numpy backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", GOLDEN_SCENARIOS)
def test_run_grid_jax_backend_matches_numpy(name):
    """Every golden scenario replayed through the JAX table backend
    yields bit-identical SimResults to the NumPy backend on every
    (trace, cell, policy) — near-tie flips are possible in principle but
    never observed on the golden grids (which is the point of pinning
    them)."""
    sc = get_scenario(name)
    traces, values = sc.golden_grid()
    base = sc.config(engine="fast", **sc.golden_base)
    ref = run_grid(traces, base, sc.axis, values, policies=sc.policies)
    got = run_grid(traces, base, sc.axis, values, policies=sc.policies,
                   backend="jax")
    assert set(ref) == set(got)
    for key, cell in ref.items():
        for p, res in cell.items():
            for f in RESULT_FIELDS:
                assert getattr(got[key][p], f) == getattr(res, f), \
                    (name, key, p, f)


def test_prefetch_jax_stacks_single_job():
    """Unlike the NumPy path (which skips groups of < 2 jobs), the JAX
    prefetch seeds the cache even for a single (cell, policy) build —
    every table then comes off the one compiled path."""
    from repro.cachesim.engine import DsPgmTables, prefetch_tables
    from repro.cachesim.simulator import SimConfig, Simulator
    from repro.cachesim.systemstate import SystemTrace
    from repro.cachesim.traces import get_trace
    trace = get_trace("gradle", 2_000, seed=3)
    cfg = SimConfig(policy="fna", update_interval=200)
    system = SystemTrace.compute(Simulator(cfg), trace)
    prefetch_tables(system, [cfg], ["fna"])
    assert not system.plan_cache                  # numpy path: skipped
    prefetch_tables(system, [cfg], ["fna"], backend="jax")
    key = DsPgmTables().cache_key(cfg)
    assert key in system.plan_cache
    tab = system.plan_cache[key]
    v = system.pi_v.shape[0]
    assert tab.shape == (v * (1 << system.n),) and tab.dtype == np.int64


def test_ds_pgm_batched_all_ones_fno_mask_is_identity():
    """The cells kernel always passes a mask array (vmap needs one
    shape); an all-ones mask must therefore be an EXACT no-op."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(14)
    costs, rhos, M = _instance(rng, 5, 33)
    with jax.enable_x64(True):
        plain = np.asarray(ds_pgm_batched(
            jnp.asarray(costs), jnp.asarray(rhos), M))
        masked = np.asarray(ds_pgm_batched(
            jnp.asarray(costs), jnp.asarray(rhos), M,
            fno_mask=jnp.ones(rhos.shape, jnp.int64)))
    assert np.array_equal(plain, masked)
