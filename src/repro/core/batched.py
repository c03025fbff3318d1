"""Vectorised JAX twin of the paper's math — the production router path.

Everything operates on a BATCH of requests at once so the serving router
can make thousands of FNA cache-selection decisions per step on-device,
fed directly by the Pallas Bloom-probe kernel (kernels/bloom).

Shapes: B = batch of requests, N = caches.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

EPS = 1e-12

# The exhaustive dispatch tiers (single source of truth for the fast
# engine):
#   * n <= MAX_EXHAUSTIVE_TABLE_CACHES: the batched table build
#     (``exhaustive_tables``) — chunked so the [rows, 2^n] subset matrix
#     never exceeds ~EXHAUSTIVE_CHUNK_ELEMS float64 elements, which makes
#     the full engine budget (``engine.MAX_TABLE_CACHES`` = 12) memory-
#     safe; beyond 12 the [V * 2^n] table itself outgrows the replay.
#   * n <= 16: the per-row enumeration (``rho_exhaustive_tables``) for
#     callers that chunk their own rows (the calibrated engine verifies
#     <= 256-row segments at a time).
#   * n > 16: nowhere — 2^n subset values per row stop being representable
#     work; the simulator falls back to the reference loop.
MAX_EXHAUSTIVE_TABLE_CACHES = 12
#: float64 elements per exhaustive DP chunk (rows * 2^n); ~32 MB
EXHAUSTIVE_CHUNK_ELEMS = 1 << 22


def exclusions(h, fp, fn) -> Tuple[jax.Array, jax.Array]:
    """Eqs. (1)-(3), elementwise."""
    q = h * (1.0 - fn) + (1.0 - h) * fp
    pi = jnp.clip(fp * (1.0 - h) / jnp.maximum(q, EPS), 0.0, 1.0)
    nu = jnp.clip((1.0 - fp) * (1.0 - h) / jnp.maximum(1.0 - q, EPS), 0.0, 1.0)
    return pi, nu


def hit_from_q(q, fp, fn):
    denom = 1.0 - fp - fn
    return jnp.clip((q - fp) / jnp.where(jnp.abs(denom) < EPS, 1.0, denom), 0.0, 1.0)


def rho_matrix(indications, q, fp, fn) -> jax.Array:
    """[B,N] rho_j per request: pi_j on positive, nu_j on negative."""
    h = hit_from_q(q, fp, fn)
    pi, nu = exclusions(h, fp, fn)
    return jnp.where(indications > 0, pi[None, :], nu[None, :])


def ds_pgm_batched(costs, rhos, miss_penalty, *, fno_mask=None) -> jax.Array:
    """Batched DS_PGM prefix evaluation.

    costs: [N] shared, or [B,N] per row (a stacked batch of decision
    cells); rhos: [B,N]; miss_penalty: scalar, or [B] per row; optional
    fno_mask [B,N] (1 = cache may be accessed; CS_FNO passes the
    positive-indication mask, CS_FNA all-ones).  Every operation is
    row-local, so a row's mask is independent of what else shares the
    batch — the decision-plan engine relies on this to stack whole sweep
    cells into one call.  Returns a selection mask [B,N] (bool).
    """
    b, n = rhos.shape
    r = jnp.clip(rhos, EPS, 1.0 - EPS)
    costs = jnp.asarray(costs)
    costs_b = jnp.broadcast_to(costs, (b, n)) if costs.ndim == 1 else costs
    m = jnp.asarray(miss_penalty)
    m_b = jnp.broadcast_to(m, (b,)) if m.ndim == 0 else m
    key = costs_b / -jnp.log(r)                             # [B,N]
    if fno_mask is not None:
        key = jnp.where(fno_mask > 0, key, jnp.inf)         # excluded -> last
    order = jnp.argsort(key, axis=1)                        # ascending
    c_sorted = jnp.take_along_axis(costs_b, order, 1)
    r_sorted = jnp.take_along_axis(r, order, 1)
    if fno_mask is not None:
        allowed = jnp.take_along_axis(fno_mask > 0, order, 1)
        c_sorted = jnp.where(allowed, c_sorted, jnp.inf)    # never pick excluded
        r_sorted = jnp.where(allowed, r_sorted, 1.0)
    csum = jnp.cumsum(c_sorted, axis=1)
    lprod = jnp.cumsum(jnp.log(r_sorted), axis=1)
    # prefix costs phi(P_i), i = 0..n (0 = empty set)
    phi = jnp.concatenate(
        [m_b[:, None].astype(csum.dtype),
         csum + m_b[:, None] * jnp.exp(lprod)], axis=1)     # [B, N+1]
    best = jnp.argmin(phi, axis=1)                          # prefix length
    pick_sorted = jnp.arange(n)[None, :] < best[:, None]    # [B,N] in sorted order
    # scatter back to cache order
    mask = jnp.take_along_axis(
        pick_sorted, jnp.argsort(order, axis=1), axis=1)
    return mask


def selection_tables(costs, pi, nu, miss_penalty, *, fno: bool = False,
                     backend: str = "jax") -> np.ndarray:
    """[V, 2^n, n] DS_PGM decision tables over ALL indication patterns for
    a whole batch of V view versions at once.

    ``pi``/``nu`` are [V, n] (or [n], treated as V=1) exclusion
    probabilities; row (v, p) holds the selection mask of view version v
    for the indication pattern whose bit j is ``(p >> j) & 1``.
    ``fno=True`` restricts candidates to positive-indication caches
    (CS_FNO).  Evaluated in float64 (x64) to match the scalar
    :func:`repro.core.ds_pgm` path — the simulator fast engine batches
    its entire version history into one call here.  Parity with the
    scalar path is exact unless two prefix costs coincide to within the
    scalar EPS dead-band (~1e-12): this path evaluates the Eq. (10)
    product as exp(cumsum(log .)) and takes a plain argmin; see the
    parity caveat in ``repro.cachesim.fastpath``.

    ``backend="numpy"`` routes through :func:`rho_selection_tables` — the
    float64 NumPy mirror of :func:`ds_pgm_batched` — which skips the JAX
    dispatch overhead entirely; the calibrated fast engine uses it for
    its many small per-segment table builds.  CS_FNO is expressed there
    as the per-row ``allowed`` candidate mask.
    """
    pi = np.atleast_2d(np.asarray(pi, np.float64))
    nu = np.atleast_2d(np.asarray(nu, np.float64))
    v, n = pi.shape
    k = 1 << n
    pat_bits = (np.arange(k)[:, None] >> np.arange(n)[None, :]) & 1   # [K,n]
    rhos = np.where(pat_bits[None, :, :] > 0,
                    pi[:, None, :], nu[:, None, :]).reshape(v * k, n)
    if backend == "numpy":
        allowed = np.tile(pat_bits.astype(bool), (v, 1)) if fno else None
        return rho_selection_tables(
            costs, rhos, miss_penalty, allowed=allowed).reshape(v, k, n)
    with jax.enable_x64(True):
        mask = ds_pgm_batched(
            jnp.asarray(np.asarray(costs, np.float64)),
            jnp.asarray(rhos), float(miss_penalty),
            fno_mask=jnp.asarray(np.tile(pat_bits, (v, 1))) if fno else None)
        out = np.asarray(mask)
    return out.reshape(v, k, n)


def selection_tables_cells(costs_cells, pi, nu, penalties, fno_cells,
                           *, max_rows: int = 1 << 20) -> np.ndarray:
    """[C, V, 2^n, n] DS_PGM decision tables for SEVERAL decision cells
    against ONE shared view history, in as few batched calls as memory
    allows.

    A decision-side sweep axis (miss penalty, access-cost vector, policy)
    leaves the system evolution — and with it the whole [V, n] (pi, nu)
    view history — untouched, so the only thing that varies across its
    cells is the (costs, miss_penalty, CS_FNO) triple each row is
    evaluated under.  This stacks all C cells' (version x pattern) grids
    into one :func:`rho_selection_tables` evaluation — the float64 NumPy
    mirror of ``ds_pgm_batched`` — with per-row costs/penalties (chunked
    to ``max_rows`` rows so the [rows, n] matrices stay bounded).  Rows
    are evaluated independently, so cell c's slice is bit-identical to a
    per-cell ``selection_tables(..., backend="numpy")`` call.  It runs on
    the host whatever JAX's default device is: this is the oracle that
    the jitted :func:`selection_tables_cells_jax` is checked against.

    ``costs_cells``: [C, n]; ``penalties``: [C]; ``fno_cells``: [C] bool.
    """
    pi = np.atleast_2d(np.asarray(pi, np.float64))
    nu = np.atleast_2d(np.asarray(nu, np.float64))
    v, n = pi.shape
    k = 1 << n
    costs_cells = np.asarray(costs_cells, np.float64)
    penalties = np.asarray(penalties, np.float64)
    fno_cells = np.asarray(fno_cells, bool)
    c = costs_cells.shape[0]
    pat_bits = (np.arange(k)[:, None] >> np.arange(n)[None, :]) & 1   # [K,n]
    rhos = np.where(pat_bits[None, :, :] > 0,
                    pi[:, None, :], nu[:, None, :]).reshape(v * k, n)
    pat_tiled = np.tile(pat_bits > 0, (v, 1))                         # [V*K,n]
    ones = np.ones_like(pat_tiled)
    out = np.empty((c, v * k, n), dtype=bool)
    per_call = max(1, max_rows // (v * k))        # whole cells per chunk
    for lo in range(0, c, per_call):
        hi = min(lo + per_call, c)
        cc = hi - lo
        mask = rho_selection_tables(
            np.repeat(costs_cells[lo:hi], v * k, axis=0),
            np.tile(rhos, (cc, 1)), np.repeat(penalties[lo:hi], v * k),
            allowed=np.concatenate(
                [pat_tiled if f else ones for f in fno_cells[lo:hi]]))
        out[lo:hi] = mask.reshape(cc, v * k, n)
    return out.reshape(c, v, k, n)


@jax.jit
def _cells_tables_kernel(costs_u, fno_u, group_idx, penalties, pi, nu):
    """[C, V*2^n, n] bool masks: the grouped two-stage evaluation of
    :func:`ds_pgm_batched` over C decision cells against one shared
    [V, n] (pi, nu) view history.

    The DS_PGM potential-gain order ``c_j / -log(rho_j)`` does not
    depend on the miss penalty — on a penalty-axis grid (the paper's
    Fig. 3) every cell with the same (costs, CS_FNO) pair shares one
    sort.  Stage 1 therefore sorts only the G UNIQUE (costs, fno)
    groups (``costs_u`` [G, n], ``fno_u`` [G]); stage 2 gathers each
    cell's group (``group_idx`` [C]) and finishes with its own penalty
    (prefix costs, argmin, scatter back to cache order).  Both stages
    replicate :func:`ds_pgm_batched`'s operation chain exactly — the
    one deviation is inverting the sort permutation by scatter instead
    of a second argsort, which is the same bijection computed exactly.

    The pattern grid / rho stack is rebuilt ON DEVICE from the
    replicated (pi, nu) pair, so only [G, .] / [C, .] cell parameters
    travel along the sharded cell axis.  ``fno_u`` selects per group
    between the CS_FNO pattern mask and all-ones; an all-ones mask is
    an exact identity in the chain (``where(True, x, .)``).
    """
    v, n = pi.shape
    k = 1 << n
    pats = ((jnp.arange(k, dtype=jnp.int32)[:, None]
             >> jnp.arange(n, dtype=jnp.int32)[None, :]) & 1)     # [K, n]
    rhos = jnp.where(pats[None, :, :] > 0,
                     pi[:, None, :], nu[:, None, :]).reshape(v * k, n)
    r = jnp.clip(rhos, EPS, 1.0 - EPS)
    pat_rows = jnp.tile(pats, (v, 1))                             # [V*K, n]
    ones = jnp.ones_like(pat_rows)
    rows = v * k

    def sort_group(costs, fno):
        # ds_pgm_batched's sort-dependent half, penalty-free
        costs_b = jnp.broadcast_to(costs, (rows, n))
        allowed_rows = jnp.where(fno, pat_rows, ones) > 0
        key = jnp.where(allowed_rows, costs_b / -jnp.log(r), jnp.inf)
        order = jnp.argsort(key, axis=1)
        c_sorted = jnp.take_along_axis(costs_b, order, 1)
        r_sorted = jnp.take_along_axis(r, order, 1)
        allowed = jnp.take_along_axis(allowed_rows, order, 1)
        c_sorted = jnp.where(allowed, c_sorted, jnp.inf)
        r_sorted = jnp.where(allowed, r_sorted, 1.0)
        return (order, jnp.cumsum(c_sorted, axis=1),
                jnp.cumsum(jnp.log(r_sorted), axis=1))

    order_g, csum_g, lprod_g = jax.vmap(sort_group)(costs_u, fno_u)

    def finish_cell(gi, m):
        order, csum, lprod = order_g[gi], csum_g[gi], lprod_g[gi]
        phi = jnp.concatenate(
            [jnp.full((rows, 1), m, csum.dtype),
             csum + m * jnp.exp(lprod)], axis=1)                  # [rows, n+1]
        best = jnp.argmin(phi, axis=1)
        pick_sorted = jnp.arange(n)[None, :] < best[:, None]
        # back to cache order: cache j is picked iff its sorted slot is
        # (one-hot contraction — the inverse permutation, exactly, and
        # vectorizable where an XLA:CPU scatter would scalar-loop)
        onehot = order[:, :, None] == jnp.arange(n)[None, None, :]
        return jnp.any(pick_sorted[:, :, None] & onehot, axis=1)

    return jax.vmap(finish_cell)(group_idx, penalties)


def selection_tables_cells_jax(costs_cells, pi, nu, penalties, fno_cells,
                               *, mesh=None) -> np.ndarray:
    """[C, V, 2^n, n] decision tables for C cells — the jitted (and
    optionally device-sharded) twin of :func:`selection_tables_cells`.

    One compiled computation evaluates every (cell x version x pattern)
    row.  The potential-gain sort does not depend on the miss penalty,
    so cells are deduplicated host-side into unique (costs, fno) groups:
    the sort/prefix stage runs once per group and each cell finishes
    with its own penalty — on a penalty-axis grid (the paper's Fig. 3)
    that is one sort for all eight penalty cells per CS_FNO flag.  With
    a ``mesh`` (see ``repro.launch.mesh.make_sweep_mesh``) both the
    group and cell axes are padded to a multiple of the mesh size and
    row-sharded across devices while the shared (pi, nu) history is
    replicated, so a whole sweep grid's table phase runs as one SPMD
    computation.  Rows are evaluated independently, so cell c's slice
    equals a per-cell :func:`selection_tables` call up to the jit
    scheduling caveat below.

    Parity note: inside the jitted computation XLA may contract the
    ``csum + m * exp(lprod)`` prefix-cost pair into an FMA (one rounding
    instead of two), shifting a prefix cost by ~1 ulp relative to the
    eager/NumPy evaluation.  A mask can therefore flip ONLY where two
    prefix costs tie to within that ulp — inside the same ~1e-12
    near-tie dead-band already documented on :func:`selection_tables`;
    the differential tests gate exact mask agreement away from it.

    The kernel's call, from dispatch to the host array, is spanned as
    ``tables.device``.
    """
    pi = np.atleast_2d(np.asarray(pi, np.float64))
    v, n = pi.shape
    k = 1 << n
    c = np.atleast_2d(np.asarray(costs_cells)).shape[0]
    if c == 0:
        return np.empty((0, v, k, n), dtype=bool)
    with jax.enable_x64(True):
        args = cells_tables_args(costs_cells, pi, nu, penalties, fno_cells,
                                 mesh=mesh)
        with obs.span("tables.device"):
            out = np.asarray(_cells_tables_kernel(*args))
    return out[:c].reshape(c, v, k, n)


def cells_tables_args(costs_cells, pi, nu, penalties, fno_cells, *,
                      mesh=None) -> tuple:
    """The device arguments of :func:`_cells_tables_kernel` for C >= 1
    cells, as :func:`selection_tables_cells_jax` stages them: cells
    deduplicated into (costs, fno) groups and, with a multi-device
    ``mesh``, both cell axes sharded and (pi, nu) replicated.  Call under
    ``jax.enable_x64(True)`` so the float64 arrays stay float64."""
    pi = np.atleast_2d(np.asarray(pi, np.float64))
    nu = np.atleast_2d(np.asarray(nu, np.float64))
    costs_cells = np.atleast_2d(np.asarray(costs_cells, np.float64))
    penalties = np.asarray(penalties, np.float64)
    fno_cells = np.asarray(fno_cells, bool)
    c = costs_cells.shape[0]
    # dedupe the penalty-independent sort stage: one group per unique
    # (costs, fno) pair, each cell pointing at its group
    keys = [(cc.tobytes(), bool(f))
            for cc, f in zip(costs_cells, fno_cells)]
    uniq: dict = {}
    group_idx = np.empty(c, np.int64)
    for i, key in enumerate(keys):
        group_idx[i] = uniq.setdefault(key, len(uniq))
    g = len(uniq)
    first = np.empty(g, np.int64)
    for i in range(c - 1, -1, -1):
        first[group_idx[i]] = i
    costs_u = costs_cells[first]
    fno_u = fno_cells[first]
    if mesh is not None and mesh.size > 1:
        from repro.distributed.sharding import replicate_to_mesh, shard_cells
        (cu, fu), _ = shard_cells([costs_u, fno_u], mesh)
        (gi, pp), _ = shard_cells([group_idx, penalties], mesh)
        return (cu, fu, gi, pp,
                replicate_to_mesh(pi, mesh), replicate_to_mesh(nu, mesh))
    return tuple(jnp.asarray(a) for a in
                 (costs_u, fno_u, group_idx, penalties, pi, nu))


def rho_selection_tables(costs, rhos, miss_penalty, *, allowed=None
                         ) -> np.ndarray:
    """[B, n] float64 DS_PGM masks for an arbitrary per-request rho matrix.

    The pattern-grid :func:`selection_tables` covers policies whose rho is
    a pure (version, indication-pattern) function; the calibrated policy's
    rho rows are instead keyed on its evolving calibration state (EWMA
    values, probe counts, epsilon exploration), one row per request.  This
    is the verification half of the ``fna_cal`` fast engine's
    speculate-and-commit loop (``repro.cachesim.fna_cal_fast``): it runs
    per speculation segment, so it is evaluated as a NumPy float64 mirror
    of :func:`ds_pgm_batched` — same stable potential-gain argsort, same
    ``exp(cumsum(log .))`` prefix evaluation, no per-segment dispatch
    overhead.  Agreement with the scalar ``ds_pgm`` carries the same
    ~1e-12 near-tie caveat documented on :func:`selection_tables`.

    ``allowed`` (bool [B, n], optional) restricts row b's candidates to
    ``allowed[b]`` — the CS_FNO restriction, handled exactly like
    ``ds_pgm_batched``'s ``fno_mask``: excluded caches sort last (key =
    inf), can never be picked (cost = inf kills every prefix containing
    one), and drop out of the exclusion product.

    ``costs`` is [n] shared or [B, n] per row, ``miss_penalty`` a scalar
    or [B] per row (a stacked batch of decision cells); every operation
    is row-local, so a row's mask does not depend on the rest of the
    batch.
    """
    rhos = np.asarray(rhos, np.float64)
    b, n = rhos.shape
    costs_b = np.broadcast_to(np.asarray(costs, np.float64), (b, n))
    m = np.broadcast_to(np.asarray(miss_penalty, np.float64), (b,))
    logr = np.log(np.clip(rhos, EPS, 1.0 - EPS))
    key = costs_b / -logr
    if allowed is not None:
        allowed = np.asarray(allowed, bool)
        key = np.where(allowed, key, np.inf)        # excluded -> last
        logr = np.where(allowed, logr, 0.0)         # drop from the product
        costs_b = np.where(allowed, costs_b, np.inf)
    order = np.argsort(key, axis=1, kind="stable")
    flat = order + (np.arange(b) * n)[:, None]      # row-flattened gather
    csum = np.cumsum(np.take_along_axis(costs_b, order, 1), axis=1)
    lprod = np.cumsum(logr.reshape(-1)[flat], axis=1)
    phi = csum + m[:, None] * np.exp(lprod)         # prefix costs, i = 1..n
    best = np.argmin(phi, axis=1)
    # the empty prefix (cost M) wins ties, exactly like argmin over [M, phi]
    take = np.where(phi[np.arange(b), best] < m, best + 1, 0)
    pick_sorted = np.arange(n)[None, :] < take[:, None]
    mask = np.empty((b, n), dtype=bool)
    mask.reshape(-1)[flat] = pick_sorted
    return mask


def _subset_dp(costs, rhos, miss_penalty):
    """[B, 2^n] Eq. (10) value of EVERY subset, bit-exact with the scalar
    :func:`repro.core.exhaustive` enumeration.

    The scalar loop accumulates a subset's cost and its exclusion product
    by ascending cache index, so ``phi[b, m]`` must reproduce exactly that
    IEEE operation order.  A DP that extends each mask by its HIGHEST set
    bit does: ``m`` strips to ``m ^ (1 << hb)``, whose own value was built
    in the same ascending order, and appends the one multiply/add the
    scalar loop performs last.

    ``miss_penalty`` is a scalar or a [B] per-row array (the stacked
    cross-cell build feeds one penalty per row) — the seeded product is
    the only place it enters, so per-row values keep every row's IEEE
    operation order identical to its scalar-penalty evaluation.
    """
    rhos = np.asarray(rhos, np.float64)
    b, n = rhos.shape
    k = 1 << n
    costs = np.asarray(costs, np.float64)
    cost_m = np.zeros(k, np.float64)
    prod_m = np.empty((b, k), np.float64)
    prod_m[:, 0] = np.asarray(miss_penalty, np.float64)
    for m in range(1, k):
        hb = m.bit_length() - 1
        rest = m ^ (1 << hb)
        cost_m[m] = cost_m[rest] + costs[hb]
        np.multiply(prod_m[:, rest], rhos[:, hb], out=prod_m[:, m])
    return cost_m[None, :] + prod_m


def rho_exhaustive_tables(costs, rhos, miss_penalty, *, allowed=None,
                          backend: str = "numpy") -> np.ndarray:
    """[B, n] bool masks: the exact Eq. (10) minimiser over all 2^n
    subsets for an arbitrary per-request rho matrix (n <= 16).

    The batched twin of the scalar :func:`repro.core.exhaustive` — the
    exhaustive counterpart of :func:`rho_selection_tables`, and the
    verification half of the calibrated fast engine when the exhaustive
    subroutine is configured.  ``allowed`` (int64 [B], optional) restricts
    row b to subsets of ``allowed[b]`` (the CS_FNO candidate set; the empty
    set is always allowed).  Subset values reproduce the scalar loop's IEEE
    operation order exactly (see ``_subset_dp``); the argmin takes the
    LOWEST qualifying mask, matching the scalar ascending enumeration, with
    the same ~1e-12 near-tie caveat documented on
    :func:`rho_selection_tables`.

    ``backend`` selects the subset-DP evaluator: ``"numpy"`` (this module's
    :func:`_subset_dp`, the golden oracle), ``"jax"`` or ``"pallas"``
    (``repro.kernels.subsetdp`` — bit-exact with the oracle by
    construction; the argmin reduction then runs on device so the
    [B, 2^n] value matrix never comes back to the host).
    """
    rhos = np.asarray(rhos, np.float64)
    b, n = rhos.shape
    if n > 16:
        raise ValueError("rho_exhaustive_tables() limited to n <= 16")
    k = 1 << n
    if backend != "numpy":
        if np.ndim(miss_penalty):
            raise ValueError(
                "per-row miss_penalty requires backend='numpy'")
        from repro.kernels.subsetdp import subset_argmin
        best = subset_argmin(costs, rhos, miss_penalty,
                             allowed=allowed, backend=backend)
        return ((best[:, None] >> np.arange(n)[None, :]) & 1).astype(bool)
    phi = _subset_dp(costs, rhos, miss_penalty)
    if allowed is not None:
        bad = (np.arange(k)[None, :] & ~np.asarray(allowed, np.int64)[:, None]) != 0
        phi[bad] = np.inf
    # np.argmin returns the FIRST minimal subset in ascending-mask order;
    # the scalar loop keeps the earlier mask unless a later one improves by
    # more than EPS — identical away from ~1e-12 near-ties
    best = np.argmin(phi, axis=1)
    return ((best[:, None] >> np.arange(n)[None, :]) & 1).astype(bool)


def exhaustive_tables(costs, pi, nu, miss_penalty, *, fno: bool = False,
                      chunk: int = None, backend: str = "numpy"
                      ) -> np.ndarray:
    """[V, 2^n] int64 selection bitmasks over ALL indication patterns for a
    batch of V view versions, with the EXHAUSTIVE subroutine
    (n <= ``MAX_EXHAUSTIVE_TABLE_CACHES``).

    The exhaustive counterpart of :func:`selection_tables`: row (v, p)
    holds the Eq. (10)-optimal subset under view version v for indication
    pattern p; ``fno=True`` restricts candidates to positive-indication
    caches.  Evaluated chunk-wise so the [rows, 2^n] subset matrix stays
    bounded — ``chunk=None`` sizes chunks to ~``EXHAUSTIVE_CHUNK_ELEMS``
    float64 elements, which keeps the peak working set near ~32 MB however
    large n grows within the cap; the simulator fast engine feeds its
    whole version history here when ``alg="exhaustive"``.  ``backend``
    selects the subset-DP evaluator (see :func:`rho_exhaustive_tables`).
    """
    pi = np.atleast_2d(np.asarray(pi, np.float64))
    nu = np.atleast_2d(np.asarray(nu, np.float64))
    v, n = pi.shape
    if n > MAX_EXHAUSTIVE_TABLE_CACHES:
        raise ValueError(
            f"exhaustive_tables() limited to n <= {MAX_EXHAUSTIVE_TABLE_CACHES}")
    k = 1 << n
    if chunk is None:
        chunk = max(1, EXHAUSTIVE_CHUNK_ELEMS // k)
    pat_bits = (np.arange(k)[:, None] >> np.arange(n)[None, :]) & 1   # [K,n]
    rhos = np.where(pat_bits[None, :, :] > 0,
                    pi[:, None, :], nu[:, None, :]).reshape(v * k, n)
    allowed = np.tile(np.arange(k, dtype=np.int64), v) if fno else None
    pow2 = (1 << np.arange(n)).astype(np.int64)
    out = np.empty(v * k, np.int64)
    for lo in range(0, v * k, chunk):
        hi = min(lo + chunk, v * k)
        mask = rho_exhaustive_tables(
            costs, rhos[lo:hi], miss_penalty,
            allowed=None if allowed is None else allowed[lo:hi],
            backend=backend)
        out[lo:hi] = mask @ pow2
    return out.reshape(v, k)


def exhaustive_tables_cells(costs, pi, nu, penalties, *, fno: bool = False,
                            chunk: int = None) -> np.ndarray:
    """[C, V, 2^n] stacked exhaustive tables for C decision cells sharing
    one (costs, fno) but differing in miss penalty — the cross-cell
    prefetch of a penalty-axis sweep (``repro.cachesim.engine``).

    One chunked subset-DP pass covers every (cell, version, pattern) row:
    the rho matrix is penalty-independent, so it is materialised once and
    fancy-indexed per chunk, with the per-row penalty entering only as
    the seeded product of :func:`_subset_dp`.  Each cell's slice is
    bit-identical to the per-cell :func:`exhaustive_tables` call it
    replaces (rows are evaluated independently; chunk boundaries don't
    enter the arithmetic), and the peak working set stays at the same
    ~``EXHAUSTIVE_CHUNK_ELEMS`` bound however many cells stack.
    """
    pi = np.atleast_2d(np.asarray(pi, np.float64))
    nu = np.atleast_2d(np.asarray(nu, np.float64))
    v, n = pi.shape
    if n > MAX_EXHAUSTIVE_TABLE_CACHES:
        raise ValueError(
            f"exhaustive_tables_cells() limited to "
            f"n <= {MAX_EXHAUSTIVE_TABLE_CACHES}")
    penalties = np.asarray(penalties, np.float64)
    c = penalties.shape[0]
    k = 1 << n
    if chunk is None:
        chunk = max(1, EXHAUSTIVE_CHUNK_ELEMS // k)
    pat_bits = (np.arange(k)[:, None] >> np.arange(n)[None, :]) & 1   # [K,n]
    rhos = np.where(pat_bits[None, :, :] > 0,
                    pi[:, None, :], nu[:, None, :]).reshape(v * k, n)
    allowed = np.tile(np.arange(k, dtype=np.int64), v) if fno else None
    pow2 = (1 << np.arange(n)).astype(np.int64)
    total = c * v * k
    out = np.empty(total, np.int64)
    for lo in range(0, total, chunk):
        idx = np.arange(lo, min(lo + chunk, total))
        sub = idx % (v * k)             # the shared rho/allowed row
        mask = rho_exhaustive_tables(
            costs, rhos[sub], penalties[idx // (v * k)],
            allowed=None if allowed is None else allowed[sub])
        out[idx[0]:idx[-1] + 1] = mask @ pow2
    return out.reshape(c, v, k)


def cs_fna_batched(indications, costs, q, fp, fn, miss_penalty) -> jax.Array:
    """Algorithm 2, batched: all caches candidates, rho by indication."""
    rhos = rho_matrix(indications, q, fp, fn)
    return ds_pgm_batched(costs, rhos, miss_penalty)


def cs_fno_batched(indications, costs, q, fp, fn, miss_penalty) -> jax.Array:
    """FNO baseline, batched: positive-indication caches only."""
    rhos = rho_matrix(indications, q, fp, fn)
    return ds_pgm_batched(costs, rhos, miss_penalty, fno_mask=indications)


def _argmin_geometric_batched(m_eff, rho, r_max) -> np.ndarray:
    """Vectorised float64 mirror of the scalar
    :func:`repro.core.policies._argmin_geometric`: same edge-case
    branches, same {0, 1, floor(r*), ceil(r*), r_max} candidate
    shortlist scanned in ascending order with the same EPS
    strict-improvement dead-band.  All inputs broadcast to [B]."""
    m_eff, rho, r_max = np.broadcast_arrays(
        np.asarray(m_eff, np.float64), np.asarray(rho, np.float64),
        np.asarray(r_max, np.int64))
    out = np.zeros(m_eff.shape, np.int64)
    pos = r_max > 0
    tiny = pos & (rho <= EPS)
    out[tiny & (m_eff > 1.0)] = 1
    mid = pos & (rho > EPS) & (rho < 1.0 - EPS)
    if not mid.any():
        return out
    m = m_eff[mid]
    r = rho[mid]
    rmax = r_max[mid]
    # continuous optimum: r* = ln(m_eff * ln(1/rho)) / ln(1/rho)
    l = np.log(1.0 / r)
    r_cont = np.log(np.maximum(m * l, EPS)) / l
    cand = np.stack([np.zeros_like(r_cont), np.ones_like(r_cont),
                     np.floor(r_cont), np.ceil(r_cont),
                     rmax.astype(np.float64)], axis=1)
    cand = np.sort(cand, axis=1)          # the scalar's ascending scan
    ok = (cand >= 0.0) & (cand <= rmax[:, None].astype(np.float64))
    val = cand + m[:, None] * r[:, None] ** cand
    best_r = np.zeros(m.shape, np.float64)
    best_v = m.copy()                     # r = 0 baseline
    for s in range(cand.shape[1]):        # duplicates can't strictly improve
        imp = ok[:, s] & (val[:, s] < best_v - EPS)
        best_r = np.where(imp, cand[:, s], best_r)
        best_v = np.where(imp, val[:, s], best_v)
    out[mid] = best_r.astype(np.int64)
    return out


def _argmin_geometric_jax(m_eff, rho, r_max):
    """Branchless jnp mirror of :func:`_argmin_geometric_batched` — the
    same {0, 1, floor(r*), ceil(r*), r_max} shortlist scanned ascending
    with the same EPS strict-improvement dead-band, but expressed with
    ``where`` lanes instead of boolean fancy-indexing so it traces into
    one jitted grid evaluation.  Dead lanes (rho outside (EPS, 1-EPS))
    are fed a harmless rho = 0.5 to keep every intermediate finite."""
    m_eff = jnp.asarray(m_eff, jnp.float64)
    rho = jnp.asarray(rho, jnp.float64)
    r_max = jnp.asarray(r_max, jnp.int64)
    pos = r_max > 0
    tiny = pos & (rho <= EPS)
    mid = pos & (rho > EPS) & (rho < 1.0 - EPS)
    r = jnp.where(mid, rho, 0.5)
    l = jnp.log(1.0 / r)
    r_cont = jnp.log(jnp.maximum(m_eff * l, EPS)) / l
    rmax_f = r_max.astype(jnp.float64)
    cand = jnp.sort(jnp.stack(
        [jnp.zeros_like(r_cont), jnp.ones_like(r_cont),
         jnp.floor(r_cont), jnp.ceil(r_cont), rmax_f], axis=1), axis=1)
    ok = (cand >= 0.0) & (cand <= rmax_f[:, None])
    val = cand + m_eff[:, None] * r[:, None] ** cand
    best_r = jnp.zeros_like(r_cont)
    best_v = m_eff                        # r = 0 baseline
    for s in range(5):                    # static shortlist, ascending
        imp = ok[:, s] & (val[:, s] < best_v - EPS)
        best_r = jnp.where(imp, cand[:, s], best_r)
        best_v = jnp.where(imp, val[:, s], best_v)
    return jnp.where(mid, best_r.astype(jnp.int64),
                     jnp.where(tiny & (m_eff > 1.0), 1, 0))


@partial(jax.jit, static_argnames=("n",))
def _hocs_fna_jit(n_x, pi, nu, m, *, n):
    r1 = _argmin_geometric_jax(m, pi, n_x)
    residual = m * pi ** r1
    r0 = jnp.where(residual > 1.0,
                   _argmin_geometric_jax(residual, nu, n - n_x), 0)
    return r0.astype(jnp.int64), r1


def hocs_fna_batched(n_x, n, pi, nu, miss_penalty, *, backend: str = "numpy"
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Algorithm 1, batched over requests (homogeneous parameters).

    The float64 NumPy mirror of the scalar :func:`repro.core.hocs_fna` —
    same candidate shortlist and EPS dead-band via
    :func:`_argmin_geometric_batched` — so the simulator fast engine can
    evaluate a whole (view version x positive-count) grid in one call
    and stay bit-exact with the reference loop (the same near-tie caveat
    as :func:`selection_tables`: a candidate shortlist can only differ
    when the continuous optimum sits within ~1 ulp of an integer).

    ``n_x``: [B] positive-indication counts; ``pi``/``nu``/
    ``miss_penalty``: scalars or [B].  Returns (r0, r1) int64 [B].

    ``backend="jax"`` evaluates the same shortlist scan as one jitted
    x64 computation (:func:`_argmin_geometric_jax`).  Its integer
    (r0, r1) output matches the NumPy mirror except where a shortlist
    value ``r + m_eff * rho**r`` sits within ~1 ulp of the EPS
    strict-improvement margin (XLA may contract that mul-into-add pair
    into an FMA) — the same near-tie dead-band as everywhere else in the
    fast engine; the property tests pin agreement away from it.
    """
    n_x = np.asarray(n_x, np.int64)
    pi, nu, m, n_x = np.broadcast_arrays(
        np.asarray(pi, np.float64), np.asarray(nu, np.float64),
        np.asarray(miss_penalty, np.float64), n_x)
    if backend == "jax":
        with jax.enable_x64(True):
            r0, r1 = _hocs_fna_jit(
                jnp.asarray(n_x), jnp.asarray(pi), jnp.asarray(nu),
                jnp.asarray(m), n=int(n))
            return np.asarray(r0), np.asarray(r1)
    r1 = _argmin_geometric_batched(m, pi, n_x)
    residual = m * pi ** r1
    r0 = np.where(residual > 1.0,
                  _argmin_geometric_batched(residual, nu, n - n_x), 0)
    return r0.astype(np.int64), r1


def hocs_selection_tables_cells(pi_v, nu_v, penalties) -> np.ndarray:
    """[C, V, 2^n] int64 HOCS selection bitmasks for C decision cells
    (one miss penalty each) sharing one view history — the cross-cell
    prefetch of a penalty-axis sweep (``repro.cachesim.engine``).

    Mirrors the reference loop exactly: per-version pooled estimates are
    LEFT-TO-RIGHT sums over caches (np.sum pairwise-accumulates for
    n >= 8, which can differ in the last ulp), computed ONCE (they are
    penalty-independent); the (r0*, r1*) grid is one
    :func:`hocs_fna_batched` call over every (cell, version, popcount)
    triple; and row (c, v, p) accesses the r1* cheapest positive-
    indication caches plus the r0* cheapest negative ones (ascending
    cache index — the homogeneous setting has no cost order).  The
    shortlist scan is elementwise per row, so each cell's slice is
    bit-identical to a per-cell call.
    """
    pi_v = np.atleast_2d(np.asarray(pi_v, np.float64))
    nu_v = np.atleast_2d(np.asarray(nu_v, np.float64))
    penalties = np.asarray(penalties, np.float64)
    c = penalties.shape[0]
    v, n = pi_v.shape
    k = 1 << n
    acc_pi = np.zeros(v, np.float64)
    acc_nu = np.zeros(v, np.float64)
    for j in range(n):                    # left-to-right, like sum(list)
        acc_pi = acc_pi + pi_v[:, j]
        acc_nu = acc_nu + nu_v[:, j]
    pi_h = acc_pi / n
    nu_h = acc_nu / n
    # (r0*, r1*) depends on the pattern only through its popcount
    nx = np.arange(n + 1, dtype=np.int64)
    r0g, r1g = hocs_fna_batched(
        np.tile(nx, c * v), n,
        np.tile(np.repeat(pi_h, n + 1), c),
        np.tile(np.repeat(nu_h, n + 1), c),
        np.repeat(penalties, v * (n + 1)))
    r0g = r0g.reshape(c * v, n + 1)
    r1g = r1g.reshape(c * v, n + 1)
    bits = ((np.arange(k)[:, None] >> np.arange(n)[None, :]) & 1
            ).astype(np.int64)                                    # [K, n]
    pow2 = (1 << np.arange(n)).astype(np.int64)
    rank_pos = np.cumsum(bits, axis=1)      # 1-based rank among set bits
    rank_neg = np.cumsum(1 - bits, axis=1)
    # low_set[p, r] = mask of the r lowest-index positive caches of p
    low_set = np.stack([(bits * (rank_pos <= r)) @ pow2
                        for r in range(n + 1)], axis=1)           # [K, n+1]
    low_clr = np.stack([((1 - bits) * (rank_neg <= r)) @ pow2
                        for r in range(n + 1)], axis=1)
    popc = bits.sum(axis=1)                                       # [K]
    rows = np.arange(k)[None, :]
    sel = low_set[rows, r1g[:, popc]] | low_clr[rows, r0g[:, popc]]
    return sel.astype(np.int64).reshape(c, v, k)


def hocs_selection_tables(pi_v, nu_v, miss_penalty) -> np.ndarray:
    """[V, 2^n] int64 HOCS selection bitmasks over ALL indication
    patterns for a batch of V view versions — the single-cell view of
    :func:`hocs_selection_tables_cells` (same code path, so the stacked
    prefetch and the per-cell provider build cannot drift apart)."""
    return hocs_selection_tables_cells(
        pi_v, nu_v, [float(miss_penalty)])[0]