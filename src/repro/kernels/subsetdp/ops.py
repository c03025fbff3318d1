"""Public wrappers for the subset-DP kernel.

``subset_dp`` returns the full [B, 2^n] Eq. (10) value matrix;
``subset_argmin`` the winning subset mask per row (the exhaustive table
builders only need the argmin, so the masking + first-min reduction stays
on device and the 2^n-wide value matrix never leaves it).

Backends — all BIT-EXACT with the oracle (the three evaluate identical
IEEE operation chains; ``ref.py`` explains why, and why the final
``cost + prod`` add happens outside the jitted product computation):

  * ``"numpy"``  — the serial highest-set-bit recurrence
    (``repro.core.batched._subset_dp``), the golden oracle;
  * ``"jax"``    — the jitted jnp mirror (``ref.subset_prod_ref``);
  * ``"pallas"`` — the row-tiled kernel (``subsetdp.subset_prod_pallas``),
    interpret mode auto-selected off-TPU.

Everything runs in float64 under ``jax.enable_x64`` (the fast engine's
exactness contract); inputs/outputs are NumPy arrays so callers stay
backend-agnostic.  A TPU has no float64, so there the ``"pallas"``
backend raises rather than compile (see ``subsetdp.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.subsetdp.ref import subset_prod_ref
from repro.kernels.subsetdp.subsetdp import (
    default_row_block,
    subset_prod_pallas,
)

_subset_prod_ref_jit = jax.jit(subset_prod_ref)


def _subset_costs(costs: np.ndarray, n: int) -> np.ndarray:
    """[2^n] per-subset cost sums, bitwise equal to ``_subset_dp``'s
    ``cost_m`` (ascending-index adds; +0.0 on clear bits is an IEEE
    identity on the non-negative partial sums)."""
    k = 1 << n
    lanes = np.arange(k)
    cost = np.zeros(k, np.float64)
    for j in range(n):
        bit = ((lanes >> j) & 1).astype(bool)
        cost = cost + np.where(bit, costs[j], 0.0)
    return cost


def _pad_rows(rhos: np.ndarray, multiple: int) -> np.ndarray:
    pad = (-rhos.shape[0]) % multiple
    if pad:
        rhos = np.concatenate([rhos, np.repeat(rhos[-1:], pad, axis=0)])
    return rhos


def _prod(rhos: np.ndarray, miss_penalty: float, backend: str,
          row_block, interpret):
    """Device-side [B(+pad), 2^n] subset products for the jax/pallas
    backends (call under ``jax.enable_x64(True)``)."""
    if backend == "jax":
        return _subset_prod_ref_jit(jnp.asarray(rhos), miss_penalty)
    if backend == "pallas":
        n = rhos.shape[1]
        rb = row_block if row_block is not None else default_row_block(n)
        return subset_prod_pallas(_pad_rows(rhos, rb), miss_penalty,
                                  row_block=rb, interpret=interpret)
    raise ValueError(f"unknown subset-DP backend {backend!r}")


def subset_dp(costs, rhos, miss_penalty, *, backend: str = "pallas",
              row_block: int = None, interpret: bool = None) -> np.ndarray:
    """[B, 2^n] float64 Eq. (10) subset values; see module docstring."""
    rhos = np.asarray(rhos, np.float64)
    costs = np.asarray(costs, np.float64)
    if backend == "numpy":
        from repro.core.batched import _subset_dp
        return _subset_dp(costs, rhos, miss_penalty)
    b, n = rhos.shape
    with jax.enable_x64(True):
        prod = np.asarray(_prod(rhos, float(miss_penalty), backend,
                                row_block, interpret))[:b]
    # final add OUTSIDE the jitted computation — same two roundings as the
    # oracle's ``cost_m[None, :] + prod_m`` (ref.py: FMA contraction)
    return _subset_costs(costs, n)[None, :] + prod


@jax.jit
def _masked_argmin(cost, prod, allowed):
    phi = cost[None, :] + prod      # both are inputs: nothing to contract
    k = prod.shape[1]
    lanes = jnp.arange(k, dtype=jnp.int64)[None, :]
    bad = (lanes & ~allowed[:, None]) != 0
    phi = jnp.where(bad, jnp.inf, phi)
    # first minimal subset in ascending-mask order, like np.argmin
    return jnp.argmin(phi, axis=1).astype(jnp.int64)


def subset_argmin(costs, rhos, miss_penalty, *, allowed=None,
                  backend: str = "pallas", row_block: int = None,
                  interpret: bool = None) -> np.ndarray:
    """[B] int64 winning subset masks: the Eq. (10) minimiser per row,
    FIRST minimum in ascending-mask order (matching ``np.argmin`` and the
    scalar enumeration away from the ~1e-12 near-tie dead-band).

    ``allowed`` (int64 [B], optional) restricts row b to subsets of
    ``allowed[b]`` — the CS_FNO candidate restriction; the empty set is
    always allowed.
    """
    rhos = np.asarray(rhos, np.float64)
    costs = np.asarray(costs, np.float64)
    b, n = rhos.shape
    k = 1 << n
    if backend == "numpy":
        from repro.core.batched import _subset_dp
        phi = _subset_dp(costs, rhos, miss_penalty)
        if allowed is not None:
            bad = (np.arange(k)[None, :]
                   & ~np.asarray(allowed, np.int64)[:, None]) != 0
            phi[bad] = np.inf
        return np.argmin(phi, axis=1).astype(np.int64)
    with jax.enable_x64(True):
        prod = _prod(rhos, float(miss_penalty), backend,
                     row_block, interpret)[:b]
        cost = jnp.asarray(_subset_costs(costs, n))
        if allowed is None:
            allow_arr = jnp.full((b,), k - 1, jnp.int64)
        else:
            allow_arr = jnp.asarray(np.asarray(allowed, np.int64))
        return np.asarray(_masked_argmin(cost, prod, allow_arr))
