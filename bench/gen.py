"""The benchmark's one traffic generator: request traces from a mix file.

A traffic mix (``bench/traffic/<name>.json``) names a generator and its
parameters; a configuration (``bench/configs/<name>.json``) gives the
trace length.  ``make_trace(mix, requests, seed)`` builds the trace.

Generators (copies of the repository's synthetic traces, which stand in
for the paper's Wiki and Gradle logs, arXiv:2102.01724 Sec. V-B; the
originals are ``recency_trace`` and ``zipf_trace`` in
``repro.cachesim.traces``):

* ``gradle`` -- recency-biased: a new object with probability
  ``p_new``, else a re-reference at a Zipf(``alpha``) stack distance
  within the last ``window`` requests.
* ``wiki`` -- frequency-biased: bounded Zipf(``alpha``) over a
  ``catalog`` whose rank-to-object map drifts by one every
  ``1/drift`` requests.

How ``--seed`` enters: the mix's ``structure_seed`` draws the request
structure (which request re-references which, and when objects are
new), and ``--seed`` draws a bijective relabelling of the object ids
that keeps each id's residue modulo the cache count, so every request
keeps its designated cache.  Each seed therefore gives the same hits,
misses, insertions and work, on different keys: the Bloom filters see
different hash collisions, so indications, estimates and decisions
differ from seed to seed while the amount of work does not.
"""
from __future__ import annotations

import numpy as np

#: the relabelled id space holds 2**ID_BITS quotients per residue class
ID_BITS = 40


def _bounded_zipf_cdf(catalog: int, alpha: float) -> np.ndarray:
    ranks = np.arange(1, catalog + 1, dtype=np.float64)
    w = ranks ** -alpha
    return np.cumsum(w) / w.sum()


def gradle(n: int, seed: int, p_new: float = 0.25, window: int = 4096,
           alpha: float = 1.2) -> np.ndarray:
    """Recency-biased trace, vectorised by pointer doubling: each
    re-reference copies the id ``d`` positions back (a seed-window slot
    or an earlier output), so chains of back-pointers are collapsed in
    O(log chain) passes."""
    rng = np.random.default_rng(seed)
    cdf = _bounded_zipf_cdf(window, alpha)
    us = rng.random(n)
    ds = np.searchsorted(cdf, rng.random(n)) + 1        # stack distances
    is_new = us < p_new
    out = np.where(is_new, window + np.cumsum(is_new), 0)
    ptr = np.arange(n, dtype=np.int64) - ds             # back-reference
    seed_ref = ~is_new & (ptr < 0)                      # into the seed window
    out[seed_ref] = window + ptr[seed_ref] + 1
    resolved = is_new | seed_ref
    unres = np.flatnonzero(~resolved)
    while unres.size:
        tgt = ptr[unres]
        done = resolved[tgt]
        hit = unres[done]
        out[hit] = out[tgt[done]]
        resolved[hit] = True
        rest = unres[~done]
        ptr[rest] = ptr[ptr[rest]]
        unres = rest
    return out


def wiki(n: int, seed: int, catalog: int = 400_000, alpha: float = 0.99,
         drift: float = 0.01) -> np.ndarray:
    """Bounded Zipf with a drifting popular head."""
    rng = np.random.default_rng(seed)
    cdf = _bounded_zipf_cdf(catalog, alpha)
    ranks = np.searchsorted(cdf, rng.random(n))
    shift = (np.arange(n) * drift).astype(np.int64)
    perm = rng.permutation(catalog)
    return perm[(ranks + shift) % catalog].astype(np.int64)


GENERATORS = {"gradle": gradle, "wiki": wiki}


def relabel(ids: np.ndarray, residues: int, seed: int) -> np.ndarray:
    """Map id ``x`` to ``residues * f(x // residues) + x % residues``
    with ``f(q) = (a q + b) mod 2**ID_BITS``, ``a`` odd: a bijection
    drawn from ``seed`` that keeps ``x % residues``."""
    ids = np.asarray(ids, dtype=np.int64)
    if ids.size and (ids.min() < 0 or ids.max() // residues >> ID_BITS):
        raise ValueError("ids outside the relabelled id space")
    rng = np.random.default_rng(seed)
    a = np.uint64(2 * int(rng.integers(0, 1 << (ID_BITS - 1))) + 1)
    b = np.uint64(int(rng.integers(0, 1 << ID_BITS)))
    q = (ids // residues).astype(np.uint64)
    f = (q * a + b) & np.uint64((1 << ID_BITS) - 1)     # mod 2**ID_BITS
    return f * np.uint64(residues) + (ids % residues).astype(np.uint64)


def make_trace(mix: dict, requests: int, seed: int,
               residues: int) -> np.ndarray:
    """The [requests] uint64 trace of ``mix`` for ``--seed`` ``seed``;
    ``residues`` is the fleet's cache count."""
    gen = GENERATORS[mix["generator"]]
    ids = gen(int(requests), int(mix["structure_seed"]),
              **mix.get("params", {}))
    return relabel(ids, int(residues), int(seed))
