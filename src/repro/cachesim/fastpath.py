"""Fast engine, policy side: decision-plan dispatch + vectorised replay.

Bit-exact twin of ``Simulator._run_reference`` built on the invariants
documented in the ``repro.cachesim.simulator`` module docstring (I1:
stale bitmaps frozen between advertisements; I2: (pi, nu) views frozen
between version bumps) plus two structural facts the reference loop
obscures:

  * the SYSTEM state (LRU contents, CBF counters, stale bitmaps, FP/FN
    estimates, q-estimates) evolves independently of any policy's
    decisions — placement is by hash and every request is placed in its
    designated cache;
  * a key can only ever reside in its DESIGNATED cache, so each cache's
    dynamics depend only on its own designated subsequence of the trace.

The engine therefore runs in phases:

  1. SYSTEM SWEEP — per-cache LRU passes, CBF event walks, vectorised
     per-epoch indications, batched q-updates, and the full view-version
     history.  This phase lives in ``repro.cachesim.systemstate`` and is
     POLICY-INDEPENDENT: :func:`run_fast` computes a
     :class:`~repro.cachesim.systemstate.SystemTrace` once per (trace,
     system config) and ``run_policies``/``repro.cachesim.sweep`` reuse
     one artifact across every policy AND across every decision-side
     sweep cell, so a P-policy, C-cell comparison costs one sweep plus
     P*C cheap replays instead of P*C full runs.

  2. DECISION PLAN — by I2, a decision within a view version is a pure
     function of the n-bit indication pattern, so the whole run needs at
     most V * 2^n distinct selections.  HOW those are produced is the
     provider registry of ``repro.cachesim.engine``: batched NumPy DS_PGM
     tables, the exact HOCS mirror, the 2^n-subset enumeration, the
     generic scalar fallback, the segmented ``fna_cal`` replay, or the
     direct PI replay — ``plan_for(cfg)`` picks the first match, and
     table plans memoise their output on the shared SystemTrace so
     decision-side sweeps can prefetch them stacked.

  3. REPLAY — selections, hits and access counts become vectorised table
     lookups over the trace (:func:`accumulate_replay`); only the
     service-cost accumulation stays a scalar fold so float-addition
     order matches the reference exactly.

``fna_cal`` breaks I2 — its empirical EWMAs move on every probe outcome —
so phases 2-3 are replaced by the speculative segmented replay in
``repro.cachesim.fna_cal_fast`` (same shared phase-1 artifact).

Parity caveat: all state evolution and accounting here is replicated
operation-for-operation, but the DS_PGM tables evaluate Eq. (10) through
``exp(cumsum(log .))`` in float64 rather than the scalar running product,
and pick the argmin rather than applying the scalar path's EPS (1e-12)
improvement dead-band.  The two can only disagree when two prefix costs
coincide to within ~1e-12 absolute — a measure-zero coincidence of the
data-derived estimates, ruled out empirically by the parity suite
(``tests/test_fastpath.py``) across every policy x trace x interval
combination tested.  The HOCS mirror carries the analogous caveat on its
candidate shortlist (``repro.core.batched.hocs_fna_batched``).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.cachesim.simulator import SimResult, Simulator
from repro.cachesim.systemstate import SystemTrace


def accumulate_replay(res: SimResult, st: SystemTrace, selm: np.ndarray,
                      costs, miss_penalty: float) -> SimResult:
    """Fold per-request selection bitmasks into the SimResult exactly as
    the reference loop would: per-mask cost sums in ascending cache order,
    hit iff the designated cache is both selected and resident, and a
    scalar float fold so cost-addition order matches bit-for-bit."""
    n = st.n
    k = 1 << n
    acc_by_mask = np.asarray(
        [sum(costs[j] for j in range(n) if (m >> j) & 1) for m in range(k)],
        np.float64)
    popcount = np.asarray([bin(m).count("1") for m in range(k)], np.int64)
    hit_arr = st.in_dj & (((selm >> st.dj_all) & 1) != 0)
    acc = acc_by_mask[selm]
    cost_arr = np.where(hit_arr, acc, acc + miss_penalty)
    pos_acc = int(popcount[selm & st.pats].sum())
    total_cost = res.total_cost
    for c in cost_arr.tolist():
        total_cost += c
    res.total_cost = total_cost
    res.hits += int(np.count_nonzero(hit_arr))
    res.pos_accesses += pos_acc
    res.neg_accesses += int(popcount[selm].sum()) - pos_acc
    res.n_requests += st.trace_len
    return res


def run_fast(sim: Simulator, trace: np.ndarray, res: SimResult,
             system: Optional[SystemTrace] = None,
             chunk_size: Optional[int] = None, spill=None) -> SimResult:
    from repro.cachesim.engine import plan_for
    plan = plan_for(sim.cfg)
    if plan is None:
        # outside every provider's budget (n beyond the table limits):
        # the reference loop is the better deal
        return sim._run_reference(trace, res)
    if trace.shape[0] == 0:
        return res

    # --- phase 1: the shared system sweep (or a reused artifact) --------
    if system is None:
        system = SystemTrace.compute(sim, trace, chunk_size=chunk_size,
                                     spill=spill)
    else:
        system.install(sim, trace)
    sim.last_system = system
    system.add_quality(res)
    system.add_advert(res)

    # --- phases 2-3: the decision plan ----------------------------------
    return plan.replay(sim, system, res)
