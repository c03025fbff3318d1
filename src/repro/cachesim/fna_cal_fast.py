"""Speculative segmented fast replay for the calibrated policy (fna_cal).

``fna_cal`` corrects the bit-counting FN inflation of Eq. (7) with
empirical probe feedback: per-cache EWMAs of observed exclusion outcomes,
blended with the model views until ``cal_min_obs`` probes accumulate (or
immediately when the indicator is uninformative, FP+FN >= 0.95), plus
epsilon-exploration.  Its EWMAs move on EVERY probe outcome, which breaks
the frozen-view invariant (I2) the table-driven fast path relies on — but
its DECISIONS only change when a drifting rho crosses a DS_PGM decision
boundary, which is far rarer than a probe: measured on the gradle trace
the 2^n decision table changes on ~2% of requests, in a bimodal pattern —
long stable runs punctuated by short flip bursts while a rho hovers at a
boundary.

The engine speculates and commits:

  1. SPECULATE a vectorised replay of a window through a frozen 2^n
     decision table (plus the precomputed epsilon-exploration draws — the
     reference RNG stream is replicated exactly).  The table need not be
     correct — it is a guess whose quality only affects speed — so in the
     post-warmup regime (every branch past min-obs, model views ignored)
     it is patched one row at a time from verification verdicts instead
     of being rebuilt; while model views are still blended in,
     per-view-version tables are rebuilt from the frozen calibration
     state, the whole (version x pattern) batch in one call of the
     verifier below — the same float64 math as the verification pass, so
     a correct speculation always verifies.
  2. RECONSTRUCT the exact calibration-state trajectory the speculated
     probes imply: probe counts are integer cumsums; EWMA paths advance
     per (cache, branch) through :func:`repro.core.estimator.ewma_paths` —
     the bit-identical scalar recurrence batched over the segment's probe
     events — and broadcast back per request.  Probe outcomes come free
     from the shared ``SystemTrace``: only the designated cache can hold
     a key, so ``in_dj`` determines every probe's result.
  3. VERIFY with one batched float64 DS_PGM evaluation of the true
     per-request rho matrix (``repro.core.batched.rho_selection_tables``)
     and COMMIT up to the first request whose recomputed EWMA / min-obs /
     exploration state alters the decision.  The mismatched request
     itself is then replayed by one step of the scalar BRIDGE — a
     reference-exact transcription of the decision/feedback loop over the
     precomputed system arrays — which both guarantees forward progress
     independent of float coincidences and yields the fresh table row.
  4. ADAPT: the window doubles on a fully-committed segment and shrinks
     on early mismatch; when commits collapse below the speculation
     break-even (a flip burst), the engine drops into the scalar bridge
     for a stretch instead of thrashing table rebuilds.

LANES: the cells of a decision-side group (a penalty or cost axis, or
calibration settings) share one sweep, so their replays differ only in
the settings each row is evaluated under.  :func:`fna_cal_lanes` runs
them in lockstep, one lane per cell, each with its own state machine:
one batched table build, one trajectory pass and one verifier call per
round cover every lane, instead of one per lane.  ``run_cells`` hands a
group's cells to it (``repro.cachesim.engine.FnaCalSegmented``); a lone
replay is one lane.

Bit-exactness: bridge-committed requests replicate the reference
operations literally; speculatively-committed requests are verified
equal to the float64 batched evaluation of the true rho (DS_PGM prefix
scan, or the 2^n-subset enumeration when ``alg="exhaustive"``, n <= 12) —
the same near-tie parity caveat as ``repro.cachesim.fastpath``, ruled
out empirically by ``tests/test_fna_cal_fast.py`` across traces and
calibration settings.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from repro import obs
from repro.cachesim.systemstate import SystemTrace
from repro.core.batched import (EXHAUSTIVE_CHUNK_ELEMS,
                                rho_exhaustive_tables, rho_selection_tables)
from repro.core.estimator import ewma_paths
from repro.core.policies import ds_pgm_mask, exhaustive_mask

_START_WINDOW = 512
_SPEC_MIN_WINDOW = 128       # smallest window worth a speculation round
_MAX_WINDOW = 65_536
_CHUNK = 256                 # trajectory/verification granularity: the
# speculated WINDOW can be huge (table lookups are cheap), but the
# expensive exact-state reconstruction + verification walk it in chunks
# and abort at the first mismatching chunk, so the work wasted past a
# mis-speculation is bounded by one chunk instead of the whole window
_BURST_COMMIT = 8            # commits below this => flip burst => bridge
_BRIDGE_LEN = 32             # scalar requests per bridge stretch
# while any branch still blends model views, cap tables built per segment
_MAX_SEG_VERSIONS = 16


def fna_cal_selections(sim, st: SystemTrace) -> np.ndarray:
    """[N] committed (post-exploration) selection bitmasks for one
    fna_cal cell: the lockstep engine of :func:`fna_cal_lanes` with one
    lane.  Exposed separately so the topology layer can re-account the
    same decisions under per-tier penalties."""
    return fna_cal_lanes([sim.cfg], st)[0]


class _Lane:
    """One fna_cal cell's speculate/verify/bridge state machine against
    the shared sweep.  Its calibration state is row ``i`` of the engine's
    [R, 2n] state arrays."""

    def __init__(self, i: int, cfg, eps_bits: np.ndarray, N: int):
        self.i = i
        self.costs = [float(c) for c in cfg.costs]
        self.M = float(cfg.miss_penalty)
        self.g = float(cfg.cal_gamma)
        self.min_obs = int(cfg.cal_min_obs)
        self.eps_bits = eps_bits
        self.selm = np.empty(N, dtype=np.int64)   # committed (post-eps)
        self.s = 0
        self.window = _START_WINDOW
        self.table = None       # steady-state (all-emp) speculation table
        # the open segment: [s, s + L), of which ``commit`` rows committed;
        # L == 0 while none is open
        self.L = 0
        self.commit = 0
        self.all_emp = False
        self.spec = self.sel_spec = None


def fna_cal_lanes(cfgs, st: SystemTrace) -> List[np.ndarray]:
    """[N] committed (post-exploration) selection bitmasks for each of
    several fna_cal cells against ONE shared sweep, run in lockstep.

    Each cell is a lane with its own copy of the speculate/verify/bridge
    state machine described in the module docstring (position, window,
    speculation table, calibration state); lanes may differ in costs,
    miss penalty and every ``cal_*`` setting, and must share ``alg``,
    which picks the verifier.  Each round opens a segment on every lane
    without one (after its scalar bridge stretch, if a flip burst called
    for one), builds the speculation tables of all of them in one batched
    call, then reconstructs the exact-state trajectory of every open
    segment's next chunk in one pass over the stacked rows and verifies
    them all in one float64 call with per-row costs and penalties.  Each
    lane then commits up to its own first mismatch and bridges it, as a
    lone lane does.  Every row is evaluated row-locally, so each lane's
    masks and counts are those of a run on its own.

    Adds to the ``fna_cal.*`` counters of :mod:`repro.obs` once per call:
    ``requests`` (N per lane), ``spec_committed`` (requests committed by
    verification), ``verified_rows`` (rows passed to the verifier, the
    aborted chunks' included), ``bridged`` (requests replayed by the
    scalar bridge; ``spec_committed + bridged == requests``), ``rounds``
    (verifier calls), ``lane_chunks`` (lane chunks verified; equal to
    ``rounds`` for one lane), and the nanoseconds of the table builds
    (``build_ns``), the exact-state trajectories (``trajectory_ns``), the
    verifier and its compare (``verify_ns``) and the bridge
    (``bridge_ns``)."""
    if len({cfg.alg for cfg in cfgs}) != 1:
        raise ValueError("fna_cal lanes must share one alg")
    n = st.n
    N = st.trace_len
    k = 1 << n
    # the speculate-and-commit loop is subroutine-agnostic: it needs a
    # scalar bitmask call (bridge/table rows) and a batched float64
    # verifier over an arbitrary rho matrix.  ds_pgm pairs the stripped
    # scalar variant with the prefix-scan verifier; exhaustive (n <= 12 —
    # the Simulator dispatch falls back to the reference loop beyond the
    # table budget) pairs it with the batched 2^n-subset enumeration.
    exhaustive = cfgs[0].alg == "exhaustive"
    mask_fn = exhaustive_mask if exhaustive else ds_pgm_mask
    arange_n = np.arange(n)
    pow2 = (np.int64(1) << arange_n).astype(np.int64)
    bits_of = ((np.arange(k)[:, None] >> arange_n) & 1).astype(bool)  # [2^n, n]

    ver = st.ver_per_req
    pats = st.pats
    # per-request data of both branches, cache-major so a lane's chunk is
    # a contiguous column slice: row j is the positive branch of cache j,
    # row n + j its negative branch
    ind2_t = np.concatenate([st.ind_all.T, ~st.ind_all.T])      # [2n, N]
    # probe outcome per (request, cache): only the designated cache can
    # hold a key, so absence is a pure function of the shared sweep
    abs2_t = np.ones((2 * n, N), dtype=np.float64)              # [2n, N]
    abs2_t[st.dj_all, np.arange(N)] = (~st.in_dj).astype(np.float64)
    abs2_t[n:] = abs2_t[:n]
    bits2_t = np.concatenate([bits_of.T, bits_of.T])           # [2n, 2^n]
    uninf_v = (st.fp_v + st.fn_v) >= 0.95                      # [V, n]
    uninf2_t = np.concatenate([uninf_v.T, uninf_v.T])          # [2n, V]
    model2_t = np.concatenate([st.pi_v.T, st.nu_v.T])          # [2n, V]
    # scalar-bridge views of the per-version data (python lists: the
    # bridge reads a handful of scalars per request)
    uninf_l = uninf_v.tolist()
    mpi_l = st.pi_v.tolist()
    mnu_l = st.nu_v.tolist()

    # epsilon-exploration draws: the exact RNG stream of the reference loop
    draws = {}
    lanes = []
    for i, cfg in enumerate(cfgs):
        if cfg.seed not in draws:
            rng = np.random.default_rng(cfg.seed + 12345)
            draws[cfg.seed] = (rng.random(N), rng.integers(0, n, N))
        eps_draws, eps_pick = draws[cfg.seed]
        lanes.append(_Lane(i, cfg, np.where(
            eps_draws < cfg.cal_epsilon, np.int64(1) << eps_pick,
            np.int64(0)), N))
    R = len(lanes)
    costs_l = np.asarray([lane.costs for lane in lanes], np.float64)  # [R, n]
    pen_l = np.asarray([lane.M for lane in lanes], np.float64)
    gam_l = np.asarray([lane.g for lane in lanes], np.float64)
    min_obs_l = np.asarray([lane.min_obs for lane in lanes], np.int64)
    cost_groups: dict = {}
    for lane in lanes:
        cost_groups.setdefault(tuple(lane.costs), []).append(lane.i)

    # calibration state, a row per lane: the EWMAs and probe counts of
    # the positive branch (columns :n) and the negative one (n:)
    # (optimistic init — see the reference loop)
    emp = np.empty((R, 2 * n), np.float64)
    emp[:, :n] = 0.5
    emp[:, n:] = 0.90
    obs_n = np.zeros((R, 2 * n), np.int64)

    clock = time.perf_counter_ns
    build_ns = trajectory_ns = verify_ns = bridge_ns = 0
    spec_committed = verified_rows = bridged = rounds = lane_chunks = 0

    def decide(rho: np.ndarray, ids: np.ndarray, sizes) -> np.ndarray:
        """int64 masks of the rho rows of lanes ``ids``, ``sizes[a]``
        consecutive rows each, under each lane's costs and penalty: the
        float64 verifier, which also builds the speculation tables, so a
        correct speculation always verifies.  The subset enumeration takes
        one cost vector per call, so its rows go by cost group, in bounded
        chunks as ``exhaustive_tables`` evaluates them."""
        lane_of_row = np.repeat(ids, sizes)
        pen = pen_l[lane_of_row]
        if not exhaustive:
            return rho_selection_tables(costs_l[lane_of_row], rho, pen) @ pow2
        out = np.empty(rho.shape[0], np.int64)
        step = max(1, EXHAUSTIVE_CHUNK_ELEMS // k)
        for costs, members in cost_groups.items():
            rows = np.flatnonzero(np.isin(lane_of_row, members))
            for lo in range(0, rows.size, step):
                r = rows[lo:lo + step]
                out[r] = rho_exhaustive_tables(costs, rho[r], pen[r]) @ pow2
        return out

    def bridge(lane: _Lane, count: int) -> int:
        """Reference-exact scalar replay of ``count`` requests from the
        lane's position: per-request blend, scalar DS_PGM, exploration,
        probe feedback — the literal reference operations over the
        precomputed system arrays.  Mutates the lane's calibration state
        and position; returns the pre-exploration mask of the last
        request — the fresh table row."""
        nonlocal bridge_ns, bridged
        t0 = clock()
        i, s = lane.i, lane.s
        end = min(s + count, N)
        costs, M, g, min_obs = lane.costs, lane.M, lane.g, lane.min_obs
        selm = lane.selm
        pe: List[float] = emp[i, :n].tolist()
        ne: List[float] = emp[i, n:].tolist()
        po: List[int] = obs_n[i, :n].tolist()
        no: List[int] = obs_n[i, n:].tolist()
        pats_c = pats[s:end].tolist()
        ver_c = ver[s:end].tolist()
        abs_c = abs2_t[:n, s:end].T.tolist()
        eps_c = lane.eps_bits[s:end].tolist()
        rng_n = range(n)
        base = 0
        for r in range(end - s):
            v = ver_c[r]
            pat = pats_c[r]
            uv = uninf_l[v]
            mp = mpi_l[v]
            mn = mnu_l[v]
            rhos = [
                (pe[j] if (po[j] >= min_obs or uv[j]) else mp[j])
                if (pat >> j) & 1
                else (ne[j] if (no[j] >= min_obs or uv[j]) else mn[j])
                for j in rng_n]
            base = mask_fn(costs, rhos, M)
            m = base | eps_c[r]
            selm[s + r] = m
            ai = abs_c[r]
            mm, j = m, 0
            while mm:
                if mm & 1:
                    a = ai[j]
                    if (pat >> j) & 1:
                        pe[j] = (1.0 - g) * pe[j] + g * a
                        po[j] += 1
                    else:
                        ne[j] = (1.0 - g) * ne[j] + g * a
                        no[j] += 1
                mm >>= 1
                j += 1
        emp[i] = pe + ne
        obs_n[i] = po + no
        lane.s = end
        bridged += end - s
        bridge_ns += clock() - t0
        return base

    live = lanes if N else []
    while live:
        # --- 0. open a segment on every lane without one -----------------
        builds = []             # (lane, sorted view versions to tabulate)
        opened = []
        for lane in live:
            if lane.L:
                continue
            if lane.window < _SPEC_MIN_WINDOW:   # flip burst: scalar stretch
                bridge(lane, _BRIDGE_LEN)
                lane.window = _SPEC_MIN_WINDOW
                lane.table = None                # state moved under the table
                if lane.s >= N:
                    continue
            s = lane.s
            L = min(lane.window, N - s)
            lane.all_emp = bool((obs_n[lane.i] >= lane.min_obs).all())
            if lane.all_emp:
                if lane.table is None:
                    builds.append((lane, ver[s:s + 1]))
            else:
                # model views in play: decisions are version-dependent, so
                # use exact per-version tables and bound how many a segment
                # builds
                cut = int(np.searchsorted(ver, ver[s] + _MAX_SEG_VERSIONS,
                                          side="left"))
                L = max(min(L, cut - s), 1)
                builds.append((lane, np.unique(ver[s:s + L])))
            lane.L, lane.commit = L, 0
            opened.append(lane)

        # --- 1. speculate: every lane's tables in one batched build --------
        # (from the frozen calibration state; the rows are the verifier's
        # float64 math, so speculation quality only improves — exactness
        # is still owned by the verification pass and the scalar bridge)
        tabs_of = {}
        if builds:
            t0 = clock()
            rhos = []
            for lane, vids in builds:
                i = lane.i
                use = (obs_n[i] >= lane.min_obs)[:, None] | uninf2_t[:, vids]
                r2 = np.where(use, emp[i][:, None], model2_t[:, vids]).T
                rhos.append(np.where(bits_of[None, :, :], r2[:, None, :n],
                                     r2[:, None, n:]).reshape(-1, n))
            tabs = decide(np.concatenate(rhos),
                          np.asarray([lane.i for lane, _ in builds]),
                          [vids.size * k for _, vids in builds])
            lo = 0
            for lane, vids in builds:
                tabs_of[lane.i] = (vids, tabs[lo:lo + vids.size * k])
                lo += vids.size * k
            build_ns += clock() - t0
        for lane in opened:
            sl = slice(lane.s, lane.s + lane.L)
            if lane.all_emp:
                if lane.table is None:
                    lane.table = tabs_of[lane.i][1].copy()
                spec = lane.table[pats[sl]]
            else:
                vids, tab = tabs_of[lane.i]
                spec = tab[np.searchsorted(vids, ver[sl]) * k + pats[sl]]
            lane.spec = spec
            lane.sel_spec = spec | lane.eps_bits[sl]

        act = [lane for lane in live if lane.L]
        if not act:
            break
        # --- 2. exact state trajectories of every open segment's next chunk
        # (a lane's state at its chunk's start is exact because every
        # previous chunk of its segment committed in full; aborting at the
        # first mismatching chunk bounds the work wasted past a
        # mis-speculation).  The chunks stack side by side: [2n, B].
        t0 = clock()
        ids = np.asarray([lane.i for lane in act])
        A = ids.size
        firsts = [lane.s + lane.commit for lane in act]
        cls = [min(lane.commit + _CHUNK, lane.L) - lane.commit
               for lane in act]
        offs = np.zeros(A + 1, np.int64)
        np.cumsum(cls, out=offs[1:])
        B = int(offs[-1])
        spec_c = np.concatenate([lane.spec[lane.commit:lane.commit + cl]
                                 for lane, cl in zip(act, cls)])
        sel_c = np.concatenate([lane.sel_spec[lane.commit:lane.commit + cl]
                                for lane, cl in zip(act, cls)])
        ind2 = np.concatenate([ind2_t[:, a:a + cl]
                               for a, cl in zip(firsts, cls)], axis=1)
        # probes: row j the positive ones of cache j, row n + j the negative
        ev = bits2_t.take(sel_c, axis=1) & ind2
        # probe counts before each stacked column (+1: after the stack); a
        # lane's own counts are these less the counts at its first column
        cs = np.zeros((2 * n, B + 1), np.int64)
        np.cumsum(ev, axis=1, out=cs[:, 1:])
        cs_o = cs[:, offs]                                  # [2n, A + 1]
        # EWMA paths per (branch, cache, lane) in one pass: each row's
        # probe outcomes in column order hold each lane's in turn
        counts = np.diff(cs_o, axis=1).ravel()              # (row, lane)
        seq = ewma_paths(
            emp[ids].T.ravel(),
            np.concatenate([abs2_t[:, a:a + cl]
                            for a, cl in zip(firsts, cls)], axis=1)[ev],
            counts, np.tile(gam_l[ids], 2 * n))
        # where each path starts in ``seq``, less the stacked counts before
        # its lane's first column: + cs indexes the EWMA before a column
        start = np.zeros(counts.size, np.int64)
        np.cumsum(counts[:-1] + 1, out=start[1:])
        base = start.reshape(2 * n, A) - cs_o[:, :-1]      # [2n, A]
        ewm = seq.take(np.repeat(base, cls, axis=1) + cs[:, :-1])
        if not all(lane.all_emp for lane in act):
            # model views blend in until a branch has min-obs probes (an
            # all-empirical lane's counts are past it: it reads the EWMA)
            vc = np.concatenate([ver[a:a + cl] for a, cl in zip(firsts, cls)])
            seen = np.repeat(obs_n[ids].T - cs_o[:, :-1], cls, axis=1) \
                + cs[:, :-1]
            use = (seen >= np.repeat(min_obs_l[ids], cls)) | \
                uninf2_t.take(vc, axis=1)
            ewm = np.where(use, ewm, model2_t.take(vc, axis=1))
        rho = np.ascontiguousarray(np.where(ind2[:n], ewm[:n], ewm[n:]).T)
        t1 = clock()
        # --- 3. verify every lane's chunk in one call ---------------------
        bad = np.flatnonzero(decide(rho, ids, cls) != spec_c)
        # each lane's first mismatching stacked row (B: none)
        first = np.append(bad, B)[np.searchsorted(bad, offs[:-1])]
        ends = np.minimum(first, offs[1:])
        trajectory_ns += t1 - t0
        verify_ns += clock() - t1
        rounds += 1
        lane_chunks += A
        verified_rows += B
        # each lane's state after its committed rows
        cs_e = cs[:, ends]
        emp[ids] = seq.take(base + cs_e).T
        obs_n[ids] += (cs_e - cs_o[:, :-1]).T

        # --- 4. commit and adapt, lane by lane ---------------------------
        for lane, ok, clean in zip(act, (ends - offs[:-1]).tolist(),
                                   (first >= offs[1:]).tolist()):
            c0 = lane.commit
            spec_committed += ok
            lane.selm[lane.s + c0:lane.s + c0 + ok] = \
                lane.sel_spec[c0:c0 + ok]
            lane.commit = commit = c0 + ok
            if clean and commit < lane.L:
                continue                         # the segment goes on
            lane.s += commit
            lane.L = 0
            if clean:
                lane.window = min(lane.window * 2, _MAX_WINDOW)
            else:
                # replay the mismatched request itself scalar-exactly; its
                # fresh decision patches the (speculation-only) table row
                pat = int(pats[lane.s])
                row = bridge(lane, 1)
                if lane.all_emp and lane.table is not None:
                    lane.table[pat] = row
                else:
                    lane.table = None
                lane.window = 0 if commit < _BURST_COMMIT \
                    else min(max(2 * commit, _SPEC_MIN_WINDOW), _MAX_WINDOW)
        live = [lane for lane in live if lane.s < N]

    for name, value in (("requests", N * R),
                        ("spec_committed", spec_committed),
                        ("verified_rows", verified_rows),
                        ("bridged", bridged), ("rounds", rounds),
                        ("lane_chunks", lane_chunks), ("build_ns", build_ns),
                        ("trajectory_ns", trajectory_ns),
                        ("verify_ns", verify_ns), ("bridge_ns", bridge_ns)):
        obs.add(f"fna_cal.{name}", value)
    return [lane.selm for lane in lanes]
