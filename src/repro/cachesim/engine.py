"""Decision-plan layer of the fast engine: pluggable policy-table
providers and cross-cell sharing for decision-side sweep axes.

The fast engine runs in three phases (see ``repro.cachesim.simulator``):

  1. SYSTEM SWEEP — the policy-independent
     :class:`~repro.cachesim.systemstate.SystemTrace`, computed once per
     (trace, system config);
  2. DECISION PLAN — this module: how a given (policy, subroutine)
     configuration turns the sweep's view history into per-request
     selections;
  3. REPLAY — vectorised table lookups + the scalar cost fold
     (``repro.cachesim.fastpath.accumulate_replay``).

Phase 2 is a REGISTRY of :class:`DecisionPlan` providers rather than an
``if/elif`` ladder: ``plan_for(cfg)`` returns the first registered plan
whose :meth:`~DecisionPlan.matches` accepts the configuration, or
``None`` when the configuration is outside every plan's budget (the
simulator then falls back to the reference loop).  The built-in registry,
in match order:

  ================  =====================================================
  ``fna_cal``       speculative segmented replay
                    (``repro.cachesim.fna_cal_fast``) — the one policy
                    whose state moves per probe outcome
  ``pi``            the perfect-information lower bound: a direct
                    vectorised replay (its "table" is the membership bit)
  ``hocs``          Algorithm 1 decision tables via the exact batched
                    mirror ``repro.core.batched.hocs_selection_tables``
  ``ds_pgm``        (version x pattern) tables in one batched
                    ``repro.core.batched.selection_tables`` call
                    (CS_FNA and CS_FNO)
  ``exhaustive``    the batched 2^n-subset enumeration
                    (``repro.core.batched.exhaustive_tables``, chunked;
                    n <= 12 — the full table budget)
  ``scalar``        the generic fallback: one scalar ``sim.alg`` call per
                    (version, pattern) — the ONLY remaining scalar table
                    loop.  No built-in (policy, subroutine, n <= 12)
                    combination reaches it any more; it stays registered
                    as the safety net for externally registered scalar
                    subroutines
  ================  =====================================================

Table plans memoise their ``[V * 2^n]`` selection-bitmask arrays on the
shared ``SystemTrace`` (``st.plan_cache``), keyed by the decision-side
configuration (costs, miss penalty, CS_FNO flag).  That cache is also the
hand-off point for CROSS-CELL sharing: a decision-side sweep axis (miss
penalty, access-cost vector, policy — anything that leaves
``SystemTrace.system_key`` unchanged) produces a group of cells that
differ only in their plan inputs, so :func:`run_cells` computes ONE
system sweep for the whole group and :func:`prefetch_tables` stacks every
ds_pgm-family (cell, policy) table build into a single
``repro.core.batched.selection_tables_cells`` evaluation.  A C-cell,
P-policy decision grid therefore costs one sweep + one stacked table
batch + C*P cheap replays instead of C*P full simulations.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro import obs
from repro.core.batched import MAX_EXHAUSTIVE_TABLE_CACHES

# 2^n table rows per version: past this the reference loop is the better
# deal for every provider (single source of truth for the fast engine)
MAX_TABLE_CACHES = 12


# ---------------------------------------------------------------------------
# Plan protocol
# ---------------------------------------------------------------------------

class DecisionPlan:
    """One policy family's replay strategy against a shared SystemTrace."""

    name = "?"

    def matches(self, cfg) -> bool:
        """Whether this plan covers ``cfg`` (policy, subroutine, budget)."""
        raise NotImplementedError

    def selections(self, sim, st) -> np.ndarray:
        """[N] int64 per-request selection bitmasks for ``sim`` against
        the shared sweep ``st`` — the committed (post-exploration) cache
        subset probed for each request, bit j = cache j.  This is the
        one-hop decision interface: the flat replay folds it into a
        SimResult below, and ``repro.cachesim.topology`` re-accounts the
        same masks under per-tier penalties/latencies."""
        raise NotImplementedError

    def replay(self, sim, st, res):
        """Phase 2+3: produce per-request selections for ``sim`` against
        the shared sweep ``st`` and fold them into ``res``; spanned as
        ``replay.<policy>`` and, beside it, ``replay.fold``."""
        from repro.cachesim.fastpath import accumulate_replay
        with obs.span(f"replay.{sim.cfg.policy}"):
            selm = self.selections(sim, st)
        with obs.span("replay.fold"):
            return accumulate_replay(res, st, selm, list(sim.cfg.costs),
                                     sim.cfg.miss_penalty)


class TablePlan(DecisionPlan):
    """A plan whose decisions are a pure (view version, indication
    pattern) function — phase 2 builds ``[V * 2^n]`` selection bitmasks,
    phase 3 is a vectorised lookup.  Tables are memoised on
    ``st.plan_cache`` under :meth:`cache_key`, which is how the sweep
    runner's stacked prefetch hands them over."""

    def cache_key(self, cfg) -> tuple:
        """The decision-side configuration the tables depend on."""
        raise NotImplementedError

    def tables(self, sim, st) -> np.ndarray:
        """[V * 2^n] int64 selection bitmasks, row (v * 2^n + p)."""
        raise NotImplementedError

    def selections(self, sim, st) -> np.ndarray:
        cfg = sim.cfg
        key = self.cache_key(cfg)
        selm_tab = st.plan_cache.get(key)
        if selm_tab is None:
            selm_tab = self.tables(sim, st)
            st.plan_cache[key] = selm_tab
        k = 1 << st.n
        return selm_tab[st.ver_per_req * k + st.pats]            # [N]


# ---------------------------------------------------------------------------
# Built-in providers
# ---------------------------------------------------------------------------

class FnaCalSegmented(DecisionPlan):
    """The calibrated policy: per-probe EWMA state breaks the frozen-view
    invariant, so it replays via the speculate-and-commit segments of
    ``repro.cachesim.fna_cal_fast`` (whose speculation tables come from
    the same batched builders as the table plans below)."""

    name = "fna_cal"

    def matches(self, cfg) -> bool:
        if cfg.policy != "fna_cal":
            return False
        # the verification pass needs the batched subset enumeration;
        # past its budget the reference loop wins
        return cfg.alg != "exhaustive" or \
            cfg.n_caches <= MAX_EXHAUSTIVE_TABLE_CACHES

    def selections(self, sim, st) -> np.ndarray:
        from repro.cachesim.fna_cal_fast import fna_cal_selections
        return fna_cal_selections(sim, st)


class PiReplay(DecisionPlan):
    """PI accesses the cheapest cache truly holding x; hash placement
    means only the designated cache can — so membership IS the plan:
    probe the designated cache iff it truly holds x, nothing otherwise.
    The default selections-fold replay is bit-identical to a dedicated
    one: a single-cache mask costs exactly ``costs[dj]``, the empty mask
    exactly ``0.0 + miss_penalty == miss_penalty``."""

    name = "pi"

    def matches(self, cfg) -> bool:
        return cfg.policy == "pi"

    def selections(self, sim, st) -> np.ndarray:
        return np.where(st.in_dj, np.int64(1) << st.dj_all, np.int64(0))


class HocsTables(TablePlan):
    """Algorithm 1 on pooled homogeneous estimates, via the exact batched
    mirror (``repro.core.batched.hocs_selection_tables``).  The tables do
    not depend on the (homogeneous) cost level, so a costs-axis decision
    grid shares one build across its cells."""

    name = "hocs"

    def matches(self, cfg) -> bool:
        return cfg.policy == "hocs"

    def cache_key(self, cfg) -> tuple:
        return ("hocs", float(cfg.miss_penalty))

    def tables(self, sim, st) -> np.ndarray:
        from repro.core.batched import hocs_selection_tables
        return hocs_selection_tables(
            st.pi_v, st.nu_v, sim.cfg.miss_penalty).reshape(-1)


class DsPgmTables(TablePlan):
    """CS_FNA / CS_FNO with the DS_PGM subroutine — the batched float64
    NumPy mirror (bit-exact modulo the ~1e-12 near-tie caveat documented
    on ``repro.core.batched.selection_tables``), evaluated on the host."""

    name = "ds_pgm"

    def matches(self, cfg) -> bool:
        return cfg.policy in ("fna", "fno") and cfg.alg == "ds_pgm"

    def cache_key(self, cfg) -> tuple:
        return ("ds_pgm", cfg.policy == "fno", tuple(cfg.costs),
                float(cfg.miss_penalty))

    def tables(self, sim, st) -> np.ndarray:
        from repro.core.batched import selection_tables
        cfg = sim.cfg
        n = st.n
        mask = selection_tables(list(cfg.costs), st.pi_v, st.nu_v,
                                cfg.miss_penalty,
                                fno=(cfg.policy == "fno"), backend="numpy")
        pow2 = 1 << np.arange(n, dtype=np.int64)
        return (mask.reshape(-1, n) @ pow2).astype(np.int64)


class ExhaustiveTables(TablePlan):
    """CS_FNA / CS_FNO with the exact Eq. (10) subroutine — the batched
    2^n-subset enumeration (IEEE operation-order-exact vs the scalar
    loop).  Covers the full table budget (n <= 12 =
    ``MAX_EXHAUSTIVE_TABLE_CACHES``): the build is chunked so the
    [rows, 2^n] subset matrix stays memory-bounded however large the
    version history grows — ``chunk_rows`` overrides the default
    ~32 MB auto-sizing (None) for callers tuning the working set."""

    name = "exhaustive"
    #: rows per subset-DP chunk; None = auto-size from the chunk budget
    #: (``repro.core.batched.EXHAUSTIVE_CHUNK_ELEMS``)
    chunk_rows = None

    def matches(self, cfg) -> bool:
        return cfg.policy in ("fna", "fno") and cfg.alg == "exhaustive" \
            and cfg.n_caches <= MAX_EXHAUSTIVE_TABLE_CACHES

    def cache_key(self, cfg) -> tuple:
        return ("exhaustive", cfg.policy == "fno", tuple(cfg.costs),
                float(cfg.miss_penalty))

    def tables(self, sim, st) -> np.ndarray:
        from repro.core.batched import exhaustive_tables
        cfg = sim.cfg
        return exhaustive_tables(list(cfg.costs), st.pi_v, st.nu_v,
                                 cfg.miss_penalty,
                                 fno=(cfg.policy == "fno"),
                                 chunk=self.chunk_rows).reshape(-1)


class ScalarTables(TablePlan):
    """Generic fallback: one scalar subroutine call per (version,
    pattern).  The only scalar table loop left in the fast engine.  Now
    that the exhaustive provider covers the whole n <= 12 table budget,
    no built-in (policy, subroutine) combination reaches this plan; it
    stays registered as the safety net for externally registered scalar
    subroutines (any ``sim.alg`` without a batched twin)."""

    name = "scalar"

    def matches(self, cfg) -> bool:
        return cfg.policy in ("fna", "fno")

    def cache_key(self, cfg) -> tuple:
        return ("scalar", cfg.alg, cfg.policy == "fno", tuple(cfg.costs),
                float(cfg.miss_penalty))

    def tables(self, sim, st) -> np.ndarray:
        cfg = sim.cfg
        costs = list(cfg.costs)
        M = cfg.miss_penalty
        n = st.n
        k = 1 << n
        fno = cfg.policy == "fno"
        v_count = st.pi_v.shape[0]
        sel = np.empty(v_count * k, dtype=np.int64)
        for v in range(v_count):
            pi, nu = st.pi_v[v], st.nu_v[v]
            for p in range(k):
                if fno:
                    pos = [j for j in range(n) if (p >> j) & 1]
                    chosen = []
                    if pos:
                        sub = sim.alg([costs[j] for j in pos],
                                      [float(pi[j]) for j in pos], M)
                        chosen = [pos[t] for t in sub]
                else:
                    rhos = [float(pi[j]) if (p >> j) & 1 else float(nu[j])
                            for j in range(n)]
                    chosen = sim.alg(costs, rhos, M)
                m = 0
                for j in chosen:
                    m |= 1 << j
                sel[v * k + p] = m
        return sel


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

#: ordered provider registry — first match wins; the scalar fallback last
PROVIDERS: List[DecisionPlan] = [
    FnaCalSegmented(), PiReplay(), HocsTables(), DsPgmTables(),
    ExhaustiveTables(), ScalarTables(),
]


def register_provider(plan: DecisionPlan, *, index: int = 0) -> None:
    """Install a custom provider (at ``index``, so it can shadow a
    built-in; the scalar fallback should stay last)."""
    PROVIDERS.insert(index, plan)


def plan_for(cfg) -> Optional[DecisionPlan]:
    """The first registered plan covering ``cfg``, or ``None`` when the
    configuration is outside every plan's budget (the simulator falls
    back to the reference loop)."""
    if cfg.n_caches > MAX_TABLE_CACHES:
        return None
    for plan in PROVIDERS:
        if plan.matches(cfg):
            return plan
    return None


# ---------------------------------------------------------------------------
# Cross-cell sharing for decision-side sweep axes
# ---------------------------------------------------------------------------

def table_keys_for(cfgs: Sequence, policies: Sequence[str]):
    """Every distinct ``plan_cache`` key a (cells x policies) panel will
    consult, in first-use order — the preload/flush manifest of the
    artifact store (``repro.cachesim.store``)."""
    keys = []
    seen = set()
    for cfg in cfgs:
        for p in policies:
            pcfg = dataclasses.replace(cfg, policy=p)
            plan = plan_for(pcfg)
            if not isinstance(plan, TablePlan):
                continue
            key = plan.cache_key(pcfg)
            if key not in seen:
                seen.add(key)
                keys.append(key)
    return keys


def _plan_jobs(system, cfgs, policies, plan_cls):
    """Unseeded (cache key, configured pcfg) pairs dispatching to
    ``plan_cls``, deduplicated in first-use order."""
    jobs = []
    seen = set()
    for cfg in cfgs:
        for p in policies:
            pcfg = dataclasses.replace(cfg, policy=p)
            plan = plan_for(pcfg)
            if type(plan) is not plan_cls:
                continue
            key = plan.cache_key(pcfg)
            if key in system.plan_cache or key in seen:
                continue
            seen.add(key)
            jobs.append((key, pcfg))
    return jobs


def _prefetch_exhaustive(system, cfgs, policies) -> None:
    """Stack exhaustive-subroutine table builds across decision cells:
    one chunked subset-DP pass per (costs, fno) group covers every
    penalty cell (``repro.core.batched.exhaustive_tables_cells``), so a
    penalty grid pays the 2^n enumeration once instead of per cell."""
    from repro.core.batched import exhaustive_tables_cells
    groups: Dict[tuple, list] = {}
    for key, pcfg in _plan_jobs(system, cfgs, policies, ExhaustiveTables):
        groups.setdefault((tuple(pcfg.costs), pcfg.policy == "fno"),
                          []).append((key, float(pcfg.miss_penalty)))
    for (costs, fno), jobs in groups.items():
        if len(jobs) < 2:    # a single build gains nothing from stacking
            continue
        tabs = exhaustive_tables_cells(
            list(costs), system.pi_v, system.nu_v,
            [m for _, m in jobs], fno=fno)
        for (key, _), tab in zip(jobs, tabs):
            system.plan_cache[key] = tab.reshape(-1)


def _prefetch_hocs(system, cfgs, policies) -> None:
    """Stack HOCS table builds across decision cells: the pooled
    estimates are penalty-independent, so one
    ``repro.core.batched.hocs_selection_tables_cells`` call covers every
    penalty cell of the group."""
    from repro.core.batched import hocs_selection_tables_cells
    jobs = _plan_jobs(system, cfgs, policies, HocsTables)
    if len(jobs) < 2:        # a single build gains nothing from stacking
        return
    tabs = hocs_selection_tables_cells(
        system.pi_v, system.nu_v, [pcfg.miss_penalty for _, pcfg in jobs])
    for (key, _), tab in zip(jobs, tabs):
        system.plan_cache[key] = tab.reshape(-1)


def ds_pgm_jobs(system, cfgs: Sequence, policies: Sequence[str]) -> list:
    """The ds_pgm-family (cell, policy) table builds :func:`prefetch_tables`
    stacks, in stacking order: ``[(cache key, costs, penalty, fno)]``,
    one per distinct key not yet in ``system.plan_cache``."""
    ds_plan = next(p for p in PROVIDERS if isinstance(p, DsPgmTables))
    jobs = []
    seen = set()
    for cfg in cfgs:
        for p in policies:
            pcfg = dataclasses.replace(cfg, policy=p)
            if not isinstance(plan_for(pcfg), DsPgmTables):
                continue
            key = ds_plan.cache_key(pcfg)
            if key in system.plan_cache or key in seen:
                continue
            seen.add(key)
            jobs.append((key, tuple(pcfg.costs),
                         float(pcfg.miss_penalty), p == "fno"))
    return jobs


def prefetch_tables(system, cfgs: Sequence, policies: Sequence[str],
                    *, backend: str = "numpy", mesh=None) -> None:
    """Stack every stackable (cell, policy) table build of a decision-
    side group into one batched call per provider family, seeding
    ``system.plan_cache`` so the per-cell replays become pure lookups:
    ds_pgm via ``repro.core.batched.selection_tables_cells``, the
    exhaustive subroutine via ``exhaustive_tables_cells`` (per (costs,
    fno) group), and HOCS via ``hocs_selection_tables_cells``.

    Row-level independence of each batched builder makes every stacked
    slice bit-identical to the per-cell build it replaces.

    ``backend="jax"`` routes the ds_pgm stacked build through the jitted
    ``selection_tables_cells_jax`` kernel instead — optionally sharded
    over the cell axis of ``mesh`` (``launch.mesh.make_sweep_mesh``).
    Unlike the NumPy path it stacks even a SINGLE job: the jit dispatch
    is the same either way, and seeding the cache keeps every cell's
    tables on the one compiled path.  Masks can differ from the NumPy
    build only inside the ~1e-12 near-tie dead-band (FMA contraction;
    see ``selection_tables_cells_jax``).  The exhaustive/HOCS stacks
    always evaluate on the NumPy oracle.  Spanned as ``tables``.
    """
    with obs.span("tables"):
        _prefetch_exhaustive(system, cfgs, policies)
        _prefetch_hocs(system, cfgs, policies)
        jobs = ds_pgm_jobs(system, cfgs, policies)
        if not jobs:
            return
        if backend == "jax":
            from repro.core.batched import selection_tables_cells_jax
            masks = selection_tables_cells_jax(
                [j[1] for j in jobs], system.pi_v, system.nu_v,
                [j[2] for j in jobs], [j[3] for j in jobs],
                mesh=mesh)                               # [C, V, 2^n, n]
        else:
            if len(jobs) < 2:  # a single build gains nothing from stacking
                return
            from repro.core.batched import selection_tables_cells
            masks = selection_tables_cells(
                [j[1] for j in jobs], system.pi_v, system.nu_v,
                [j[2] for j in jobs], [j[3] for j in jobs])  # [C,V,2^n,n]
        n = system.n
        pow2 = 1 << np.arange(n, dtype=np.int64)
        for (key, *_), mask in zip(jobs, masks):
            system.plan_cache[key] = \
                (mask.reshape(-1, n) @ pow2).astype(np.int64)


def run_cells(trace: np.ndarray, cfgs: Sequence, policies: Sequence[str],
              share_system: bool = True, *, backend: str = "numpy",
              mesh=None, store=None, chunk_size: Optional[int] = None,
              spill=None) -> List[Dict]:
    """Run a policy panel over several decision-side cells that share one
    system evolution; returns ``[{policy: SimResult}]`` aligned with
    ``cfgs``.

    On the fast engine with ``share_system=True`` the policy-independent
    system sweep is computed EXACTLY ONCE for the whole group (all cells
    must share ``SystemTrace.system_key`` — ``repro.cachesim.sweep``
    groups cells accordingly) and the ds_pgm-family decision tables of
    every (cell, policy) are prefetched in one stacked batched call.
    ``share_system=False`` forces independent full runs (benchmarking the
    amortisation itself); the reference engine always runs full.

    ``store`` (an ``ArtifactStore``, a root path, or None) consults the
    content-addressed artifact store (``repro.cachesim.store``) before
    the sweep: a hit hydrates the stored ``SystemTrace`` (bit-identical
    replay) instead of computing, a miss computes and persists it.
    Decision tables are preloaded from the store under the same (trace
    digest, system key) and any freshly built ones are flushed back
    after the replays — on the NumPy backend only, so stored tables are
    always golden-oracle output (a JAX run still loads and benefits
    from them; its near-tie dead-band is documented in
    ``docs/engine.md``).

    ``backend="jax"`` builds the stacked tables with the jitted
    (optionally device-sharded) kernel — ``mesh=None`` auto-creates the
    sweep mesh when more than one device is visible (see
    :func:`prefetch_tables`).  The replay phase is unchanged either way.

    ``chunk_size`` streams every phase-1 sweep this call performs (the
    shared one and any per-cell fallback) through fixed-size trace
    slices; ``spill`` memmap-backs the shared sweep's per-request
    arrays.  Both are bit-identity-preserving — see
    ``SystemTrace.compute``.
    """
    from repro.cachesim.simulator import Simulator
    from repro.cachesim.store import as_store
    from repro.cachesim.systemstate import SystemTrace
    trace = np.asarray(trace, dtype=np.uint64)
    out: List[Dict] = [dict() for _ in cfgs]
    system = None
    share = share_system and bool(cfgs) and trace.shape[0] > 0 and \
        all(cfg.engine == "fast" for cfg in cfgs)
    store = as_store(store) if share else None
    digest = None
    preloaded = set()
    if backend == "jax" and mesh is None:
        from repro.launch.mesh import make_sweep_mesh
        mesh = make_sweep_mesh()
    if share:
        fastable = any(
            plan_for(dataclasses.replace(cfg, policy=p)) is not None
            for cfg in cfgs for p in policies)
        if fastable:
            sys_key = SystemTrace.system_key(cfgs[0])
            if store is not None:
                digest = store.trace_digest(trace)
                system = store.load_sweep(trace, sys_key,
                                          trace_digest=digest)
            if system is None:
                system = SystemTrace.compute(Simulator(cfgs[0]), trace,
                                             chunk_size=chunk_size,
                                             spill=spill)
                if store is not None:
                    store.save_sweep(system, trace_digest=digest)
            if store is not None and backend == "numpy":
                for key in table_keys_for(cfgs, policies):
                    tab = store.load_table(digest, sys_key, key)
                    if tab is not None:
                        system.plan_cache[key] = tab
                        preloaded.add(key)
            prefetch_tables(system, cfgs, policies,
                            backend=backend, mesh=mesh)
    for ci, cfg in enumerate(cfgs):
        for p in policies:
            sim = Simulator(dataclasses.replace(cfg, policy=p))
            out[ci][p] = sim.run(trace,
                                 system=system if share_system else None,
                                 chunk_size=chunk_size)
            if share_system and system is None:
                system = getattr(sim, "last_system", None)
    # flush tables built this run (prefetched or replay-built) so the
    # next warm run starts with every lookup already on disk
    if store is not None and digest is not None and \
            system is not None and backend == "numpy":
        for key, tab in system.plan_cache.items():
            if key not in preloaded:
                store.save_table(digest, system.key, key, tab)
    return out
