"""Plain per-request reference of the simulated cache fleet.

The benchmark's yardstick for ``correct``: a straightforward scalar
loop over the requests, written against the paper (arXiv:2102.01724,
Sec. II-V) and independent of the program under test.  It imports
nothing of the program and takes nothing the program made: it builds
its own LRU caches, counting Bloom filters, stale advertised bitmaps,
Eq. (7)-(9) estimates and policy decisions from the configuration and
the request trace alone.

It is a copy, trimmed to the deployments the benchmark runs (periodic
advertisements, the DS_PGM subroutine), of the per-request reference
loop the repository pins its golden files with
(``SimConfig(engine="reference")`` in ``repro.cachesim.simulator`` and
the scalar helpers it calls in ``repro.core``).  Every floating-point
operation is kept in the original's order, so the two agree bit for
bit; ``bench/tests/test_bench_reference.py`` checks that on a small
trace.

    row = run_row(system, policy, trace)   # raw accumulator tuple

``system`` is the configuration's ``system`` mapping with the grid
cell's overrides applied (see ``bench/configs/*.json``).
"""
from __future__ import annotations

import math
from collections import OrderedDict

import numpy as np

EPS = 1e-12
MASK64 = (1 << 64) - 1

#: the raw accumulators one replay produces, in tuple order
FIELDS = ("n_requests", "total_cost", "hits", "pos_accesses",
          "neg_accesses", "fn_events", "fn_opportunities", "fp_events",
          "fp_opportunities", "resident")

#: configuration keys the reference understands, with the defaults the
#: paper's Sec. V setup uses
DEFAULTS = dict(n_caches=3, cache_size=10_000, costs=(1.0, 2.0, 3.0),
                miss_penalty=100.0, bpe=14.0, update_interval=1_000,
                est_interval=50, q_horizon=100, q_delta=0.25, seed=0,
                cal_gamma=0.05, cal_min_obs=30, cal_epsilon=0.005)


# --- hashing and indicators (paper Sec. IV-A/IV-B) -------------------------

def splitmix64(x: np.ndarray) -> np.ndarray:
    z = (x + np.uint64(0x9E3779B97F4A7C15)) & np.uint64(MASK64)
    z = ((z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)) \
        & np.uint64(MASK64)
    z = ((z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)) \
        & np.uint64(MASK64)
    return z ^ (z >> np.uint64(31))


def optimal_k(bpe: float) -> int:
    return max(1, round(math.log(2.0) * bpe))


def hash_indices(keys, k: int, m: int, seed: int) -> np.ndarray:
    """[len(keys), k] bit indices by double hashing of two splitmix64
    streams."""
    keys = np.asarray(keys, dtype=np.uint64)
    h1 = splitmix64(keys ^ np.uint64(seed * 0x9E3779B97F4A7C15 & MASK64))
    h2 = splitmix64(keys ^ np.uint64(0xDEADBEEFCAFEBABE)) | np.uint64(1)
    i = np.arange(k, dtype=np.uint64)[None, :]
    return ((h1[:, None] + i * h2[:, None]) % np.uint64(m)).astype(np.int64)


class Cache:
    """One cache: LRU contents, a counting Bloom filter kept up to date,
    the last advertised (stale) bitmap, and the Eq. (7)-(8) estimates."""

    def __init__(self, size: int, bpe: float, seed: int,
                 update_interval: int, est_interval: int):
        self.capacity = int(size)
        self.lru: "OrderedDict[int, None]" = OrderedDict()
        self.m = int(bpe * size)
        self.k = optimal_k(bpe)
        self.seed = seed
        self.counters = np.zeros(self.m, dtype=np.uint8)
        self.stale = np.zeros(self.m, dtype=bool)
        self.fn_est = 0.0
        self.fp_est = 0.0
        self.update_interval = update_interval
        self.est_interval = est_interval
        self.version = 0
        self.since_adv = 0
        self.since_est = 0
        self.advertise()

    def idx(self, key: int) -> np.ndarray:
        return hash_indices(np.asarray([key], dtype=np.uint64),
                            self.k, self.m, self.seed)[0]

    def advertise(self) -> None:
        self.stale = (self.counters > 0).copy()

    def estimate_rates(self) -> None:
        updated = self.counters > 0
        b1 = int(np.count_nonzero(updated))
        d1 = int(np.count_nonzero(updated & ~self.stale))
        d0 = int(np.count_nonzero(~updated & self.stale))
        self.fn_est = 1.0 - ((b1 - d1) / b1) ** self.k if b1 > 0 else 0.0
        self.fp_est = ((b1 - d1 + d0) / self.m) ** self.k

    def insert(self, key: int, idx: np.ndarray) -> None:
        """Place a missed key: LRU put, CBF bookkeeping, then the
        estimation and advertisement cadences counted in insertions."""
        if key in self.lru:
            self.lru.move_to_end(key)
            return
        evicted = None
        if len(self.lru) >= self.capacity:
            evicted, _ = self.lru.popitem(last=False)
        self.lru[key] = None
        c = self.counters
        c[idx] = np.minimum(c[idx].astype(np.int32) + 1, 255)
        if evicted is not None:
            eidx = self.idx(evicted)
            c[eidx] = np.maximum(c[eidx].astype(np.int32) - 1, 0)
        self.since_adv += 1
        self.since_est += 1
        if self.since_est >= self.est_interval:
            self.estimate_rates()
            self.since_est = 0
            self.version += 1
        if self.since_adv >= self.update_interval:
            self.advertise()
            self.estimate_rates()
            self.since_adv = 0
            self.since_est = 0
            self.version += 1


class QEstimate:
    """Eq. (9): the client's positive-indication ratio, per epoch."""

    def __init__(self, horizon: int, delta: float):
        self.horizon = int(horizon)
        self.delta = float(delta)
        self.q = 0.5
        self.version = 0
        self.count = 0
        self.positives = 0
        self.bootstrapped = False

    def observe(self, indication: bool) -> None:
        self.count += 1
        self.positives += int(indication)
        if self.count >= self.horizon:
            frac = self.positives / self.count
            if not self.bootstrapped:
                self.q = frac
                self.bootstrapped = True
            else:
                self.q = self.delta * frac + (1.0 - self.delta) * self.q
            self.version += 1
            self.count = 0
            self.positives = 0


# --- the cost model and policies (paper Sec. II-III) -----------------------

def clamp01(x: float) -> float:
    return min(1.0, max(0.0, x))


def hit_ratio_from_q(q: float, fp: float, fn: float) -> float:
    denom = 1.0 - fp - fn
    if abs(denom) < EPS:
        return clamp01(q)
    return clamp01((q - fp) / denom)


def exclusion_probabilities(h: float, fp: float, fn: float):
    q = h * (1.0 - fn) + (1.0 - h) * fp
    pi = clamp01(fp * (1.0 - h) / q) if q > EPS else 1.0
    nu = clamp01((1.0 - fp) * (1.0 - h) / (1.0 - q)) \
        if (1.0 - q) > EPS else 0.0
    return pi, nu


def ds_pgm(costs, rhos, miss_penalty) -> list:
    """Potential-gain order, then the best prefix under Eq. (10)."""
    n = len(costs)

    def key(j: int) -> float:
        r = min(max(rhos[j], EPS), 1.0 - EPS)
        return costs[j] / -math.log(r)

    order = sorted(range(n), key=key)
    best_sel = []
    best_cost = miss_penalty
    run_cost, run_prod = 0.0, 1.0
    for i, j in enumerate(order):
        run_cost += costs[j]
        run_prod *= rhos[j]
        v = run_cost + miss_penalty * run_prod
        if v < best_cost - EPS:
            best_cost = v
            best_sel = order[: i + 1]
    return sorted(best_sel)


def argmin_geometric(m_eff: float, rho: float, r_max: int) -> int:
    if r_max <= 0:
        return 0
    if rho <= EPS:
        return 1 if m_eff > 1.0 else 0
    if rho >= 1.0 - EPS:
        return 0
    l = math.log(1.0 / rho)
    r_cont = math.log(max(m_eff * l, EPS)) / l
    best_r, best_v = 0, m_eff
    for r in sorted({0, 1, int(math.floor(r_cont)), int(math.ceil(r_cont)),
                     r_max}):
        if 0 <= r <= r_max:
            v = r + m_eff * rho ** r
            if v < best_v - EPS:
                best_r, best_v = r, v
    return best_r


def hocs(n_x: int, n: int, pi: float, nu: float, miss_penalty: float):
    """Algorithm 1: (r0*, r1*) negative and positive accesses."""
    r1 = argmin_geometric(miss_penalty, pi, n_x)
    r0 = 0
    residual = miss_penalty * (pi ** r1)
    if residual > 1.0:
        r0 = argmin_geometric(residual, nu, n - n_x)
    return r0, r1


def per_cache(value, n: int) -> tuple:
    if isinstance(value, (list, tuple)):
        if len(value) != n:
            raise ValueError(f"per-cache value {value!r} needs {n} entries")
        return tuple(value)
    return (value,) * n


def run_row(system: dict, policy: str, trace) -> tuple:
    """Replay ``trace`` through the fleet ``system`` under ``policy``
    (fna, fno, pi, hocs or fna_cal); returns the raw accumulators in
    :data:`FIELDS` order."""
    unknown = set(system) - set(DEFAULTS)
    if unknown:
        raise ValueError(f"the reference does not model {sorted(unknown)}")
    cfg = dict(DEFAULTS, **system)
    n = int(cfg["n_caches"])
    costs = [float(c) for c in cfg["costs"]]
    if len(costs) != n:
        raise ValueError(f"costs {costs} for {n} caches")
    M = float(cfg["miss_penalty"])
    seed = int(cfg["seed"])
    sizes = per_cache(cfg["cache_size"], n)
    bpes = per_cache(cfg["bpe"], n)
    upd = per_cache(cfg["update_interval"], n)
    est = per_cache(cfg["est_interval"], n)
    caches = [Cache(int(sizes[j]), float(bpes[j]), seed * 1000 + j,
                    int(upd[j]), int(est[j])) for j in range(n)]
    q_est = [QEstimate(cfg["q_horizon"], cfg["q_delta"]) for _ in range(n)]
    trace = np.asarray(trace, dtype=np.uint64)
    count = trace.shape[0]
    pi_v = [1.0] * n
    nu_v = [1.0] * n
    view_ver = [None] * n
    cal = policy == "fna_cal"
    g = float(cfg["cal_gamma"])
    min_obs = int(cfg["cal_min_obs"])
    nu_emp, pi_emp = [0.90] * n, [0.5] * n
    nu_obs, pi_obs = [0] * n, [0] * n
    rng_cal = np.random.default_rng(seed + 12345)
    eps_draws = rng_cal.random(count) if cal else None
    eps_pick = rng_cal.integers(0, n, count) if cal else None
    idx_all = [hash_indices(trace, c.k, c.m, c.seed) for c in caches]
    acc = dict.fromkeys(FIELDS, 0)
    acc["total_cost"] = 0.0
    for i in range(count):
        x = int(trace[i])
        ind = [bool(caches[j].stale[idx_all[j][i]].all()) for j in range(n)]
        for qe, b in zip(q_est, ind):
            qe.observe(b)
        dj = x % n
        in_dj = x in caches[dj].lru
        if in_dj:
            acc["fn_opportunities"] += 1
            acc["fn_events"] += int(not ind[dj])
            acc["resident"] += 1
        else:
            acc["fp_opportunities"] += 1
            acc["fp_events"] += int(ind[dj])
        if policy == "pi":
            best, best_c = None, None
            for j in range(n):
                if x in caches[j].lru and (best_c is None
                                           or costs[j] < best_c):
                    best, best_c = j, costs[j]
            sel = [] if best is None else [best]
        else:
            for j, c in enumerate(caches):      # views move with estimates
                ver = (c.version, q_est[j].version)
                if view_ver[j] != ver:
                    h = hit_ratio_from_q(q_est[j].q, c.fp_est, c.fn_est)
                    pi_v[j], nu_v[j] = exclusion_probabilities(
                        h, c.fp_est, c.fn_est)
                    view_ver[j] = ver
            if cal:
                rhos = []
                for j in range(n):
                    uninformative = (caches[j].fp_est
                                     + caches[j].fn_est) >= 0.95
                    if ind[j]:
                        emp = pi_obs[j] >= min_obs or uninformative
                        rhos.append(pi_emp[j] if emp else pi_v[j])
                    else:
                        emp = nu_obs[j] >= min_obs or uninformative
                        rhos.append(nu_emp[j] if emp else nu_v[j])
                sel = ds_pgm(costs, rhos, M)
                if eps_draws[i] < cfg["cal_epsilon"]:
                    jx = int(eps_pick[i])
                    if jx not in sel:
                        sel = sorted(sel + [jx])
            elif policy == "hocs":
                pos = [j for j in range(n) if ind[j]]
                neg = [j for j in range(n) if not ind[j]]
                r0, r1 = hocs(len(pos), n, sum(pi_v) / n, sum(nu_v) / n, M)
                sel = sorted(pos[:r1] + neg[:r0])
            elif policy == "fna":
                sel = ds_pgm(costs, [pi_v[j] if ind[j] else nu_v[j]
                                     for j in range(n)], M)
            elif policy == "fno":
                pos = [j for j in range(n) if ind[j]]
                sel = [pos[t] for t in ds_pgm([costs[j] for j in pos],
                                              [pi_v[j] for j in pos], M)] \
                    if pos else []
            else:
                raise ValueError(f"unknown policy {policy!r}")
            if cal:
                for j in sel:
                    absent = x not in caches[j].lru
                    if ind[j]:
                        pi_emp[j] = (1 - g) * pi_emp[j] + g * absent
                        pi_obs[j] += 1
                    else:
                        nu_emp[j] = (1 - g) * nu_emp[j] + g * absent
                        nu_obs[j] += 1
        cost = sum(costs[j] for j in sel)
        hit = any(x in caches[j].lru for j in sel)
        if not hit:
            cost += M
        acc["total_cost"] += cost
        acc["hits"] += int(hit)
        acc["pos_accesses"] += sum(1 for j in sel if ind[j])
        acc["neg_accesses"] += sum(1 for j in sel if not ind[j])
        acc["n_requests"] += 1
        caches[dj].insert(x, idx_all[dj][i])
    return tuple(acc[f] for f in FIELDS)
