"""Simulator throughput benchmarks: requests/sec per policy x trace on the
fast engine, the headline fast-vs-reference comparisons
(``sim_speedup_fna_gradle``, ``sim_speedup_fna_cal_gradle``), and the
shared-SystemTrace amortisation rows: multi-policy runs
(``sweep_amortisation``) and decision-side grid cells
(``sweep_amortisation_decision`` — the Fig. 3 miss-penalty axis as one
sweep + stacked tables + replays vs per-cell full runs).

CSV columns: us_per_call = wall-clock per simulated request; derived =
requests/sec (or the speedup/amortisation factor for the ``sim_speedup`` /
``sweep_amortisation*`` rows).  Speedup/amortisation rows attach an
extras dict (JSON only) recording the workload shape behind the ratio —
request counts, and for table-build rows the (cells x versions x
patterns) row counts — so a perf trajectory across commits can tell a
regression from a workload change.

``run_jax_benches`` (section ``sim_jax``) covers the jitted table core:
the stacked (Fig. 3 penalty-grid-shaped) decision-table build on the
JAX backend vs the per-cell NumPy mirror (``sim_tables_jax_speedup`` —
CI gates this >= 1), the device-sharding efficiency of the same build
(``sweep_shard_efficiency``), and the Pallas subset-DP kernel in
interpret mode with an inline bit-exactness assert against the NumPy
oracle (``sim_subsetdp_pallas_interpret``).

``run_store_benches`` (section ``sim_store``) covers the artifact-store
perf tier (``repro.cachesim.store``): ``sweep_store_warm_speedup`` — the
Fig. 3 penalty grid cold vs warm-store, with an inline bit-identity
assert between the two grids (CI gates this >= 5) — and
``sweep_parallel_speedup`` — a 4-group system axis serial vs
``run_grid(workers=4)``, fresh store per measurement (recorded, not
gated: spawn + import overhead makes it machine-dependent).

``run_ingest_benches`` (section ``sim_ingest``) covers the streaming
trace-ingestion tier (``repro.cachesim.tracefiles``): a 10M-request
synthetic wiki log is generated chunk-written by ``tools/
make_trace_file.py`` in a scratch directory, then statted twice in
SEPARATE child processes — one-shot (``parse_trace_file`` +
``trace_info``, the full array materialised) vs streaming
(``stream_trace_info``, O(chunk + catalog) memory) — with an inline
equality assert between the two :class:`TraceInfo` results.  Each child
reports its own ``ru_maxrss`` process high-water, so the
``ingest_peak_rss_ratio`` row (streaming / one-shot peak RSS; CI gates
this <= 0.5) measures the paths in isolation rather than whichever
allocator high-water the bench process accumulated first.

``run_topology_benches`` (section ``sim_topology``) covers the
hierarchical-topology tier (``repro.cachesim.topology``):
``sim_topology_tree`` — requests/sec through a 3-level fanout-2 tree on
the Fig. 3 workload — and ``topology_sweep_amortisation`` — the same
tree swept along a decision-side ``hop_penalty`` axis with one shared
:class:`SweepPool` vs per-cell recompute, with an inline bit-identity
assert between the two grids (CI gates this >= 2: cross-cell tier-sweep
sharing must at least halve the grid's wall-clock).

``run_advert_benches`` (section ``sim_advert``) covers the
advertisement-event subsystem (``repro.cachesim.advert``): per-bandwidth
``advert_pareto_bw*`` rows compare the self-adjusting policy's cost
against a fixed-cadence baseline advertising the SAME per-cache event
count (equal bytes-on-wire budget — both send full bitmaps), and the
``advert_bandwidth_pareto`` summary row records the worst ratio across
the bandwidth grid (CI gates this >= 1: drift-triggered advertisement
must not lose to uniform cadence at equal budget).
"""
from __future__ import annotations

import time

HEADLINE_REQUESTS = 200_000      # the acceptance benchmark (gradle)
POLICIES = ("fna", "fno", "pi", "hocs", "fna_cal")
SWEEP_POLICIES = POLICIES
#: the decision-axis amortisation grid (miss_penalty is decision-side:
#: every cell shares one SystemTrace per trace)
DECISION_PENALTIES = (25.0, 50.0, 75.0, 100.0, 150.0, 250.0, 500.0, 1000.0)
DECISION_POLICIES = ("fna", "fno", "pi")


def _run_once(cfg, trace):
    from repro.cachesim import Simulator
    t0 = time.time()
    Simulator(cfg).run(trace)
    return time.time() - t0


def run_sim_benches(full: bool):
    from repro.cachesim import SimConfig, get_trace
    from repro.cachesim.simulator import run_policies
    from repro.cachesim.traces import TRACES

    out = []
    # --- headline: fast vs reference, 200k-request gradle trace ---------
    # (fna exercises the table replay, fna_cal the speculative segmented
    # replay — the acceptance thresholds track both)
    trace = get_trace("gradle", HEADLINE_REQUESTS, seed=0)
    n_ref = HEADLINE_REQUESTS if full else HEADLINE_REQUESTS // 5
    for policy in ("fna", "fna_cal"):
        fast_cfg = SimConfig(engine="fast", policy=policy)
        _run_once(fast_cfg, trace)       # warm numpy/XLA caches
        dt_fast = min(_run_once(fast_cfg, trace) for _ in range(2))
        dt_ref = _run_once(
            SimConfig(engine="reference", policy=policy), trace[:n_ref])
        rps_fast = HEADLINE_REQUESTS / dt_fast
        rps_ref = n_ref / dt_ref
        out.append((f"sim_throughput_fast_{policy}_gradle",
                    dt_fast / HEADLINE_REQUESTS * 1e6, rps_fast))
        out.append((f"sim_throughput_ref_{policy}_gradle",
                    dt_ref / n_ref * 1e6, rps_ref))
        out.append((f"sim_speedup_{policy}_gradle",
                    dt_fast / HEADLINE_REQUESTS * 1e6, rps_fast / rps_ref,
                    {"n_requests": HEADLINE_REQUESTS,
                     "n_requests_ref": n_ref}))

    # --- shared-SystemTrace amortisation: 1 sweep + P replays vs P full
    # runs over the same (trace, system config); min-of-2 on both sides
    # like the headline rows, so a load spike can't skew the ratio --------
    n_amort = HEADLINE_REQUESTS if full else 150_000
    tr = get_trace("gradle", n_amort, seed=0)
    base = SimConfig(engine="fast", costs=(2.0, 2.0, 2.0))
    run_policies(tr, base, policies=SWEEP_POLICIES)          # warm

    def _time_policies(**kw):
        t0 = time.time()
        run_policies(tr, base, policies=SWEEP_POLICIES, **kw)
        return time.time() - t0

    dt_shared = min(_time_policies() for _ in range(2))
    dt_indep = min(_time_policies(share_system=False) for _ in range(2))
    out.append(("sweep_amortisation",
                dt_shared / (n_amort * len(SWEEP_POLICIES)) * 1e6,
                dt_indep / dt_shared,
                {"n_requests": n_amort, "policies": len(SWEEP_POLICIES)}))

    # --- decision-side cross-cell sharing: a miss-penalty grid (the
    # Fig. 3 axis) computes ONE SystemTrace for all its cells and stacks
    # the ds_pgm tables into one batched call, vs per-cell full runs ----
    from repro.cachesim.sweep import run_grid
    n_dec = 100_000 if full else 50_000
    grid_traces = {"gradle": get_trace("gradle", n_dec, seed=0)}
    dec_base = SimConfig(engine="fast", update_interval=200)

    def _time_grid(shared: bool) -> float:
        t0 = time.time()
        run_grid(grid_traces, dec_base, "miss_penalty", DECISION_PENALTIES,
                 policies=DECISION_POLICIES, share_system=shared)
        return time.time() - t0

    _time_grid(True)                                         # warm
    dt_dec_shared = min(_time_grid(True) for _ in range(2))
    dt_dec_indep = min(_time_grid(False) for _ in range(2))
    cells = len(DECISION_PENALTIES) * len(DECISION_POLICIES)
    out.append(("sweep_amortisation_decision",
                dt_dec_shared / (n_dec * cells) * 1e6,
                dt_dec_indep / dt_dec_shared,
                {"n_requests": n_dec, "cells": len(DECISION_PENALTIES),
                 "policies": len(DECISION_POLICIES)}))

    # --- requests/sec per policy x trace (fast engine) ------------------
    n_req = 100_000 if full else 30_000
    for trace_name in TRACES:
        tr = get_trace(trace_name, n_req, seed=0)
        for policy in POLICIES:
            costs = (2.0, 2.0, 2.0) if policy == "hocs" else (1.0, 2.0, 3.0)
            cfg = SimConfig(policy=policy, costs=costs, engine="fast")
            dt = _run_once(cfg, tr)
            out.append((f"sim_{policy}_{trace_name}", dt / n_req * 1e6,
                        n_req / dt))
    return out


def run_jax_benches(full: bool):
    """JAX/Pallas table-core rows (section ``sim_jax``); see the module
    docstring.  Runs entirely on host/CPU (the Pallas row uses interpret
    mode), so the CI smoke job covers every row."""
    import numpy as np

    from repro.cachesim import SimConfig, Simulator, get_trace
    from repro.cachesim.systemstate import SystemTrace
    from repro.core.batched import (
        _subset_dp,
        selection_tables,
        selection_tables_cells_jax,
    )
    from repro.kernels.subsetdp import subset_dp
    from repro.launch.mesh import make_sweep_mesh

    out = []
    # --- the Fig. 3 grid shape: a real SystemTrace view history, every
    # (penalty x fna/fno) decision cell stacked — jitted build vs the
    # per-cell NumPy mirror (the fast engine's two table backends) -------
    n_req = 100_000 if full else 50_000
    trace = get_trace("gradle", n_req, seed=0)
    cfg = SimConfig(engine="fast", update_interval=200)
    st = SystemTrace.compute(Simulator(cfg), trace)
    pi_v, nu_v = st.pi_v, st.nu_v
    v, n = pi_v.shape
    k = 1 << n
    cells = [(np.asarray(cfg.costs, np.float64), m, f)
             for m in DECISION_PENALTIES for f in (False, True)]
    c = len(cells)
    rows = c * v * k
    costs_cells = np.stack([j[0] for j in cells])
    penalties = np.asarray([j[1] for j in cells])
    fno_cells = np.asarray([j[2] for j in cells])

    def _numpy_build():
        t0 = time.time()
        for costs, m, f in cells:
            selection_tables(costs, pi_v, nu_v, m, fno=f, backend="numpy")
        return time.time() - t0

    def _jax_build(mesh=None):
        t0 = time.time()
        selection_tables_cells_jax(costs_cells, pi_v, nu_v, penalties,
                                   fno_cells, mesh=mesh)
        return time.time() - t0

    _jax_build()                                  # compile + warm
    dt_np = min(_numpy_build() for _ in range(3))
    dt_jax = min(_jax_build() for _ in range(3))
    out.append(("sim_tables_jax_speedup", dt_jax / rows * 1e6,
                dt_np / dt_jax,
                {"rows": rows, "cells": c, "versions": v, "patterns": k}))

    # --- device sharding: same stacked build over the sweep mesh; the
    # efficiency is (t_single / t_sharded) / devices, 1.0 on one device --
    mesh = make_sweep_mesh()
    devices = 1 if mesh is None else int(mesh.size)
    if mesh is None:
        dt_sharded, eff = dt_jax, 1.0
    else:
        _jax_build(mesh)                          # compile + warm
        dt_sharded = min(_jax_build(mesh) for _ in range(3))
        eff = (dt_jax / dt_sharded) / devices
    out.append(("sweep_shard_efficiency", dt_sharded / rows * 1e6, eff,
                {"rows": rows, "devices": devices}))

    # --- Pallas subset-DP kernel, interpret mode (CPU CI): throughput in
    # table rows/sec, with an inline bit-exactness assert vs the oracle --
    rng = np.random.default_rng(0)
    n_dp = 8
    b_dp = 4096 if full else 1024
    dp_costs = rng.uniform(0.05, 5.0, n_dp)
    dp_rhos = rng.uniform(0.0, 1.0, (b_dp, n_dp))
    ref = _subset_dp(dp_costs, dp_rhos, 100.0)
    got = subset_dp(dp_costs, dp_rhos, 100.0, backend="pallas",
                    interpret=True)
    assert got.tobytes() == ref.tobytes(), \
        "Pallas subset-DP drifted off the NumPy oracle"
    t0 = time.time()
    iters = 3
    for _ in range(iters):
        subset_dp(dp_costs, dp_rhos, 100.0, backend="pallas",
                  interpret=True)
    dt = (time.time() - t0) / iters
    out.append(("sim_subsetdp_pallas_interpret", dt / b_dp * 1e6,
                b_dp / dt, {"rows": b_dp, "n_caches": n_dp}))
    return out


#: the cost-vs-advertisement-bandwidth Pareto grid (bytes per insertion)
ADVERT_BANDWIDTHS = (1.0, 4.0, 16.0)


def run_advert_benches(full: bool):
    """Advert-subsystem rows (section ``sim_advert``); see the module
    docstring.  The fixed-cadence baseline is MATCHED per bandwidth: its
    per-cache ``update_interval`` is chosen so it advertises the same
    number of (full-bitmap) events the self-adjusting run actually made,
    i.e. both sides spend the same wire budget — the comparison isolates
    WHEN to advertise, the axis arXiv:2104.01386 optimises."""
    from repro.cachesim import SimConfig, Simulator, get_trace

    out = []
    n_req = 100_000 if full else 50_000
    trace = get_trace("gradle", n_req, seed=0)
    system = dict(cache_size=2_000, est_interval=50)
    ratios = []
    for bw in ADVERT_BANDWIDTHS:
        cfg = SimConfig(engine="fast", policy="fna",
                        advert_policy="self_adjusting",
                        advert_bandwidth=bw, advert_threshold=0.05,
                        **system)
        sim = Simulator(cfg)
        t0 = time.time()
        res_sa = sim.run(trace)
        dt = time.time() - t0
        nodes = sim.last_system.final_state["nodes"]
        events = [len(nd["adv_ins"]) for nd in nodes]
        n_ins = [nd["n_ins"] for nd in nodes]
        # same per-cache event count on a uniform cadence (insertion
        # dynamics are advert-independent, so n_ins carries over exactly)
        upd = tuple(max(1, n // max(e, 1))
                    for n, e in zip(n_ins, events))
        res_fx = Simulator(SimConfig(engine="fast", policy="fna",
                                     update_interval=upd,
                                     **system)).run(trace)
        ratio = res_fx.mean_cost / res_sa.mean_cost
        ratios.append(ratio)
        out.append((f"advert_pareto_bw{bw:g}", dt / n_req * 1e6, ratio,
                    {"bandwidth": bw,
                     "advert_events": int(res_sa.advert_events),
                     "advert_bytes": float(res_sa.advert_bytes),
                     "mean_cost_self_adjusting": res_sa.mean_cost,
                     "mean_cost_fixed": res_fx.mean_cost,
                     "baseline_update_interval": list(upd),
                     "baseline_advert_events": int(res_fx.advert_events),
                     "n_requests": n_req}))
    out.append(("advert_bandwidth_pareto", 0.0, min(ratios),
                {"bandwidths": list(ADVERT_BANDWIDTHS),
                 "ratios": [round(r, 4) for r in ratios],
                 "n_requests": n_req}))
    return out


#: the streaming-ingestion benchmark log (the ISSUE/CI acceptance size)
INGEST_REQUESTS = 10_000_000
#: catalog of the synthetic wiki log — kept moderate so the token -> id
#: dict (paid by BOTH paths) doesn't drown the array memory the
#: streaming path exists to avoid
INGEST_CATALOG = 100_000
#: streaming child's chunk size — the knob that bounds its peak memory
INGEST_CHUNK = 1 << 16

# child payloads for the two measured ingestion paths; each prints one
# JSON object {wall_s, maxrss_kb, info} and nothing else
_INGEST_ONESHOT = """\
import json, resource, sys, time
from repro.cachesim.tracefiles import parse_trace_file, trace_info
path = sys.argv[1]
t0 = time.perf_counter()
ids = parse_trace_file(path, fmt="keys")
info = trace_info(ids, path=path, fmt="keys")
wall = time.perf_counter() - t0
print(json.dumps({"wall_s": wall,
                  "maxrss_kb": resource.getrusage(
                      resource.RUSAGE_SELF).ru_maxrss,
                  "info": info.to_dict()}))
"""
_INGEST_STREAM = """\
import json, resource, sys, time
from repro.cachesim.tracefiles import stream_trace_info
path, chunk = sys.argv[1], int(sys.argv[2])
t0 = time.perf_counter()
info = stream_trace_info(path, fmt="keys", chunk_size=chunk)
wall = time.perf_counter() - t0
print(json.dumps({"wall_s": wall,
                  "maxrss_kb": resource.getrusage(
                      resource.RUSAGE_SELF).ru_maxrss,
                  "info": info.to_dict()}))
"""


def run_ingest_benches(full: bool):
    """Streaming-ingestion rows (section ``sim_ingest``); see the module
    docstring.  Linux ``ru_maxrss`` is in KB; the extras record MB."""
    import json
    import os
    import shutil
    import subprocess
    import sys
    import tempfile
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(repo / "src")] + ([env["PYTHONPATH"]]
                               if env.get("PYTHONPATH") else []))
    # the children only parse with NumPy, but importing repro.cachesim
    # imports JAX: keep them off the chip this process may hold
    env["JAX_PLATFORMS"] = "cpu"

    def _child(code: str, *argv: str) -> dict:
        proc = subprocess.run([sys.executable, "-c", code, *argv],
                              env=env, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(f"ingest child failed:\n{proc.stderr}")
        return json.loads(proc.stdout)

    out = []
    n = INGEST_REQUESTS
    tmp = tempfile.mkdtemp(prefix="repro-bench-ingest-")
    try:
        log = Path(tmp) / "wiki_10m.log"
        t0 = time.time()
        subprocess.run(
            [sys.executable, str(repo / "tools" / "make_trace_file.py"),
             "--generator", "wiki", "--n", str(n), "--seed", "0",
             "--kw", f"catalog={INGEST_CATALOG}",
             "--format", "keys", "-o", str(log)],
            env=env, check=True, capture_output=True, text=True)
        dt_gen = time.time() - t0
        out.append(("ingest_make_log_10m", dt_gen / n * 1e6, n / dt_gen,
                    {"n_requests": n, "bytes": log.stat().st_size}))

        one = _child(_INGEST_ONESHOT, str(log))
        stream = _child(_INGEST_STREAM, str(log), str(INGEST_CHUNK))
        assert stream["info"] == one["info"], \
            f"streaming TraceInfo drifted: {stream['info']} vs {one['info']}"
        for name, r in (("ingest_oneshot_10m", one),
                        ("ingest_stream_10m", stream)):
            out.append((name, r["wall_s"] / n * 1e6, n / r["wall_s"],
                        {"n_requests": n,
                         "maxrss_mb": round(r["maxrss_kb"] / 1024, 1),
                         "n_unique": r["info"]["n_unique"],
                         "top1pct_share": r["info"]["top1pct_share"]}))
        ratio = stream["maxrss_kb"] / one["maxrss_kb"]
        out.append(("ingest_peak_rss_ratio", 0.0, ratio,
                    {"n_requests": n, "chunk_size": INGEST_CHUNK,
                     "stream_maxrss_mb": round(stream["maxrss_kb"] / 1024, 1),
                     "oneshot_maxrss_mb": round(one["maxrss_kb"] / 1024, 1)}))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def run_store_benches(full: bool):
    """Artifact-store rows (section ``sim_store``); see the module
    docstring.  Both rows use throwaway store roots, so the benchmark
    never reads from — or pollutes — a developer's ``REPRO_STORE``."""
    import os
    import shutil
    import tempfile

    from repro.cachesim import ArtifactStore, SimConfig, get_trace
    from repro.cachesim.sweep import run_grid

    out = []
    # --- warm-store speedup on the Fig. 3 penalty axis over a 6-cache
    # fleet (the Fig. 7 scale): one sweep + one stacked 2^6-pattern
    # table build cold, pure hydrate + replay warm.  The CI gate (>= 5x)
    # is the acceptance criterion for the store actually paying for
    # itself; locally this lands >= 12x, so the gate has headroom for
    # shared-runner noise -----------------------------------------------
    n_req = 100_000 if full else 50_000
    traces = {"gradle": get_trace("gradle", n_req, seed=0)}
    base = SimConfig(engine="fast", update_interval=200, n_caches=6,
                     costs=(2.0,) * 6)
    policies = ("fna", "fno")

    def _time_grid(store=None):
        t0 = time.time()
        grid = run_grid(traces, base, "miss_penalty", DECISION_PENALTIES,
                        policies=policies, store=store)
        return time.time() - t0, grid

    _time_grid()                                              # warm caches
    dt_cold, grid_cold = min((_time_grid() for _ in range(2)),
                             key=lambda r: r[0])
    root = tempfile.mkdtemp(prefix="repro-bench-store-")
    try:
        store = ArtifactStore(root)
        _time_grid(store)                                     # populate
        dt_warm, grid_warm = min((_time_grid(store) for _ in range(2)),
                                 key=lambda r: r[0])
        assert grid_warm == grid_cold, \
            "store-hydrated grid drifted off cold compute"
        cells = len(DECISION_PENALTIES) * len(policies)
        out.append(("sweep_store_warm_speedup",
                    dt_warm / (n_req * cells) * 1e6, dt_cold / dt_warm,
                    {"n_requests": n_req, "cells": len(DECISION_PENALTIES),
                     "policies": len(policies),
                     "sweep_hits": store.stats["sweep_hits"],
                     "sweep_misses": store.stats["sweep_misses"],
                     "table_hits": store.stats["table_hits"],
                     "table_misses": store.stats["table_misses"]}))
    finally:
        shutil.rmtree(root, ignore_errors=True)

    # --- parallel phase-1 farm: 4 independent system-key groups, serial
    # vs a 4-process spawn pool; every measurement gets a FRESH store so
    # both sides always compute all 4 sweeps ----------------------------
    n_par = 100_000 if full else 50_000
    par_traces = {"gradle": get_trace("gradle", n_par, seed=0)}
    intervals = (100, 200, 400, 800)
    # floor 2 so the spawn-pool path always runs (on a 1-core box the
    # row then records the farm's overhead, which is the honest number)
    workers = max(2, min(4, os.cpu_count() or 1))

    def _time_parallel(w: int):
        root = tempfile.mkdtemp(prefix="repro-bench-par-")
        try:
            t0 = time.time()
            grid = run_grid(par_traces, base, "update_interval", intervals,
                            policies=policies, store=ArtifactStore(root),
                            workers=w)
            return time.time() - t0, grid
        finally:
            shutil.rmtree(root, ignore_errors=True)

    dt_ser, grid_ser = min((_time_parallel(0) for _ in range(2)),
                           key=lambda r: r[0])
    dt_par, grid_par = min((_time_parallel(workers) for _ in range(2)),
                           key=lambda r: r[0])
    assert grid_par == grid_ser, "parallel grid drifted off serial"
    out.append(("sweep_parallel_speedup",
                dt_par / (n_par * len(intervals)) * 1e6, dt_ser / dt_par,
                {"n_requests": n_par, "groups": len(intervals),
                 "workers": workers}))
    return out


def run_topology_benches(full: bool):
    """Hierarchical-topology rows (section ``sim_topology``); see the
    module docstring."""
    from repro.cachesim import SimConfig, get_trace
    from repro.cachesim.topology import TopoConfig, run_topo_grid, run_topology

    out = []
    n_req = 100_000 if full else 40_000
    traces = {"gradle": get_trace("gradle", n_req, seed=0)}
    base = TopoConfig(
        base=SimConfig(engine="fast", update_interval=200),
        kind="tree", depth=3, fanout=2,
        tiers=(dict(cache_size=2_000, update_interval=100,
                    tier_latency=1.0),
               dict(cache_size=6_000, update_interval=200,
                    tier_latency=4.0),
               dict(cache_size=12_000, update_interval=400,
                    tier_latency=16.0)),
        origin_latency=64.0)

    # --- tree throughput: one 3-level fanout-2 cell, full policy panel
    policies = ("fna", "fna_cal", "fno", "pi")
    t0 = time.time()
    run_topology(traces["gradle"], base, policies)   # warm caches
    t0 = time.time()
    run_topology(traces["gradle"], base, policies)
    dt = time.time() - t0
    out.append(("sim_topology_tree", dt / n_req * 1e6, n_req / dt,
                {"n_requests": n_req, "depth": base.depth,
                 "fanout": base.fanout, "policies": len(policies)}))

    # --- cross-cell sweep amortisation: hop_penalty is decision-side
    # (outside every tier's system key), so the shared pool computes the
    # 7 tier sweeps ONCE for the whole axis and replays per cell, while
    # share_system=False recomputes them per cell.  fna + pi keep the
    # replay side cheap so the ratio isolates the sweep sharing
    amort_policies = ("fna", "pi")
    penalties = (0.0, 2.0, 8.0, 32.0)

    def _time_axis(share: bool):
        t0 = time.time()
        grid = run_topo_grid(traces, base, "hop_penalty", penalties,
                             policies=amort_policies, share_system=share)
        return time.time() - t0, grid

    _time_axis(True)                                 # warm caches
    dt_shared, grid_shared = min((_time_axis(True) for _ in range(2)),
                                 key=lambda r: r[0])
    dt_cold, grid_cold = min((_time_axis(False) for _ in range(2)),
                             key=lambda r: r[0])
    assert grid_shared == grid_cold, \
        "shared-pool topology grid drifted off per-cell recompute"
    out.append(("topology_sweep_amortisation",
                dt_shared / (n_req * len(penalties)) * 1e6,
                dt_cold / dt_shared,
                {"n_requests": n_req, "cells": len(penalties),
                 "policies": len(amort_policies), "depth": base.depth,
                 "fanout": base.fanout}))
    return out
