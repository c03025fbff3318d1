"""Benchmark harness: one entry per paper figure + framework micro-benches.

Prints ``name,us_per_call,derived`` CSV (one line per benchmark):
  * paper figures:  us_per_call = simulated-request latency; derived =
    the figure's headline scalar (see benchmarks/paper_figs.py).
  * router/kernel micro-benches: us_per_call = wall-clock per call on this
    host; derived = the relevant throughput/quality scalar.

``python -m benchmarks.run [--full] [--only section[,section...]]
[--interpret auto|on|off] [--json PATH]``

``--json`` additionally writes every record as a JSON list of
``{"name", "us_per_call", "derived"}`` objects (plus any per-row context
fields a section attaches — table row counts, device counts) — the CI
bench-smoke job
uploads it as the ``BENCH_sim.json`` artifact so the perf trajectory
accumulates per commit, and gates on the headline speedups.

Every JSON record also carries ``ru_maxrss`` — the harness process's
peak RSS in KB (``getrusage(RUSAGE_SELF)``, Linux semantics) sampled
right after the row ran.  It is a process HIGH-WATER mark, monotone
across rows within one run; rows that need per-path isolation (the
``sim_ingest`` section) measure in child processes and report their own
numbers in the extras.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

try:
    import resource
except ImportError:                      # non-POSIX host
    resource = None


def _ru_maxrss() -> int:
    """Peak RSS of this process in KB (0 where getrusage is missing)."""
    if resource is None:
        return 0
    return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)


def paper_fig_benches(full: bool):
    from benchmarks.paper_figs import FIGS, _scale, run_fig

    out = []
    for name in FIGS:
        rows, derived, dt = run_fig(name, full)
        reqs = _scale(full)[0] * max(len(rows), 1)
        us = dt / max(reqs, 1) * 1e6
        out.append((name, us, derived))
    return out


def router_bench(full: bool):
    """Batched FNA router (paper technique on the serving path): wall-clock
    per routed request, JAX jitted on this host — once on a synthetic
    16-cache fleet, once on a scenario-registry configuration
    (``hetero_tiers``: cheap-small/expensive-large tiers) whose (q, FP,
    FN) views and indication patterns come from a short simulator run."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.batched import cs_fna_batched

    def _time_router(costs, q, fp, fn, ind, miss_penalty):
        f = jax.jit(lambda i: cs_fna_batched(i, costs, q, fp, fn,
                                             miss_penalty))
        f(ind).block_until_ready()
        iters = 50 if full else 20
        t0 = time.time()
        for _ in range(iters):
            f(ind).block_until_ready()
        dt = (time.time() - t0) / iters
        return dt / ind.shape[0] * 1e6, float(np.asarray(f(ind)).mean())

    out = []
    n, b = 16, 4096
    rng = np.random.default_rng(0)
    us, mean = _time_router(
        jnp.asarray(rng.uniform(1, 3, n), jnp.float32),
        jnp.asarray(rng.uniform(0.2, 0.8, n), jnp.float32),
        jnp.asarray(rng.uniform(0.001, 0.05, n), jnp.float32),
        jnp.asarray(rng.uniform(0.0, 0.4, n), jnp.float32),
        jnp.asarray(rng.random((b, n)) < 0.3, jnp.int32), 100.0)
    out.append(("router_cs_fna_batched", us, mean))

    # registry-defined heterogeneous regime (scenario hetero_tiers): the
    # router's views are the END-OF-RUN estimates of a short fast-engine
    # run, its request batch the run's actual indication patterns.  The
    # stale-advertisement grid cell (update_interval=512, 20k requests)
    # is the paper's FN-heavy regime — the views are informative and the
    # router genuinely trades positive vs negative accesses (a fresher
    # cell degenerates to all-empty selections)
    from repro.cachesim import Simulator, get_scenario, get_trace
    sc = get_scenario("hetero_tiers")
    cfg = sc.config(policy="fna", update_interval=512)
    trace = get_trace(sc.traces[0], 20_000, seed=sc.seed)
    sim = Simulator(cfg)
    sim.run(trace)
    st = sim.last_system
    us, mean = _time_router(
        jnp.asarray(cfg.costs, jnp.float32),
        jnp.asarray([s["q"] for s in st.final_state["q"]], jnp.float32),
        jnp.asarray(st.fp_v[-1], jnp.float32),
        jnp.asarray(st.fn_v[-1], jnp.float32),
        jnp.asarray(st.ind_all[-b:].astype(np.int32)),
        cfg.miss_penalty)
    out.append(("router_cs_fna_hetero_tiers", us, mean))
    return out


def kernel_benches(full: bool, interpret=None):
    from benchmarks.kernels import run_kernel_benches
    return run_kernel_benches(full, interpret=interpret)


def sim_benches(full: bool):
    """Trace-simulator throughput (fast engine per policy x trace, plus the
    fast-vs-reference speedup on the 200k gradle headline)."""
    from benchmarks.sim import run_sim_benches
    return run_sim_benches(full)


def sim_jax_benches(full: bool):
    """JAX/Pallas table-core rows: jitted (and device-sharded) decision
    table builds vs the NumPy mirror on the Fig. 3 grid shape."""
    from benchmarks.sim import run_jax_benches
    return run_jax_benches(full)


def sim_store_benches(full: bool):
    """Artifact-store perf tier: warm-store speedup on the Fig. 3 grid
    (CI-gated >= 5x) and the parallel phase-1 farm speedup (recorded)."""
    from benchmarks.sim import run_store_benches
    return run_store_benches(full)


def sim_advert_benches(full: bool):
    """Advertisement-event subsystem: cost-vs-bandwidth Pareto rows for
    the self-adjusting policy vs a budget-matched fixed cadence (the
    ``advert_bandwidth_pareto`` summary is CI-gated >= 1)."""
    from benchmarks.sim import run_advert_benches
    return run_advert_benches(full)


def sim_topology_benches(full: bool):
    """Hierarchical topologies (``repro.cachesim.topology``): 3-level
    tree throughput on the Fig. 3 workload plus the
    ``topology_sweep_amortisation`` ratio — shared per-tier sweeps vs
    per-cell recompute across a topology axis (CI-gated >= 2x)."""
    from benchmarks.sim import run_topology_benches
    return run_topology_benches(full)


def sim_ingest_benches(full: bool):
    """Streaming trace ingestion: 10M-request log generation, one-shot vs
    streaming statistics in isolated child processes, and the
    ``ingest_peak_rss_ratio`` row (CI-gated <= 0.5)."""
    from benchmarks.sim import run_ingest_benches
    return run_ingest_benches(full)


def serving_bench(full: bool):
    from benchmarks.serving import run_serving_bench
    return run_serving_bench(full)


def router_replay_bench(full: bool):
    """Concurrent-client router replay: throughput + p50/p99 decision
    latency per scenario-defined regime, plus a batch-size sweep."""
    from benchmarks.serving import run_replay_benches
    return run_replay_benches(full)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale parameters")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of sections to run")
    ap.add_argument("--interpret", choices=("auto", "on", "off"), default="auto",
                    help="Pallas interpret mode for kernel benches "
                         "(auto = from JAX backend: compiled on TPU)")
    ap.add_argument("--json", default="",
                    help="also write records to this path as JSON")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None
    interpret = {"auto": None, "on": True, "off": False}[args.interpret]

    sections = {
        "paper": paper_fig_benches,
        "router": router_bench,
        "kernels": lambda full: kernel_benches(full, interpret=interpret),
        "sim": sim_benches,
        "sim_jax": sim_jax_benches,
        "sim_store": sim_store_benches,
        "sim_advert": sim_advert_benches,
        "sim_topology": sim_topology_benches,
        "sim_ingest": sim_ingest_benches,
        "serving": serving_bench,
        "router_replay": router_replay_bench,
    }
    if only:
        unknown = sorted(only - set(sections))
        if unknown:
            # a typo'd --only used to run NOTHING and exit 0 — fail loudly
            ap.error(f"unknown --only section(s): {', '.join(unknown)} "
                     f"(valid: {', '.join(sections)})")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(Path(__file__).resolve().parent.parent)
    records = []
    print("name,us_per_call,derived")
    for sec, fn in sections.items():
        if only and sec not in only:
            continue
        # rows are (name, us, derived[, extras]); extras is an optional
        # dict of context fields (row counts, device counts, ...) merged
        # into the JSON record — the CSV stays 3 columns
        for name, us, derived, *rest in fn(args.full):
            print(f"{name},{us:.3f},{derived:.6g}")
            sys.stdout.flush()
            rec = {"name": name, "us_per_call": us,
                   "derived": float(derived), "ru_maxrss": _ru_maxrss()}
            if rest:
                rec.update(rest[0])
            records.append(rec)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)


if __name__ == "__main__":
    main()
