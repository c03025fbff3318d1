"""Benchmark harness: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload secv3.full --seed 7 --seconds 51 --trace 0

Everything that belongs to one cell is found by name:

* the configuration: ``BENCHMARK.json`` ``configs[].file``, a JSON
  deployment (``system`` holds ``SimConfig`` fields, ``grid`` the swept
  axis and its values, ``requests`` the trace length);
* the traffic mix: ``bench/traffic/<traffic>.json`` (generator and its
  parameters for ``bench/gen.py``, and the policies);
* each per-layer metric: ``bench/metrics/<name>.py``, whose
  ``read(ctx)`` returns a number or None.

One *job* is one ``repro.cachesim.sweep.run_grid(..., backend="jax")``
call over the cell's trace: phase 1 (the system sweep) on the host,
phase 2 (the stacked DS_PGM tables) on the chip, phase 3 (the replays)
on the host.  Set-up makes the trace from ``--seed`` and runs a job's
sweep and table build, which compiles the job's one device program.
Set-up must end within :data:`SETUP_LIMIT_S` of the process's start: a
run that overruns it ends there with exit code 1, the stacks of its
threads on standard error and no result line.
The window then runs jobs back to back until ``--seconds`` have passed
and finishes the job in flight.  Each job must compute its own sweeps
(``SWEEPS_COMPUTED`` rises by one per system group of the grid) and
compile nothing; a job that breaks either is failed.  After the window, every ``SimResult`` row of every job is
compared with the plain reference (``bench/reference.py``), run in
worker processes on the host's cores.

The last line of standard output is the result object; the numbers
compared, each beside its limit, come last in it and on standard error.
"""
from __future__ import annotations

import contextlib
import faulthandler
import importlib
import json
import os
import resource
import shutil
import sys
import tempfile
import time
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the host spans the traced run places around program calls, named
#: after the call: (span name, module, attribute path)
SPANS = (("SystemTrace.compute", "repro.cachesim.systemstate",
          "SystemTrace.compute"),
         ("prefetch_tables", "repro.cachesim.engine", "prefetch_tables"),
         ("Simulator.run", "repro.cachesim.simulator", "Simulator.run"))
SPAN_NAMES = tuple(s[0] for s in SPANS)
JOB_SPAN = "bench.job"
WINDOW_SPAN = "bench.window"
#: the jitted phase-2 program, by the name XLA gives its module
TABLES_MODULE = "jit__cells_tables_kernel"
#: the exact comparison: rows that differ from the reference
ROWS_LIMIT = 0
#: seconds from the process's start by which set-up must end.  A run has
#: six minutes; this leaves the rest to the window, the job in flight,
#: the reference and the trace's reduction.  The slowest sound set-up,
#: a cold compile of ``secv3.full``, takes about 43 s.
SETUP_LIMIT_S = 150


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --- the cell, by name -------------------------------------------------------

def load_manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def resolve(manifest: dict, workload: str) -> SimpleNamespace:
    """Everything one cell needs, from its name."""
    w = _named(manifest["workloads"], workload, "workload")
    conf_entry = _named(manifest["configs"], w["config"], "config")
    config = json.loads((ROOT / conf_entry["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    grid = config["grid"]
    return SimpleNamespace(
        name=workload, chips=int(w["chips"]), config=config, mix=mix,
        system=config["system"], axis=grid["axis"],
        values=list(grid["values"]), policies=list(mix["policies"]),
        requests=int(config["requests"]),
        end_to_end=manifest["end_to_end"], per_layer=manifest["per_layer"])


def cell_system(cell, value) -> dict:
    """The fleet of one grid cell: the axis value applied to the
    configuration's ``system``."""
    return dict(cell.system, **{cell.axis: value})


def sim_config(system: dict):
    from repro.cachesim import SimConfig
    return SimConfig(**{k: tuple(v) if isinstance(v, list) else v
                        for k, v in system.items()})


def metric_reader(name: str) -> Callable:
    return importlib.import_module(f"bench.metrics.{name}").read


# --- instruments -------------------------------------------------------------

class CompileCounter:
    """Backend compiles, and their seconds, while installed
    (``jax.monitoring``)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


class Spans:
    """Wrap the :data:`SPANS` program calls in ``TraceAnnotation``s for
    the traced run, and count each table build's (cells, versions,
    caches).  Fails when a call is missing from the program."""

    def __init__(self):
        self.tables: List[tuple] = []
        self._saved: List[tuple] = []

    def _wrap(self, span: str, fn: Callable) -> Callable:
        import jax

        def traced(*args, **kwargs):
            with jax.profiler.TraceAnnotation(span):
                return fn(*args, **kwargs)
        return traced

    def _count_tables(self, fn: Callable) -> Callable:
        def counted(system, *args, **kwargs):
            before = {k for k in system.plan_cache if k[0] == "ds_pgm"}
            out = fn(system, *args, **kwargs)
            new = {k for k in system.plan_cache if k[0] == "ds_pgm"}
            v, n = system.pi_v.shape
            self.tables.append((len(new - before), v, n))
            return out
        return counted

    def __enter__(self):
        for span, module, path in SPANS:
            owner = importlib.import_module(module)
            *outer, attr = path.split(".")
            for part in outer:
                owner = getattr(owner, part)
            raw = vars(owner)[attr] if isinstance(owner, type) \
                else getattr(owner, attr)
            self._saved.append((owner, attr, raw))
            fn = raw.__func__ if isinstance(raw, classmethod) else raw
            if span == "prefetch_tables":
                fn = self._count_tables(fn)
            fn = self._wrap(span, fn)
            setattr(owner, attr,
                    classmethod(fn) if isinstance(raw, classmethod) else fn)
        return self

    def __exit__(self, *exc):
        for owner, attr, raw in reversed(self._saved):
            setattr(owner, attr, raw)
        self._saved.clear()


def sweeps_computed() -> int:
    from repro.cachesim import systemstate
    return systemstate.SWEEPS_COMPUTED


# --- one job -----------------------------------------------------------------

def run_job(cell, trace) -> list:
    """One ``run_grid`` call; returns its rows
    ``[(value index, policy, raw accumulator tuple)]``."""
    from repro.cachesim.sweep import run_grid
    from bench.reference import FIELDS
    base = sim_config(cell.system)
    name = cell.mix["generator"]
    grid = run_grid({name: trace}, base, axis=cell.axis, values=cell.values,
                    policies=cell.policies, backend="jax",
                    store=None, workers=0)
    rows = []
    for i, results in enumerate(grid.values()):
        for policy in cell.policies:
            res = results[policy]
            rows.append((i, policy, tuple(getattr(res, f) for f in FIELDS)))
    return rows


def warm_up(cell, trace) -> int:
    """A job's sweeps and stacked table builds, without its replays:
    the set-up that compiles the job's one device program.  Returns the
    sweeps a job computes, one per system group of the grid (as
    ``run_grid`` groups its cells)."""
    import numpy as np

    from repro.cachesim.engine import prefetch_tables
    from repro.cachesim.simulator import Simulator
    from repro.cachesim.systemstate import SystemTrace
    from repro.launch.mesh import make_sweep_mesh
    groups: Dict[tuple, list] = {}
    for v in cell.values:
        cfg = sim_config(cell_system(cell, v))
        groups.setdefault(SystemTrace.system_key(cfg), []).append(cfg)
    trace = np.asarray(trace, dtype=np.uint64)
    for cfgs in groups.values():
        system = SystemTrace.compute(Simulator(cfgs[0]), trace)
        prefetch_tables(system, cfgs, cell.policies, backend="jax",
                        mesh=make_sweep_mesh())
    return len(groups)


# --- the reference -----------------------------------------------------------

_WORKER_TRACE = None


def _init_worker(trace) -> None:
    global _WORKER_TRACE
    _WORKER_TRACE = trace


def _reference_task(system: dict, policy: str) -> tuple:
    from bench.reference import run_row
    return run_row(system, policy, _WORKER_TRACE)


def reference_rows(cell, trace, workers: Optional[int] = None) -> Dict:
    """{(value index, policy): raw tuple} from the plain reference, one
    worker process per (grid cell, policy) task on the host's cores
    (``workers=0`` runs them in this process)."""
    from bench.reference import run_row
    tasks = [((i, p), cell_system(cell, v), p)
             for i, v in enumerate(cell.values) for p in cell.policies]
    if workers is None:
        workers = max(1, len(os.sched_getaffinity(0)) - 1)
    workers = min(workers, len(tasks))
    if workers == 0:
        return {key: run_row(system, p, trace) for key, system, p in tasks}
    import multiprocessing
    saved = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"     # no worker may reach the chip
    try:
        with ProcessPoolExecutor(
                max_workers=workers,
                mp_context=multiprocessing.get_context("spawn"),
                initializer=_init_worker, initargs=(trace,)) as pool:
            futs = {key: pool.submit(_reference_task, system, p)
                    for key, system, p in tasks}
            return {key: f.result() for key, f in futs.items()}
    finally:
        if saved is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = saved


def compare(jobs: List[list], ref: Dict) -> List[int]:
    """Rows of each job that differ from the reference."""
    return [sum(row != ref[(i, p)] for i, p, row in rows) for rows in jobs]


# --- the run -----------------------------------------------------------------

def device_info(require_tpu: bool, chips: int) -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {dev.platform!r} "
                         f"({dev.device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def device_peak_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def host_peak_bytes() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def host_rss_bytes() -> int:
    """The process's resident set now (``VmRSS``)."""
    for line in Path("/proc/self/status").read_text().splitlines():
        if line.startswith("VmRSS:"):
            return int(line.split()[1]) * 1024
    raise RuntimeError("no VmRSS in /proc/self/status")


@contextlib.contextmanager
def setup_deadline(started: float, limit: Optional[float]):
    """End the process, with exit code 1 and every thread's stack on
    standard error, if the block is still running ``limit`` seconds
    after ``started`` (``time.perf_counter``).  A compile is one long
    native call that holds the interpreter lock, so a signal handler or
    a Python timer could not run before it returns; ``faulthandler``'s
    watchdog is a native thread.  ``None`` arms nothing."""
    if limit is None:
        yield
        return
    log(f"bench: set-up must end within {limit:g} s of the start")
    remaining = limit - (time.perf_counter() - started)
    faulthandler.dump_traceback_later(max(remaining, 1e-3), exit=True)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             started: float, require_tpu: bool = True,
             requests: Optional[int] = None,
             ref_workers: Optional[int] = None,
             setup_limit: Optional[float] = None) -> dict:
    """One run of one cell; returns the result object.  ``started`` is
    the process's start on the ``time.perf_counter`` clock;
    ``requests`` overrides the trace length (tests only);
    ``setup_limit`` arms :func:`setup_deadline` over set-up (the
    command line's run only: the watchdog is one per process)."""
    import gc

    import jax

    from bench.gen import make_trace
    cell = resolve(load_manifest(), workload)
    if requests is not None:
        cell.requests = int(requests)
    device = device_info(require_tpu, cell.chips)
    # the runtime's resident set once it holds the chip, for the log
    runtime_rss = host_rss_bytes()
    log(f"bench: {workload} on {device['kind']} x{device['count']} "
        f"({device['platform']}), seed {seed}; "
        f"{time.perf_counter() - started:.3f} s in, host RSS "
        f"{runtime_rss / 1e6:.3f} MB")
    n_rows = len(cell.values) * len(cell.policies)

    # set-up: a job's sweep and table build compile what the window runs
    spans = Spans() if trace else None
    if spans is not None:
        spans.__enter__()
    try:
        with setup_deadline(started, setup_limit):
            tr = make_trace(cell.mix, cell.requests, seed,
                            residues=int(cell.system["n_caches"]))
            log(f"bench: trace made {time.perf_counter() - started:.3f} "
                f"s in")
            with CompileCounter() as warm:
                sweeps_per_job = warm_up(cell, tr)
        setup_s = time.perf_counter() - started
        log(f"bench: set-up {setup_s:.3f} s ({warm.count} compiles, "
            f"{warm.seconds:.3f} s), {sweeps_per_job} sweep(s) per job, "
            f"{cell.requests} requests x {n_rows} rows, host RSS "
            f"{host_rss_bytes() / 1e6:.3f} MB")
        if spans is not None:
            spans.tables.clear()
            prof_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(prof_dir, profiler_options=opts)
        jobs: List[list] = []
        job_failed: List[bool] = []
        job_s: List[float] = []
        with CompileCounter() as compiles:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                t0 = time.perf_counter()
                while True:
                    s0, c0 = sweeps_computed(), compiles.count
                    j0 = time.perf_counter()
                    with jax.profiler.TraceAnnotation(JOB_SPAN):
                        jobs.append(run_job(cell, tr))
                    job_s.append(time.perf_counter() - j0)
                    job_failed.append(
                        sweeps_computed() - s0 != sweeps_per_job
                        or compiles.count != c0)
                    if time.perf_counter() - t0 >= seconds:
                        break
                window_s = time.perf_counter() - t0
        if spans is not None:
            jax.profiler.stop_trace()
    finally:
        if spans is not None:
            spans.__exit__(None, None, None)

    metrics: Dict[str, dict] = {}
    dev_peak = device_peak_bytes()
    host_peak = host_peak_bytes()
    device["memory_peak_bytes"] = dev_peak if dev_peak is not None else 0
    log(f"bench: window {window_s:.3f} s, {len(jobs)} jobs, "
        f"{compiles.count} compiles in the window; jobs "
        f"{' '.join(f'{s:.3f}' for s in job_s)} s; host peak "
        f"{host_peak / 1e6:.3f} MB, {runtime_rss / 1e6:.3f} MB of it the "
        f"runtime's")
    breakdown = None
    if trace:
        flat = _read_profile(prof_dir)
        ctx = _trace_context(flat, len(jobs), spans.tables, device["kind"])
        device["busy_s"] = ctx.busy_s or 0.0
        device["window_s"] = ctx.window_s
        for m in cell.per_layer:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        from bench import tracereduce
        breakdown = {"device_ops": tracereduce.top_ops(flat),
                     "idle_gaps": tracereduce.idle_by_host(
                         flat, ctx.window, SPAN_NAMES)}
        del flat, ctx
    else:
        e2e = {"sim_req_per_s": len(jobs) * cell.requests * n_rows
               / window_s,
               "device_peak_mb": None if dev_peak is None else dev_peak / 1e6,
               "host_peak_mb": host_peak / 1e6,
               "setup_s": setup_s}
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    # the reference, once the window is closed and the program's state
    # is freed
    gc.collect()
    jax.clear_caches()
    t0 = time.perf_counter()
    ref = reference_rows(cell, tr, workers=ref_workers)
    off = compare(jobs, ref)
    failed = sum(bad or o > 0 for bad, o in zip(job_failed, off))
    compared = {
        "rows_off": {"value": sum(off), "limit": ROWS_LIMIT},
        "sweep_or_compile_faults": {"value": sum(job_failed), "limit": 0},
    }
    log(f"bench: reference {time.perf_counter() - t0:.3f} s for "
        f"{len(ref)} rows; compared {len(jobs) * len(ref)} rows of "
        f"{len(jobs)} jobs")
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    for name, c in compared.items():
        log(f"compared: {name} {c['value']} (limit {c['limit']})")
    result = {"correct": correct, "attempted": len(jobs), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def _read_profile(prof_dir: str) -> dict:
    from bench import tracereduce
    try:
        paths = sorted(Path(prof_dir).rglob("*.xplane.pb"))
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        flat = tracereduce.from_profile(str(paths[-1]))
    finally:
        shutil.rmtree(prof_dir, ignore_errors=True)
    for line in tracereduce.summary(flat):
        log(f"trace: {line}")
    return flat


def _trace_context(flat: dict, jobs: int, tables: List[tuple],
                   device_kind: str) -> SimpleNamespace:
    """What the per-layer readers read."""
    from bench import tracereduce
    win = tracereduce.spans(flat, WINDOW_SPAN)
    if len(win) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(win)}")
    missing = [s for s in SPAN_NAMES if not tracereduce.spans(flat, s)]
    if missing:
        raise RuntimeError(f"no {missing} spans in the trace")
    window = win[0][1:]
    window_s = (window[1] - window[0]) * 1e-9
    return SimpleNamespace(
        trace=flat, jobs=jobs, tables=tables, device_kind=device_kind,
        window=window, window_s=window_s,
        busy_s=tracereduce.busy_seconds(flat, window),
        span_names=SPAN_NAMES, tables_module=TABLES_MODULE)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    when that is set, else at the fixed ``<checkout>/.jax_cache``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def main(argv=None, started: Optional[float] = None) -> int:
    import argparse
    started = time.perf_counter() if started is None else started
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    log(f"bench: compile cache {enable_compile_cache()}")
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), started=started,
                      setup_limit=SETUP_LIMIT_S)
    print(json.dumps(result), flush=True)
    return 0
