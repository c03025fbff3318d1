#!/usr/bin/env python3
"""Drive the simulator's device path once on a TPU and check every result.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded table build only

One chip runs three phases in this one process:

  1. The paper's Sec. V system (3 caches x 10,000 entries, bpe 14, costs
     (1, 2, 3), advertisements every 200 insertions) on the ``gradle``
     trace, 1,000,000 requests from seed 0, over the 8 miss penalties of
     the ``fig3_penalty_shared`` scenario and the policies fna, fno, pi,
     hocs and fna_cal — through ``run_grid(backend="jax")``, whose
     phase-2 decision tables are the device's work.  The same grid under
     ``backend="numpy"`` is the oracle: every ``SimResult.to_dict()`` must
     be equal, except for cells whose tables differ from the NumPy tables
     in rows of the near-tie band (two prefix costs or two potential-gain
     keys within a relative 1e-9), which are counted and printed.  Any
     other difference fails the run.
  2. The Bloom probe kernel, compiled, on 8 caches x 10,000 entries x
     bpe 14 (filters padded to 18,432 bytes) probing 4,096 keys, equal to
     ``bloom_probe_ref``; then ``cs_fna_batched`` in float32 on those
     indications, equal to the scalar float64 DS_PGM outside the float32
     near-tie band (relative 1e-3).
  3. The subset-DP kernel, compiled, in float32 at n=8 on 4,096 rows,
     bit-equal to ``subset_prod_ref`` jitted at float32.

``--chips 4`` runs only the Sec. V grid's table build on the 4-chip
"cells" mesh against one chip (``mesh=None``); the tables must be
identical.

Earlier lines report what was measured; the last line of standard output
is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count":
...}}``.  A failed phase raises and the script exits non-zero without
that line, as it does when JAX finds no TPU.  Traces, filters and keys
are generated from seeds; nothing outside the checkout is read.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.cachesim import SimConfig, Simulator, SystemTrace  # noqa: E402
from repro.cachesim.engine import ds_pgm_jobs  # noqa: E402
from repro.cachesim.scenarios import get_scenario  # noqa: E402
from repro.cachesim.sweep import run_grid  # noqa: E402
from repro.cachesim.traces import get_trace  # noqa: E402
from repro.core.batched import (  # noqa: E402
    EPS, _cells_tables_kernel, cells_tables_args, selection_tables_cells)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SEC_V_REQUESTS = 1_000_000
SEC_V_POLICIES = ("fna", "fno", "pi", "hocs", "fna_cal")
F64_TIE_MARGIN = 1e-9
F32_TIE_MARGIN = 1e-3
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends in backend compiles while installed."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


def near_tie_rows(costs, rhos, penalty, allowed, margin):
    """[R] bool: DS_PGM rows whose decision a relative ``margin`` of
    evaluation error could flip — two allowed potential-gain keys or the
    two best prefix costs within ``margin`` of each other.  Evaluated in
    float64 in the order of ``ds_pgm_batched``."""
    rhos = np.asarray(rhos, np.float64)
    r_count, n = rhos.shape
    costs = np.broadcast_to(np.asarray(costs, np.float64), (r_count, n))
    allowed = np.broadcast_to(np.asarray(allowed, bool), (r_count, n))
    m = np.broadcast_to(np.asarray(penalty, np.float64), (r_count,))
    r = np.clip(rhos, EPS, 1.0 - EPS)
    key = np.where(allowed, costs / -np.log(r), np.inf)
    order = np.argsort(key, axis=1, kind="stable")
    ks = np.take_along_axis(key, order, 1)
    finite = np.isfinite(ks[:, 1:])
    with np.errstate(invalid="ignore"):     # inf - inf past the allowed
        gap = ks[:, 1:] - ks[:, :-1]
    key_tie = np.any(finite & (gap > 0)
                     & (gap <= margin * np.maximum(np.abs(ks[:, :-1]), 1.0)),
                     axis=1)
    ok = np.take_along_axis(allowed, order, 1)
    c_sorted = np.where(ok, np.take_along_axis(costs, order, 1), np.inf)
    l_sorted = np.where(ok, np.log(np.take_along_axis(r, order, 1)), 0.0)
    phi = np.concatenate([m[:, None], np.cumsum(c_sorted, axis=1)
                          + m[:, None] * np.exp(np.cumsum(l_sorted, axis=1))],
                         axis=1)
    two = np.sort(phi, axis=1)[:, :2]
    cost_tie = (two[:, 1] - two[:, 0]) <= margin * np.maximum(
        np.abs(two[:, 0]), 1.0)
    return key_tie | cost_tie


def _table_rhos(pi_v, nu_v):
    """[V*2^n, n] rho rows and [V*2^n, n] indication bits of a table."""
    v, n = pi_v.shape
    pats = (np.arange(1 << n)[:, None] >> np.arange(n)[None, :]) & 1
    rhos = np.where(pats[None] > 0, pi_v[:, None, :], nu_v[:, None, :])
    return rhos.reshape(-1, n), np.tile(pats, (v, 1)).astype(bool)


def _sec_v_setup(n_requests):
    base = SimConfig(update_interval=200)
    penalties = get_scenario("fig3_penalty_shared").values
    trace = np.asarray(get_trace("gradle", n_requests, seed=base.seed),
                       np.uint64)
    t0 = time.perf_counter()
    system = SystemTrace.compute(Simulator(base), trace)
    sweep_s = time.perf_counter() - t0
    cfgs = [dataclasses.replace(base, miss_penalty=m) for m in penalties]
    jobs = ds_pgm_jobs(system, cfgs, SEC_V_POLICIES)
    v, n = system.pi_v.shape
    log(f"sec_v: trace gradle requests={trace.shape[0]} "
        f"unique={np.unique(trace).shape[0]} caches={n} "
        f"cache_size={base.cache_size} bpe={base.bpe} "
        f"update_interval={base.update_interval} "
        f"penalties={list(penalties)} policies={list(SEC_V_POLICIES)}")
    log(f"sec_v: phase-1 sweep {sweep_s:.3f} s on the host; view versions "
        f"V={v}; ds_pgm table cells C={len(jobs)}, rows per cell "
        f"{v << n}, rows in all {len(jobs) * (v << n)}")
    return base, penalties, trace, system, jobs


def _device_tables(system, jobs, mesh):
    """Compile and run the phase-2 table program exactly as
    ``selection_tables_cells_jax`` stages it; returns the [C, V*2^n, n]
    masks."""
    import jax
    c = len(jobs)
    with jax.enable_x64(True):
        args = cells_tables_args(
            [j[1] for j in jobs], system.pi_v, system.nu_v,
            [j[2] for j in jobs], [j[3] for j in jobs], mesh=mesh)
        t0 = time.perf_counter()
        compiled = _cells_tables_kernel.lower(*args).compile()
        compile_s = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        run_s = time.perf_counter() - t0
        tables = np.asarray(out)[:c]
    dtypes = sorted({str(a.dtype) for a in args})
    log(f"phase2: program compiled in {compile_s:.3f} s, ran in "
        f"{run_s:.3f} s (first call); argument dtypes {dtypes}; "
        f"per-device temp {mem.temp_size_in_bytes} B, arguments "
        f"{mem.argument_size_in_bytes} B, output "
        f"{mem.output_size_in_bytes} B")
    return tables


def sec_v_grid(n_requests: int = SEC_V_REQUESTS) -> None:
    """Phase 1: run_grid(backend="jax") against backend="numpy"."""
    import jax
    base, penalties, trace, system, jobs = _sec_v_setup(n_requests)
    n = system.n
    tables_jax = _device_tables(system, jobs, mesh=None)

    # the NumPy oracle's tables and the near-tie classification
    tables_np = selection_tables_cells(
        [j[1] for j in jobs], system.pi_v, system.nu_v,
        [j[2] for j in jobs], [j[3] for j in jobs]).reshape(len(jobs), -1, n)
    rhos, pats = _table_rhos(system.pi_v, system.nu_v)
    flipped = {}
    band_rows = flipped_rows = 0
    for ci, (key, costs, m, fno) in enumerate(jobs):
        band = near_tie_rows(costs, rhos, m, pats if fno else True,
                             F64_TIE_MARGIN)
        diff = np.any(tables_np[ci] != tables_jax[ci], axis=1)
        outside = int(np.sum(diff & ~band))
        if outside:
            raise AssertionError(
                f"phase2 tables: {outside} rows of cell (penalty={m}, "
                f"fno={fno}) differ from NumPy outside the near-tie band")
        band_rows += int(band.sum())
        flipped_rows += int(diff.sum())
        flipped[(m, "fno" if fno else "fna")] = int(diff.sum())
    log(f"phase2: near-tie band rows {band_rows} of "
        f"{len(jobs) * rhos.shape[0]}; rows where device and NumPy tables "
        f"differ {flipped_rows} (all inside the band)")

    grid = dict(traces={"gradle": trace}, base=base, axis="miss_penalty",
                values=penalties, policies=SEC_V_POLICIES,
                store=None, workers=0)
    t0 = time.perf_counter()
    ref = run_grid(backend="numpy", **grid)
    numpy_s = time.perf_counter() - t0
    with CompileClock() as clock:
        t0 = time.perf_counter()
        got = run_grid(backend="jax", **grid)
        jax_s = time.perf_counter() - t0
    log(f"sec_v: run_grid wall {numpy_s:.3f} s (numpy), {jax_s:.3f} s "
        f"(jax; {clock.count} backend compiles, {clock.seconds:.3f} s)")

    same = tied = 0
    for cell, results in ref.items():
        for policy, res in results.items():
            if got[cell][policy].to_dict() == res.to_dict():
                same += 1
            elif flipped.get((cell[1], policy), 0):
                tied += 1
            else:
                raise AssertionError(
                    f"sec_v: {cell} {policy}: backend='jax' result "
                    f"{got[cell][policy].to_dict()} != numpy {res.to_dict()}")
    log(f"sec_v: SimResult rows equal {same}, differing only through "
        f"near-tie table rows {tied}")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"sec_v: device peak bytes in use {stats['peak_bytes_in_use']}")


def sharded_tables(count: int) -> None:
    """``--chips 4``: the Sec. V table build on the cells mesh of every
    visible chip against one chip (``mesh=None``); must be identical."""
    from repro.launch.mesh import make_sweep_mesh
    mesh = make_sweep_mesh()
    if mesh is None or mesh.size != count:
        raise AssertionError(
            f"sharded: expected a {count}-device cells mesh, got {mesh}")
    _, _, _, system, jobs = _sec_v_setup(SEC_V_REQUESTS)
    one = _device_tables(system, jobs, mesh=None)
    many = _device_tables(system, jobs, mesh=mesh)
    if not np.array_equal(one, many):
        rows = int(np.any(one != many, axis=-1).sum())
        raise AssertionError(
            f"sharded: {rows} table rows differ between {count} chips "
            f"and one")
    log(f"sharded: tables on the {count}-chip mesh equal one chip's "
        f"({one.shape[0]} cells x {one.shape[1]} rows)")


def bloom_and_router(interpret: bool = False, n_keys: int = 4096) -> None:
    """Phase 2: the Bloom probe kernel and the float32 CS_FNA decision."""
    import jax
    import jax.numpy as jnp

    from repro.core import CacheView, cs_fna, optimal_k, rho_vector
    from repro.core.batched import cs_fna_batched
    from repro.kernels.bloom import bloom_probe_ref, build_indicator
    from repro.kernels.bloom.bloom import BYTE_BLOCK, bloom_probe_pallas

    n, entries, bpe = 8, 10_000, 14
    k = optimal_k(bpe)
    mbytes = -(-entries * bpe // (8 * BYTE_BLOCK)) * BYTE_BLOCK
    rng = np.random.default_rng(0)
    members = rng.integers(0, 2**31 - 1, (n, entries), dtype=np.int64)
    bits = jnp.stack([build_indicator(jnp.asarray(members[j], jnp.int32),
                                      mbytes * 8, k, seed=j)
                      for j in range(n)])
    # half the probes are members of a known cache, half random keys
    owner = rng.integers(0, n, n_keys // 2)
    keys = np.concatenate([
        members[owner, rng.integers(0, entries, n_keys // 2)],
        rng.integers(0, 2**31 - 1, n_keys - n_keys // 2)]).astype(np.int32)
    seeds = jnp.arange(n, dtype=jnp.int32)
    got = np.asarray(bloom_probe_pallas(bits, jnp.asarray(keys), seeds, k=k,
                                        interpret=interpret))
    ref = np.asarray(bloom_probe_ref(bits, jnp.asarray(keys), k))
    if not np.array_equal(got, ref):
        raise AssertionError(
            f"bloom: {int(np.sum(got != ref))} indications differ from "
            f"bloom_probe_ref")
    if not np.all(got[np.arange(n_keys // 2), owner] == 1):
        raise AssertionError("bloom: a member key probed negative")
    log(f"bloom: compiled={not interpret} caches={n} entries={entries} "
        f"bpe={bpe} k={k} filter_bytes={mbytes} keys={n_keys}: equal to "
        f"bloom_probe_ref; positive share {got.mean():.6f}")

    # CS_FNA on those indications in float32 against the scalar DS_PGM
    costs = rng.uniform(1.0, 3.0, n).astype(np.float32)
    q = rng.uniform(0.05, 0.5, n).astype(np.float32)
    fp = rng.uniform(0.001, 0.05, n).astype(np.float32)
    fn = rng.uniform(0.0, 0.3, n).astype(np.float32)
    penalty = 1000.0
    ind = got.astype(np.int32)
    mask = np.asarray(jax.jit(cs_fna_batched)(
        jnp.asarray(ind), jnp.asarray(costs), jnp.asarray(q),
        jnp.asarray(fp), jnp.asarray(fn), penalty))
    if mask.dtype != np.bool_ or mask.shape != (n_keys, n):
        raise AssertionError(f"router: mask {mask.dtype} {mask.shape}")
    views = [CacheView(float(c), float(p), float(f), float(qq))
             for c, p, f, qq in zip(costs, fp, fn, q)]
    scalar = np.zeros((n_keys, n), bool)
    rhos = np.empty((n_keys, n))
    for i in range(n_keys):
        scalar[i, cs_fna(views, ind[i].tolist(), penalty)] = True
        rhos[i] = rho_vector(views, ind[i].tolist())
    band = near_tie_rows(costs.astype(np.float64), rhos, penalty, True,
                         F32_TIE_MARGIN)
    diff = np.any(mask != scalar, axis=1)
    if np.any(diff & ~band):
        raise AssertionError(
            f"router: {int(np.sum(diff & ~band))} float32 CS_FNA rows "
            f"differ from the scalar DS_PGM outside the near-tie band")
    log(f"router: cs_fna_batched float32 on {n_keys} rows: float32 near-tie "
        f"band rows {int(band.sum())}, differing rows {int(diff.sum())} "
        f"(all inside the band); caches accessed per row "
        f"{np.bincount(mask.sum(1), minlength=n + 1).tolist()}")


def subset_dp(interpret: bool = False, n: int = 8, rows: int = 4096) -> None:
    """Phase 3: the float32 subset-DP kernel against its jnp oracle."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.subsetdp import subset_prod_pallas
    from repro.kernels.subsetdp.ref import subset_prod_ref

    rng = np.random.default_rng(1)
    rhos = jnp.asarray(rng.uniform(0.0, 1.0, (rows, n)), jnp.float32)
    penalty = np.float32(100.0)
    got = np.asarray(subset_prod_pallas(rhos, penalty, interpret=interpret))
    want = np.asarray(jax.jit(subset_prod_ref)(rhos, penalty))
    if got.dtype != np.float32 or got.tobytes() != want.tobytes():
        raise AssertionError(
            f"subsetdp: {int(np.sum(got != want))} of {got.size} float32 "
            f"subset products differ from subset_prod_ref")
    log(f"subsetdp: compiled={not interpret} float32 n={n} rows={rows}: "
        f"bit-equal to jitted subset_prod_ref ({got.size} values)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the Sec. V table build on a 4-chip mesh "
                         "against one chip")
    args = ap.parse_args(argv)
    log(f"compile cache: {enable_compile_cache(ROOT)}")
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
            f"({dev.device_kind})")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} TPUs, JAX found {len(devices)}")
    log(f"device: {dev.device_kind} x{len(devices)} ({dev.platform})")

    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_tables(len(devices))
    else:
        from repro.kernels.bloom.bloom import default_interpret as bloom_auto
        from repro.kernels.subsetdp import default_interpret as sdp_auto
        if bloom_auto() or sdp_auto():
            raise AssertionError("kernels would run in interpret mode on TPU")
        sec_v_grid()
        bloom_and_router(interpret=False)
        subset_dp(interpret=False)
    log(f"wall: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
