"""Content-addressed artifact store (``repro.cachesim.store``) suite.

The store's contract has three load-bearing claims, each pinned here:

  * **bit-identity** — a store-hydrated ``SystemTrace`` replays exactly
    like cold compute, across every golden scenario x policy, and the
    ``run_grid(workers=N)`` parallel path is bit-identical to serial;
  * **structural invalidation** — any input change (a trace byte, a
    system-side config field, the schema version) misses by
    construction; corrupt/truncated entries read as misses and rebuild;
  * **durability** — concurrent writers racing on one entry leave a
    loadable archive (atomic ``os.replace``).

Plus the satellite integrations: the tracefiles parse cache routed
through a ``REPRO_STORE`` root (with legacy next-to-source fallback) and
the ``tools/store_tool.py`` maintenance CLI.
"""
import dataclasses
import multiprocessing
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import repro.cachesim.store as store_mod
import repro.cachesim.systemstate as systemstate
from repro.cachesim import (
    ArtifactStore,
    SimConfig,
    SimResult,
    Simulator,
    get_scenario,
)
from repro.cachesim.scenarios import GOLDEN_SCENARIOS, run_scenario
from repro.cachesim.sweep import _sweep_worker, run_grid
from repro.cachesim.systemstate import SystemTrace
from repro.cachesim.traces import get_trace

RESULT_FIELDS = tuple(f.name for f in dataclasses.fields(SimResult))

PENALTIES = (25.0, 100.0, 500.0)


def _assert_grids_identical(a, b):
    assert set(a) == set(b)
    for key, cell in a.items():
        assert set(cell) == set(b[key])
        for p, res in cell.items():
            for f in RESULT_FIELDS:
                assert getattr(res, f) == getattr(b[key][p], f), (key, p, f)


def _small_grid(store=None, workers=0, trace_n=5_000, **base_kw):
    traces = {"gradle": get_trace("gradle", trace_n, seed=0)}
    base = SimConfig(engine="fast", update_interval=200, **base_kw)
    return run_grid(traces, base, "miss_penalty", PENALTIES,
                    policies=("fna", "fno", "pi"), store=store,
                    workers=workers)


# ---------------------------------------------------------------------------
# Serialisation round-trip
# ---------------------------------------------------------------------------

def test_to_arrays_roundtrip_is_lossless():
    """from_arrays(to_arrays(st)) re-serialises byte-for-byte: every
    array the replay phase consumes survives the round trip exactly."""
    trace = get_trace("gradle", 5_000, seed=0)
    cfg = SimConfig(engine="fast", update_interval=200)
    st = SystemTrace.compute(Simulator(cfg), trace)
    arrays = st.to_arrays()
    st2 = SystemTrace.from_arrays(arrays, key=st.key, trace=st._trace)
    arrays2 = st2.to_arrays()
    assert set(arrays) == set(arrays2)
    for k in arrays:
        a, b = np.asarray(arrays[k]), np.asarray(arrays2[k])
        assert a.dtype == b.dtype and a.shape == b.shape, k
        assert a.tobytes() == b.tobytes(), k
    assert st2.key == st.key and st2.from_fresh == st.from_fresh
    assert st2.plan_cache == {}


# ---------------------------------------------------------------------------
# Hit / miss / bit-identity through the grid runner
# ---------------------------------------------------------------------------

def test_store_hit_skips_sweep_and_is_bit_identical(tmp_path):
    cold = _small_grid()
    store = ArtifactStore(tmp_path / "store")
    populated = _small_grid(store=store)
    before = systemstate.SWEEPS_COMPUTED
    warm = _small_grid(store=store)
    assert systemstate.SWEEPS_COMPUTED == before, \
        "warm run recomputed a stored sweep"
    assert store.stats["sweep_hits"] >= 1
    assert store.stats["table_hits"] >= 1, \
        "warm run rebuilt tables instead of preloading them"
    _assert_grids_identical(populated, cold)
    _assert_grids_identical(warm, cold)


def test_store_invalidates_on_trace_byte_change(tmp_path):
    store = ArtifactStore(tmp_path)
    trace = np.asarray(get_trace("gradle", 3_000, seed=0), np.uint64)
    cfg = SimConfig(engine="fast")
    st = SystemTrace.compute(Simulator(cfg), trace)
    store.save_sweep(st)
    assert store.load_sweep(trace, st.key) is not None
    mutated = trace.copy()
    mutated[1_500] += 1
    assert store.load_sweep(mutated, st.key) is None
    assert not store.has_sweep(store.trace_digest(mutated), st.key)


def test_store_invalidates_on_system_key_change(tmp_path):
    store = ArtifactStore(tmp_path)
    trace = np.asarray(get_trace("gradle", 3_000, seed=0), np.uint64)
    cfg = SimConfig(engine="fast", update_interval=200)
    st = SystemTrace.compute(Simulator(cfg), trace)
    store.save_sweep(st)
    other = SystemTrace.system_key(
        SimConfig(engine="fast", update_interval=400))
    assert other != st.key
    assert store.load_sweep(trace, st.key) is not None
    assert store.load_sweep(trace, other) is None


def test_store_invalidates_on_schema_bump(tmp_path, monkeypatch):
    store = ArtifactStore(tmp_path)
    trace = np.asarray(get_trace("gradle", 3_000, seed=0), np.uint64)
    st = SystemTrace.compute(Simulator(SimConfig(engine="fast")), trace)
    store.save_sweep(st)
    assert store.load_sweep(trace, st.key) is not None
    monkeypatch.setattr(store_mod, "SCHEMA_VERSION",
                        store_mod.SCHEMA_VERSION + 1)
    assert store.load_sweep(trace, st.key) is None


def test_corrupt_entry_reads_as_miss_and_rebuilds(tmp_path):
    store = ArtifactStore(tmp_path)
    trace = np.asarray(get_trace("gradle", 3_000, seed=0), np.uint64)
    st = SystemTrace.compute(Simulator(SimConfig(engine="fast")), trace)
    store.save_sweep(st)
    entries = list((tmp_path / "sweeps").glob("*.npz"))
    assert len(entries) == 1
    # truncate mid-archive: np.load must fail, not return garbage
    data = entries[0].read_bytes()
    entries[0].write_bytes(data[:len(data) // 2])
    assert store.load_sweep(trace, st.key) is None
    assert store.stats["corrupt_dropped"] == 1
    assert not entries[0].exists(), "corrupt entry not unlinked"
    store.save_sweep(st)                          # rebuild lands cleanly
    hydrated = store.load_sweep(trace, st.key)
    assert hydrated is not None
    assert hydrated.to_arrays()["pats"].tobytes() == \
        st.to_arrays()["pats"].tobytes()


def test_foreign_meta_reads_as_miss_not_corruption(tmp_path):
    """A colliding/foreign file whose archive IS loadable but whose meta
    differs must read as a plain miss and stay on disk untouched."""
    store = ArtifactStore(tmp_path)
    digest = "0" * 64
    key = (3,)
    meta = store.sweep_meta(digest, key)
    path = store._path("sweep", meta)
    store._write(path, {"pats": np.arange(3)}, "some-other-meta")
    assert store._read(path, meta) is None
    assert store.stats["corrupt_dropped"] == 0
    assert path.exists()


# ---------------------------------------------------------------------------
# Concurrency
# ---------------------------------------------------------------------------

def test_concurrent_writers_leave_loadable_entry(tmp_path):
    """Two spawn processes race _sweep_worker on the SAME (trace, cfg):
    both must succeed, and the surviving entry must verify + hydrate."""
    trace = np.asarray(get_trace("gradle", 3_000, seed=0), np.uint64)
    cfg = SimConfig(engine="fast", update_interval=200)
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(2) as pool:
        results = pool.starmap(_sweep_worker,
                               [(str(tmp_path), trace, cfg)] * 2)
    assert set(results) <= {"hit", "computed"} and "computed" in results
    store = ArtifactStore(tmp_path)
    assert all(ok for _, ok in store.verify())
    st = store.load_sweep(trace, SystemTrace.system_key(cfg))
    assert st is not None
    ref = SystemTrace.compute(Simulator(cfg), trace)
    assert st.to_arrays()["pats"].tobytes() == \
        ref.to_arrays()["pats"].tobytes()


def test_run_grid_workers_bit_identical_to_serial(tmp_path):
    traces = {"gradle": get_trace("gradle", 5_000, seed=0)}
    base = SimConfig(engine="fast")
    serial = run_grid(traces, base, "update_interval", (100, 400),
                      policies=("fna", "fno"))
    store = ArtifactStore(tmp_path)
    before = systemstate.SWEEPS_COMPUTED
    parallel = run_grid(traces, base, "update_interval", (100, 400),
                        policies=("fna", "fno"), store=store, workers=2)
    _assert_grids_identical(parallel, serial)
    # the farm computed both sweeps out-of-process; the parent's serial
    # pass hydrated them from the store
    assert systemstate.SWEEPS_COMPUTED == before
    assert store.stats["sweep_hits"] == 2


def test_run_grid_workers_without_store_uses_ephemeral_root(monkeypatch):
    traces = {"gradle": get_trace("gradle", 5_000, seed=0)}
    base = SimConfig(engine="fast")
    serial = run_grid(traces, base, "update_interval", (100, 400),
                      policies=("fna",))
    # workers are spawned with JAX pinned to the CPU; the parent's own
    # environment is left as it was
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    parallel = run_grid(traces, base, "update_interval", (100, 400),
                        policies=("fna",), workers=2)
    assert "JAX_PLATFORMS" not in os.environ
    _assert_grids_identical(parallel, serial)


# ---------------------------------------------------------------------------
# Golden-scenario hydration parity (the acceptance bar)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def golden_store(tmp_path_factory):
    return ArtifactStore(tmp_path_factory.mktemp("golden-store"))


@pytest.mark.parametrize("name", sorted(GOLDEN_SCENARIOS))
def test_golden_scenario_store_hydrated_bit_identical(name, golden_store):
    """Populate-then-warm on each golden scenario's pinned sub-grid: the
    warm (fully store-hydrated) run must reproduce every record of the
    cold run exactly — every scenario, every policy, every raw
    accumulator — while computing ZERO sweeps."""
    sc = get_scenario(name)
    cold = run_scenario(sc, golden=True, store=golden_store)
    before = systemstate.SWEEPS_COMPUTED
    warm = run_scenario(sc, golden=True, store=golden_store)
    assert systemstate.SWEEPS_COMPUTED == before, \
        f"{name}: warm golden run recomputed a sweep"
    assert warm == cold, f"{name}: store-hydrated records drifted"


# ---------------------------------------------------------------------------
# tracefiles parse cache under the store root
# ---------------------------------------------------------------------------

@pytest.fixture
def keys_log(tmp_path):
    src = tmp_path / "src"
    src.mkdir()
    p = src / "t.log"
    p.write_text("".join(f"k{i % 17}\n" for i in range(300)))
    return p


def test_tracefiles_cache_lands_under_store_root(keys_log, tmp_path,
                                                 monkeypatch):
    from repro.cachesim.tracefiles import load_trace_file
    root = tmp_path / "store"
    monkeypatch.setenv("REPRO_STORE", str(root))
    ids = load_trace_file(keys_log)
    assert ids.shape[0] == 300
    assert list((root / "traces").glob("t.log.*.npz")), \
        "parse cache not under the store root"
    assert not list(keys_log.parent.glob("t.log.*.npz")), \
        "parse cache leaked next to the source despite REPRO_STORE"
    # warm load comes from the store-rooted cache, not a re-parse
    import repro.cachesim.tracefiles as tf
    monkeypatch.setattr(tf, "parse_trace_file",
                        lambda *a, **k: pytest.fail("re-parsed despite cache"))
    again = load_trace_file(keys_log)
    assert np.array_equal(again, ids)


def test_tracefiles_legacy_cache_still_hits_with_store_set(
        keys_log, tmp_path, monkeypatch):
    """A pre-existing next-to-source cache (written before REPRO_STORE
    existed) must still be honoured once the env var is set."""
    import repro.cachesim.tracefiles as tf
    from repro.cachesim.tracefiles import load_trace_file
    monkeypatch.delenv("REPRO_STORE", raising=False)
    ids = load_trace_file(keys_log)               # legacy location
    assert list(keys_log.parent.glob("t.log.*.npz"))
    monkeypatch.setenv("REPRO_STORE", str(tmp_path / "store"))
    monkeypatch.setattr(tf, "parse_trace_file",
                        lambda *a, **k: pytest.fail("legacy cache ignored"))
    again = load_trace_file(keys_log)
    assert np.array_equal(again, ids)


def test_tracefiles_default_stays_next_to_source(keys_log, monkeypatch):
    from repro.cachesim.tracefiles import load_trace_file
    monkeypatch.delenv("REPRO_STORE", raising=False)
    load_trace_file(keys_log)
    assert list(keys_log.parent.glob("t.log.*.npz"))


# ---------------------------------------------------------------------------
# Maintenance CLI
# ---------------------------------------------------------------------------

def test_store_tool_ls_verify_gc(tmp_path):
    repo = Path(__file__).resolve().parents[1]
    store = ArtifactStore(tmp_path)
    store.save_table("a" * 64, (3,), ("k1",), np.arange(8))
    store.save_table("b" * 64, (3,), ("k2",), np.arange(8))
    env = {**os.environ, "PYTHONPATH": str(repo / "src")}

    def tool(*args):
        return subprocess.run(
            [sys.executable, str(repo / "tools" / "store_tool.py"),
             "--store", str(tmp_path), *args],
            capture_output=True, text=True, env=env, cwd=repo)

    ls = tool("ls")
    assert ls.returncode == 0 and "total: 2 entries" in ls.stdout
    ver = tool("verify")
    assert ver.returncode == 0 and "0 corrupt" in ver.stdout
    gc = tool("gc", "--max-bytes", "1K")
    assert gc.returncode == 0
    assert len(store.entries()) < 2
    # a corrupt entry fails verify with exit 1
    [(path, _, _, _)] = store.entries()
    path.write_bytes(b"not an archive")
    bad = tool("verify")
    assert bad.returncode == 1 and "CORRUPT" in bad.stdout
