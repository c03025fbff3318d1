"""The readers of the program's spans and counters (``repro.obs``), on a
trace written by hand and on a job recorded on the CPU."""
import glob
import sys
from types import SimpleNamespace

import pytest

from bench import harness
from bench import tracereduce as tr
from bench.gen import make_trace
from bench.metrics import (fna_cal_bridge_s, fna_cal_bridged_pct,
                           fna_cal_build_s, fna_cal_commit_pct,
                           fna_cal_trajectory_s, fna_cal_verify_s,
                           replay_fna_cal_s, replay_fold_s, replay_s,
                           sweep_cbf_s, sweep_lru_s, sweep_s, tables_host_s,
                           tables_wait_s)
from repro import obs

#: the program's span tree on a flat grid; ``replay.<policy>`` per policy
PROGRAM_SPANS = ("grid.run", "cells.group", "sweep", "sweep.lru",
                 "sweep.cbf_walk", "tables", "tables.device", "replay.fold")
HARNESS_NAMES = harness.SPAN_NAMES + (harness.JOB_SPAN, harness.WINDOW_SPAN)
SPAN_READERS = (replay_fna_cal_s, replay_fold_s, sweep_lru_s, sweep_cbf_s,
                tables_wait_s)
COUNTER_READERS = (fna_cal_build_s, fna_cal_trajectory_s, fna_cal_verify_s,
                   fna_cal_bridge_s, fna_cal_commit_pct, fna_cal_bridged_pct)

# one job of two replays, under the harness's spans
TRACE = {
    "host": [
        ("python", "bench.window", 0, 10_000),
        ("python", "grid.run", 0, 6_000),
        ("python", "cells.group", 0, 6_000),
        ("python", "SystemTrace.compute", 0, 1_000),
        ("python", "sweep", 10, 980),
        ("python", "sweep.lru", 20, 300),
        ("python", "sweep.cbf_walk", 330, 200),
        ("python", "sweep.lru", 540, 100),
        ("python", "prefetch_tables", 1_000, 500),
        ("python", "tables", 1_010, 480),
        ("python", "tables.device", 1_100, 250),
        ("python", "Simulator.run", 2_000, 3_000),
        ("python", "replay.fna_cal", 2_010, 2_500),
        ("python", "replay.fold", 4_520, 400),
        ("python", "Simulator.run", 5_000, 500),
        ("python", "replay.fna", 5_010, 300),
        ("python", "replay.fold", 5_320, 100),
    ],
    "device": {},
}


def ctx(trace=TRACE, jobs=2):
    return SimpleNamespace(trace=trace, jobs=jobs,
                           span_names=harness.SPAN_NAMES)


def without_program_spans(trace):
    harness_only = set(HARNESS_NAMES)
    return {"host": [e for e in trace["host"] if e[1] in harness_only],
            "device": trace["device"]}


@pytest.fixture
def fresh_obs():
    """``repro.obs`` with no counters, before and after the test."""
    obs.reset()
    yield obs
    obs.reset()


def test_span_readers_by_hand():
    assert replay_fna_cal_s.read(ctx()) == pytest.approx(1250e-9)
    assert replay_fold_s.read(ctx()) == pytest.approx(250e-9)
    assert sweep_lru_s.read(ctx()) == pytest.approx(200e-9)
    assert sweep_cbf_s.read(ctx()) == pytest.approx(100e-9)
    assert tables_wait_s.read(ctx()) == pytest.approx(125e-9)
    bare = ctx(without_program_spans(TRACE))
    for reader in SPAN_READERS:             # a program without the spans
        assert reader.read(bare) is None, reader.__name__


def test_harness_readers_ignore_program_spans():
    bare = ctx(without_program_spans(TRACE))
    for reader in (replay_s, sweep_s, tables_host_s):
        assert reader.read(ctx()) == reader.read(bare), reader.__name__
    assert replay_s.read(ctx()) == pytest.approx(1750e-9)


def test_counter_readers_by_hand(fresh_obs):
    for name, value in (("requests", 1_000), ("spec_committed", 900),
                        ("verified_rows", 1_500), ("bridged", 100),
                        ("build_ns", 2e9), ("trajectory_ns", 3e9),
                        ("verify_ns", 1e9), ("bridge_ns", 5e8)):
        fresh_obs.add(f"fna_cal.{name}", value)
    assert fna_cal_build_s.read(ctx()) == pytest.approx(1.0)
    assert fna_cal_trajectory_s.read(ctx()) == pytest.approx(1.5)
    assert fna_cal_verify_s.read(ctx()) == pytest.approx(0.5)
    assert fna_cal_bridge_s.read(ctx()) == pytest.approx(0.25)
    assert fna_cal_commit_pct.read(ctx()) == pytest.approx(60.0)
    assert fna_cal_bridged_pct.read(ctx()) == pytest.approx(10.0)


def test_counter_readers_without_counters(fresh_obs, monkeypatch):
    for reader in COUNTER_READERS:          # nothing replayed
        assert reader.read(ctx()) is None, reader.__name__
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    fresh_obs.add("fna_cal.requests", 1)
    for reader in COUNTER_READERS:          # a program without repro.obs
        assert reader.read(ctx()) is None, reader.__name__


def _small_cell():
    cell = harness.resolve(harness.load_manifest(), "secv3.full")
    cell.requests = 2_000
    return cell, make_trace(cell.mix, cell.requests, 5,
                            residues=int(cell.system["n_caches"]))


def test_warm_up_replays_nothing(fresh_obs):
    """The counter readers count the window alone: set-up leaves every
    ``fna_cal.*`` counter at 0, and a job moves them."""
    cell, trace = _small_cell()
    harness.warm_up(cell, trace)
    assert not any(v for k, v in fresh_obs.counters().items()
                   if k.startswith("fna_cal."))
    harness.run_job(cell, trace)
    assert fresh_obs.counters()["fna_cal.requests"] == \
        len(cell.values) * cell.requests


def _inside(trace, inner, outer):
    """Every ``inner`` span lies inside an ``outer`` span of its thread."""
    outers = tr.spans(trace, outer)
    return all(any(ot == t and os <= s and e <= oe for ot, os, oe in outers)
               for t, s, e in tr.spans(trace, inner))


def test_recorded_job(tmp_path, monkeypatch, fresh_obs):
    """A job of the cell recorded on the CPU under the harness's spans:
    the program's span tree is whole and nested, uses no harness name,
    and the harness's readers read the same with it as without it."""
    jax = pytest.importorskip("jax")
    cell, trace = _small_cell()
    harness.warm_up(cell, trace)
    called = []
    span = obs.span

    def recording(name):
        called.append(name)
        return span(name)

    monkeypatch.setattr(obs, "span", recording)
    with harness.Spans() as spans:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation(harness.WINDOW_SPAN):
                harness.run_job(cell, trace)
        finally:
            jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    flat = tr.from_profile(path[0])

    tree = set(PROGRAM_SPANS) | {f"replay.{p}" for p in cell.policies}
    assert set(called) == tree
    assert not tree & set(HARNESS_NAMES)
    for name in tree:
        assert tr.spans(flat, name), name
    n_caches = int(cell.system["n_caches"])
    assert len(tr.spans(flat, "sweep.lru")) == n_caches
    assert len(tr.spans(flat, "replay.fold")) == \
        len(cell.values) * len(cell.policies)
    assert _inside(flat, "tables.device", "tables")
    assert _inside(flat, "replay.fold", "cells.group")
    assert _inside(flat, "cells.group", "grid.run")
    replays = [sp for p in cell.policies
               for sp in tr.spans(flat, f"replay.{p}")]
    for t, s, e in tr.spans(flat, "replay.fold"):      # beside, not inside
        assert not any(rt == t and rs <= s and e <= re
                       for rt, rs, re in replays)

    full = harness._trace_context(flat, 1, spans.tables, "cpu")
    bare = harness._trace_context(without_program_spans(flat), 1,
                                  spans.tables, "cpu")
    for reader in (replay_s, sweep_s, tables_host_s):
        assert reader.read(full) == reader.read(bare), reader.__name__
    assert tr.idle_by_host(flat, full.window, harness.SPAN_NAMES) == \
        tr.idle_by_host(bare.trace, bare.window, harness.SPAN_NAMES)
    for reader in SPAN_READERS + COUNTER_READERS:
        assert reader.read(full) is not None, reader.__name__
    assert sweep_lru_s.read(full) + sweep_cbf_s.read(full) <= \
        sweep_s.read(full)
    assert replay_fna_cal_s.read(full) + replay_fold_s.read(full) <= \
        replay_s.read(full)
    assert tables_wait_s.read(full) <= tables_host_s.read(full)
