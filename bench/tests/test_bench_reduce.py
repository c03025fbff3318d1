"""Trace-to-metric reduction, peaks and the table byte count, on traces
written by hand and one recorded on the CPU."""
import glob
import time

import pytest

from bench import tracereduce as tr
from bench.metrics import (device_idle_pct, replay_s, sweep_s,
                           tables_host_s, tables_kernel_ms, tables_roofline)
from bench.roofline import peaks, table_bytes, table_seconds
from bench.harness import SPAN_NAMES, TABLES_MODULE
from types import SimpleNamespace

DEV = "/device:TPU:0"
TRACE = {
    "host": [
        ("python", "bench.window", 0, 1000),
        ("python", "SystemTrace.compute", 0, 300),
        ("python", "prefetch_tables", 300, 400),
        ("python", "Simulator.run", 700, 100),
        ("python", "Simulator.run", 800, 200),
        ("python", "inner", 820, 50),
        ("other", "SystemTrace.compute", 0, 1000),
    ],
    "device": {DEV: {
        "XLA Modules": [("jit__cells_tables_kernel(1)", 400, 200),
                        ("jit_other", 900, 50)],
        "XLA Ops": [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
                     400, 100), ("sort.2", 450, 100),
                    ("fusion.3", 650, 20), ("copy", 900, 50)],
    }},
}


def ctx(trace=TRACE, jobs=1, tables=((16, 3703, 3),)):
    window = (0, 1000)
    return SimpleNamespace(
        trace=trace, jobs=jobs, tables=list(tables),
        device_kind="TPU v5 lite", window=window, window_s=1e-6,
        busy_s=tr.busy_seconds(trace, window), span_names=SPAN_NAMES,
        tables_module=TABLES_MODULE)


def test_union_and_length():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert tr.length(u) == 6
    assert tr.clip(u, 2, 6) == [(2, 3), (5, 6)]


def test_busy_and_idle():
    # ops cover [400, 550) and [650, 670) and [900, 950): 220 ns
    assert tr.busy_seconds(TRACE, (0, 1000)) == pytest.approx(220e-9)
    assert device_idle_pct.read(ctx()) == pytest.approx(78.0)
    assert tr.busy_seconds({"host": [], "device": {}}, (0, 1)) is None


def test_kernel_time_by_module():
    # ops inside the tables module's [400, 600): 150 ns
    assert tr.module_op_seconds(TRACE, TABLES_MODULE) == pytest.approx(150e-9)
    assert tables_kernel_ms.read(ctx(jobs=2)) == pytest.approx(75e-6)
    assert tr.module_op_seconds(TRACE, "jit_absent") is None


def test_span_self_time():
    # the second Simulator.run holds no span of another layer
    assert tr.self_seconds(TRACE, "Simulator.run", SPAN_NAMES) == \
        pytest.approx(300e-9)
    # the 'other' thread's sweep has nothing nested: all its own
    assert sweep_s.read(ctx()) == pytest.approx(1300e-9)
    assert tables_host_s.read(ctx(jobs=4)) == pytest.approx(100e-9)
    assert replay_s.read(ctx(jobs=3)) == pytest.approx(100e-9)
    nested = {"host": [("t", "a", 0, 100), ("t", "b", 10, 30),
                       ("t", "b", 20, 30), ("u", "b", 0, 100)],
              "device": {}}
    assert tr.self_seconds(nested, "a", ("a", "b")) == pytest.approx(60e-9)
    assert tr.self_seconds(nested, "c", ("b",)) is None


def test_idle_by_host_activity():
    idle = dict(tr.idle_by_host(TRACE, (0, 1000), SPAN_NAMES))
    # idle [0,300) sweep; [300,400), [550,650) and [670,700) prefetch
    # (the latest started span wins); [700,900) and [950,1000) replay
    assert idle["SystemTrace.compute"] == pytest.approx(300e-9)
    assert idle["prefetch_tables"] == pytest.approx(230e-9)
    assert idle["Simulator.run"] == pytest.approx(250e-9)
    assert sum(idle.values()) == pytest.approx(780e-9)


def test_top_ops():
    ops = tr.top_ops(TRACE, limit=2)
    assert [name for name, _ in ops] == ["fusion.1", "sort.2"]


def test_table_bytes_secv3_by_hand():
    # 16 cells x 3,703 versions x 2^3 patterns x 1 byte written, and
    # pi, nu [3,703 x 3] float64 read: 473,984 + 177,744 bytes
    assert table_bytes(16, 3703, 3) == 651_728
    # n = 9 needs two bytes per mask
    assert table_bytes(1, 1, 9) == 512 * 2 + 2 * 9 * 8


def test_peaks_table():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("cpu")
    assert table_seconds(16, 3703, 3, "TPU v5 lite") == \
        pytest.approx(651_728 / 819e9)


def test_roofline_share():
    share = tables_roofline.read(ctx())
    assert share == pytest.approx(100 * (651_728 / 819e9) / 150e-9)
    assert tables_roofline.read(ctx(tables=())) is None


def test_recorded_cpu_trace(tmp_path):
    jax = pytest.importorskip("jax")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("SystemTrace.compute"):
                time.sleep(0.02)
                with jax.profiler.TraceAnnotation("prefetch_tables"):
                    time.sleep(0.03)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    flat = tr.from_profile(path[0])
    assert len(tr.spans(flat, "bench.window")) == 1
    own = tr.self_seconds(flat, "SystemTrace.compute", SPAN_NAMES)
    inner = tr.self_seconds(flat, "prefetch_tables", SPAN_NAMES)
    assert 0.015 < own < 0.05 and 0.025 < inner < 0.08
    assert tr.summary(flat)[-1].startswith("host:")
