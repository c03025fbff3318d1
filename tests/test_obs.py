"""The simulator's spans and counters (``repro.obs``): the calibrated
replay's counters add up, repeat exactly, and neither they nor a running
profiler move a result."""
import pytest

from repro import obs
from repro.cachesim import SimConfig, Simulator, get_trace
from repro.cachesim.sweep import run_grid, sweep_records

N = 8_000
COUNTS = ("requests", "spec_committed", "verified_rows", "bridged")
TIMES = ("build_ns", "trajectory_ns", "verify_ns", "bridge_ns")


@pytest.fixture(autouse=True)
def fresh_counters():
    obs.reset()
    yield
    obs.reset()


def _fna_cal_counters(trace, **cfg_kw):
    """The ``fna_cal.*`` counters of one fast fna_cal replay."""
    cfg = SimConfig(cache_size=1_000, policy="fna_cal", engine="fast",
                    **cfg_kw)
    before = obs.counters()
    Simulator(cfg).run(trace)
    after = obs.counters()
    return {k[len("fna_cal."):]: after[k] - before.get(k, 0)
            for k in after if k.startswith("fna_cal.")}


def test_add_counters_reset():
    obs.add("a", 2)
    obs.add("a", 3)
    obs.add("b", 1)
    snap = obs.counters()
    assert snap == {"a": 5, "b": 1}
    snap["a"] = 0                       # a copy: the totals stay
    assert obs.counters()["a"] == 5
    obs.reset()
    assert obs.counters() == {}


@pytest.mark.parametrize("cfg_kw", [
    dict(update_interval=200, est_interval=25),
    dict(update_interval=64, est_interval=16, cal_epsilon=0.05,
         cal_min_obs=5),
    dict(update_interval=200, est_interval=25, cal_epsilon=0.0,
         cal_min_obs=1_000_000),        # never leaves the model blend
    dict(update_interval=200, est_interval=25, alg="exhaustive"),
], ids=["default", "fast_calibration", "model_blend", "exhaustive"])
def test_fna_cal_counters_add_up(cfg_kw):
    c = _fna_cal_counters(get_trace("gradle", N, seed=3), **cfg_kw)
    assert c["requests"] == N
    assert c["spec_committed"] + c["bridged"] == c["requests"]
    assert c["verified_rows"] >= c["spec_committed"] > 0
    assert c["bridged"] > 0
    for name in TIMES:
        assert c[name] > 0, name


def test_fna_cal_counts_repeat():
    trace = get_trace("gradle", N, seed=5)
    first = _fna_cal_counters(trace, update_interval=200, est_interval=25)
    second = _fna_cal_counters(trace, update_interval=200, est_interval=25)
    assert {k: first[k] for k in COUNTS} == {k: second[k] for k in COUNTS}


def test_results_unchanged_under_the_profiler(tmp_path):
    """Spans are annotations only: a grid run with the profiler recording
    gives the same rows, to the bit, as one without."""
    jax = pytest.importorskip("jax")
    trace = get_trace("gradle", 3_000, seed=7)
    base = SimConfig(cache_size=500, update_interval=200, est_interval=25)
    policies = ("fna", "fno", "pi", "hocs", "fna_cal")

    def rows():
        grid = run_grid({"gradle": trace}, base, axis="miss_penalty",
                        values=[50.0, 200.0], policies=policies)
        return [(r, res.total_cost) for r, res in zip(
            sweep_records(grid, "miss_penalty"),
            (res for cell in grid.values() for res in cell.values()))]

    plain = rows()
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = rows()
    finally:
        jax.profiler.stop_trace()
    assert traced == plain
    assert obs.counters()["fna_cal.requests"] == 2 * 2 * 3_000
