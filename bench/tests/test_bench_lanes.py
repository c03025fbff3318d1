"""The reader of ``fna_cal_lanes`` on counter dicts written by hand."""
import sys
from types import SimpleNamespace

import pytest

from bench import harness
from bench.metrics import fna_cal_lanes
from repro import obs

#: the counters a program that replays lane by lane keeps
LONE_COUNTERS = (("requests", 1_000), ("spec_committed", 900),
                 ("verified_rows", 1_500), ("bridged", 100))


def ctx():
    return SimpleNamespace(trace={"host": [], "device": {}}, jobs=2,
                           span_names=harness.SPAN_NAMES)


@pytest.fixture
def fresh_obs():
    """``repro.obs`` with no counters, before and after the test."""
    obs.reset()
    yield obs
    obs.reset()


def test_lanes_reader_by_hand(fresh_obs):
    """Lane chunks over verifier calls, and None on counters without
    ``rounds`` (a program that replays lane by lane)."""
    for name, value in LONE_COUNTERS:
        fresh_obs.add(f"fna_cal.{name}", value)
    assert fna_cal_lanes.read(ctx()) is None
    fresh_obs.add("fna_cal.rounds", 40)
    fresh_obs.add("fna_cal.lane_chunks", 250)
    assert fna_cal_lanes.read(ctx()) == pytest.approx(6.25)


def test_lanes_reader_without_counters(fresh_obs, monkeypatch):
    assert fna_cal_lanes.read(ctx()) is None     # nothing replayed
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    fresh_obs.add("fna_cal.rounds", 1)
    fresh_obs.add("fna_cal.lane_chunks", 1)
    assert fna_cal_lanes.read(ctx()) is None     # a program without repro.obs
