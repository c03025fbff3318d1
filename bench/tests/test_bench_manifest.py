"""The benchmark manifest: every cell resolves its files by name, names
and units keep to the allowed characters, and the traffic generator is
pinned by a digest of its output."""
import hashlib
import importlib
import json
import re
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench.gen import GENERATORS, gradle, make_trace, relabel, wiki

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["bench"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_by_name(workload):
    cell = harness.resolve(MANIFEST, workload)
    assert cell.mix["generator"] in GENERATORS
    assert cell.requests > 0 and cell.values and cell.policies
    assert cell.chips in (1, 4)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))


def test_names_and_units():
    names = [e["name"] for e in MANIFEST["configs"]] + WORKLOADS + \
        [m["name"] for m in METRICS] + \
        [w["traffic"] for w in MANIFEST["workloads"]] + \
        [k for c in MANIFEST["configs"] for k in c["reduced"]]
    for name in names:
        assert NAME.match(name), name
    for m in METRICS:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    groups = [[e["name"] for e in MANIFEST[k]]
              for k in ("configs", "workloads")] + \
        [[m["name"] for m in METRICS]]
    for group in groups:
        assert len(group) == len(set(group))


def test_bounds_and_layers():
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        assert m["layer"] and "\n" not in m["layer"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("conf", MANIFEST["configs"],
                         ids=[c["name"] for c in MANIFEST["configs"]])
def test_config_files_state_their_cuts(conf):
    data = json.loads((ROOT / conf["file"]).read_text())
    assert data["name"] == conf["name"]
    assert sorted(data["reduced"]) == sorted(conf["reduced"])
    for key in conf["reduced"]:
        assert data["published"][key] != data[key]
    assert data["assumed"] and data["source"]
    assert (ROOT / data["reference"]).is_file()
    harness.sim_config(data["system"])          # a valid deployment


def test_traffic_files_name_their_source():
    for w in MANIFEST["workloads"]:
        mix = json.loads((ROOT / "bench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
        assert mix["name"] == w["traffic"] and mix["source"]


def test_gradle_digest():
    ids = gradle(1000, 0)
    assert hashlib.sha256(ids.astype("<i8").tobytes()).hexdigest() == \
        "bfa41a53b212f2b951a40b0ec93796eadf61106f64b2e264e4d878507064ffd3"


def test_wiki_digest():
    ids = wiki(1000, 0)
    assert hashlib.sha256(ids.astype("<i8").tobytes()).hexdigest() == \
        "6642f1cfe163f85e2b5ef3ae6ced2a83b939092104767125b4c3127030fb5835"


def test_relabel_digest():
    ids = relabel(gradle(1000, 0), 3, 0)
    assert hashlib.sha256(ids.astype("<u8").tobytes()).hexdigest() == \
        "94dcb1e22a50b67db9a5ef8ddaedf798d305f7897260f8c5a0926e3f1025721d"


@pytest.mark.parametrize("residues", [3, 7])
def test_relabel_keeps_the_work(residues):
    ids = gradle(5000, 0)
    seeds = [relabel(ids, residues, s) for s in (1, 2**31 + 5)]
    for out in seeds:
        assert np.array_equal(out % residues, ids % residues)
        # a bijection: as many distinct ids, pairs and images
        pairs = np.unique(np.stack([ids.astype(np.uint64), out]), axis=1)
        assert pairs.shape[1] == len(np.unique(ids)) == len(np.unique(out))
    assert not np.array_equal(seeds[0], seeds[1])


def test_make_trace_is_seeded():
    mix = json.loads((ROOT / "bench" / "traffic" / "full.json").read_text())
    a = make_trace(mix, 2000, 11, residues=3)
    assert a.dtype == np.uint64
    assert np.array_equal(a, make_trace(mix, 2000, 11, residues=3))
    assert not np.array_equal(a, make_trace(mix, 2000, 12, residues=3))


def test_metric_readers_are_modules_of_their_own():
    for m in MANIFEST["per_layer"]:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        assert mod.__doc__
