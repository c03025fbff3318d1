"""The benchmark's plain reference agrees bit for bit with the
repository's per-request reference engine, for every policy of every
configuration."""
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench.gen import gradle
from bench.reference import FIELDS, run_row

ROOT = Path(__file__).resolve().parents[2]
POLICIES = ("fna", "fno", "pi", "hocs", "fna_cal")


CONFIGS = sorted(p.stem for p in (ROOT / "bench" / "configs").glob("*.json"))


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("requests", [1500, 20000])
def test_reference_matches_program_reference(config, policy, requests):
    """On a short trace, and on one long enough for the caches to fill
    and the estimates to settle."""
    from repro.cachesim import Simulator
    data = json.loads((ROOT / "bench" / "configs"
                       / f"{config}.json").read_text())
    system = dict(data["system"], miss_penalty=data["grid"]["values"][-1])
    trace = gradle(requests, 3).astype(np.uint64)
    cfg = dataclasses.replace(harness.sim_config(system), policy=policy,
                              engine="reference")
    res = Simulator(cfg).run(trace)
    assert run_row(system, policy, trace) == \
        tuple(getattr(res, f) for f in FIELDS)


def test_reference_refuses_what_it_does_not_model():
    with pytest.raises(ValueError):
        run_row({"advert_policy": "delta"}, "fna", np.arange(3))
