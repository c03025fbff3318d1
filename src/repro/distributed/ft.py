"""Fault tolerance: preemption handling, straggler detection, elastic meshes.

* :class:`PreemptionHandler` — SIGTERM/SIGUSR1 -> "checkpoint and exit 42"
  (the restart contract cluster schedulers expect; the launcher re-invokes
  with ``--resume``).
* :class:`StepTimer` — EMA/variance step-time tracker flagging stragglers
  (on a real pod the per-host step times come from a collective of local
  timings; here the same detector runs on the local stream).
* :func:`elastic_mesh` — builds the largest usable (data, model) mesh from
  the CURRENTLY live device set: model dim fixed (weights must fit),
  data dim = largest divisor of live devices.  Combined with
  checkpoint.restore(shardings=...) this is the elastic-restart path:
  lose a host, rebuild a smaller mesh, reshard, continue.
"""
from __future__ import annotations

import math
import signal
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
from jax.sharding import AxisType


class PreemptionHandler:
    def __init__(self, signals=(signal.SIGTERM, signal.SIGUSR1)):
        self._flag = threading.Event()
        self._installed = False
        self._signals = signals

    def install(self) -> "PreemptionHandler":
        if not self._installed:
            for s in self._signals:
                try:
                    signal.signal(s, self._on_signal)
                except ValueError:  # non-main thread (tests)
                    pass
            self._installed = True
        return self

    def _on_signal(self, signum, frame):
        self._flag.set()

    def trigger(self) -> None:  # for tests / manual drills
        self._flag.set()

    @property
    def preempted(self) -> bool:
        return self._flag.is_set()


@dataclass
class StepTimer:
    """EMA step-time straggler detector."""
    alpha: float = 0.1
    threshold: float = 2.0     # x mean => straggler
    warmup: int = 5
    mean: float = 0.0
    var: float = 0.0
    n: int = 0
    stragglers: List[int] = field(default_factory=list)
    _t0: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self, step: int) -> float:
        dt = time.perf_counter() - self._t0
        self.observe(step, dt)
        return dt

    def observe(self, step: int, dt: float) -> bool:
        self.n += 1
        if self.n <= self.warmup:
            # seed the EMA
            self.mean = dt if self.n == 1 else (self.mean + dt) / 2
            return False
        is_straggler = dt > self.threshold * max(self.mean, 1e-9)
        if is_straggler:
            self.stragglers.append(step)
        # straggler steps don't poison the EMA
        w = self.alpha if not is_straggler else self.alpha * 0.1
        self.var = (1 - w) * self.var + w * (dt - self.mean) ** 2
        self.mean = (1 - w) * self.mean + w * dt
        return is_straggler


def elastic_mesh(model_dim: int = 1, devices=None):
    """Largest (data, model) mesh from the live device set.

    model_dim is fixed by weight sharding; data = floor(live / model_dim),
    rounded down to a power of two so batch sharding stays divisible.
    """
    devices = list(devices if devices is not None else jax.devices())
    live = len(devices)
    if live < model_dim:
        raise RuntimeError(f"only {live} devices live; need >= model_dim={model_dim}")
    data = live // model_dim
    data = 2 ** int(math.log2(data)) if data > 0 else 1
    n = data * model_dim
    return jax.make_mesh((data, model_dim), ("data", "model"),
                         devices=devices[:n],
                         axis_types=(AxisType.Auto, AxisType.Auto))
