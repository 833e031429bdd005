"""Elastic runtime: shrink / expand via in-memory checkpoint + reshard (§II-B).

Charm++ rescaling protocol, step for step:

  1. migrate work away from departing PEs   (implicit: resharding does this)
  2. checkpoint to Linux shared memory      -> ``store.save`` (host RAM)
  3. restart with the new PE count          -> rebuild Mesh + re-jit
  4. restore state                          -> ``store.restore`` with the new
                                               shardings (device_put reshards)
  5. load balance                           -> LB step / sharding rules already
                                               balance SPMD work

Stage timings are recorded per rescale so the benchmark harness reproduces
the paper's four-bar breakdown (checkpoint / load balance / restart /
restore, Figures 5-6).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.core.checkpointing import InMemoryStore


@dataclasses.dataclass
class RescaleEvent:
    kind: str                 # 'shrink' | 'expand'
    from_devices: int
    to_devices: int
    stages: Dict[str, float]  # checkpoint/loadbalance/restart/restore seconds

    @property
    def total(self) -> float:
        return sum(self.stages.values())


class ElasticRuntime:
    """Wraps a jit-able step function with shrink/expand over device subsets.

    ``mesh_factory(n_devices)``   -> Mesh using the first n devices
    ``shardings_factory(mesh)``   -> (in_shardings pytree for the state)
    ``step_factory(mesh)``        -> jitted step fn(state, batch)
    ``init_fn(shardings)``        -> initial state, created in place in
                                     those shardings (never whole on one
                                     device first)

    The runtime owns the current mesh/state and performs the 5-stage
    rescale protocol; the CloudManager calls ``rescale_to``.
    """

    def __init__(self, *, mesh_factory, shardings_factory, step_factory,
                 init_fn: Callable[[Any], Any], n_devices: int,
                 store: Optional[InMemoryStore] = None):
        self.mesh_factory = mesh_factory
        self.shardings_factory = shardings_factory
        self.step_factory = step_factory
        self.store = store or InMemoryStore()
        self.events: List[RescaleEvent] = []
        self.n_devices = n_devices
        self.mesh = mesh_factory(n_devices)
        self.shardings = shardings_factory(self.mesh)
        self._step = step_factory(self.mesh)
        self.state = init_fn(self.shardings)

    def step(self, batch):
        self.state, out = self._step(self.state, batch)
        return out

    def rescale_to(self, n_devices: int) -> RescaleEvent:
        kind = "shrink" if n_devices < self.n_devices else "expand"
        stages: Dict[str, float] = {}

        t0 = time.perf_counter()
        self.store.save("elastic", self.state)
        stages["checkpoint"] = time.perf_counter() - t0

        # "restart": tear down the old executable, rebuild mesh + re-jit.
        t0 = time.perf_counter()
        del self._step
        self.mesh = self.mesh_factory(n_devices)
        self.shardings = self.shardings_factory(self.mesh)
        self._step = self.step_factory(self.mesh)
        stages["restart"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        self.state = self.store.restore("elastic", self.shardings)
        stages["restore"] = time.perf_counter() - t0

        # post-expand LB step (§II-B): for SPMD state the resharding already
        # rebalances; we account the explicit device_put-based rebalance pass.
        t0 = time.perf_counter()
        self.state = jax.block_until_ready(self.state)
        stages["loadbalance"] = time.perf_counter() - t0

        ev = RescaleEvent(kind, self.n_devices, n_devices, stages)
        self.n_devices = n_devices
        self.events.append(ev)
        return ev
