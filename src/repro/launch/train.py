"""Training launcher: data pipeline + model zoo + elastic adaptive runtime.

``ElasticTrainer`` is the production driver: it owns the mesh, shardings,
jitted train step, prefetching data pipeline, periodic checkpoints, and the
shrink/expand protocol (via core.elastic.ElasticRuntime).  Spot events from a
CloudManager (or an explicit schedule, as in the examples) trigger real
rescales whose stage timings are recorded.

CLI (CPU-scale):
  PYTHONPATH=src python -m repro.launch.train --arch granite-8b --reduced \
      --steps 20 --n-devices 1
"""

from __future__ import annotations

import argparse
import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.configs import ARCHS, SHAPES
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.checkpointing import InMemoryStore, make_store
from repro.core.elastic import ElasticRuntime, RescaleEvent
from repro.data.pipeline import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.sharding import ShardingRules, use_rules
from repro.launch.specs import (batch_shardings, metrics_shardings,
                                state_shardings)
from repro.models import model_zoo as zoo
from repro.optim import adamw


def _mesh_for(n_devices: int, model_par: int = 1):
    assert n_devices % model_par == 0
    return make_mesh((n_devices // model_par, model_par), ("data", "model"))


class ElasticTrainer:
    def __init__(self, cfg: ModelConfig, shape: ShapeConfig, *,
                 n_devices: Optional[int] = None, model_par: int = 1,
                 seed: int = 0, store_kind: str = "memory",
                 hp: Optional[adamw.HParams] = None):
        self.cfg = cfg
        self.shape = shape
        self.data = SyntheticLM(cfg, shape, seed=seed)
        self.step_idx = 0
        self.metrics_log: List[Dict[str, float]] = []
        n_devices = n_devices or len(jax.devices())
        self.model_par = model_par

        def init_fn(shardings):
            # each device draws only its own shards of the state
            init = jax.jit(functools.partial(zoo.init_state, cfg),
                           out_shardings=shardings)
            return init(jax.random.PRNGKey(seed))

        def mesh_factory(n):
            return _mesh_for(n, self.model_par)

        def shardings_factory(mesh):
            return state_shardings(cfg, ShardingRules(mesh))

        def step_factory(mesh):
            rules = ShardingRules(mesh)
            ssh = state_shardings(cfg, rules)
            bsh = batch_shardings(cfg, shape, rules)
            fn = zoo.make_train_step(cfg, hp=hp)
            jitted = jax.jit(fn, in_shardings=(ssh, bsh),
                             out_shardings=(ssh, metrics_shardings(rules)),
                             donate_argnums=(0,))
            # eager AOT compile: this is the paper's 'restart' stage --
            # application startup dominates rescale cost (Fig 5/6).  The
            # steps run this executable, so nothing compiles twice.
            with mesh, use_rules(rules):
                return jitted.lower(zoo.abstract_state(cfg),
                                    zoo.batch_spec(cfg, shape)).compile()

        self.runtime = ElasticRuntime(
            mesh_factory=mesh_factory,
            shardings_factory=shardings_factory,
            step_factory=step_factory,
            init_fn=init_fn,
            n_devices=n_devices,
            store=make_store(store_kind),
        )

    # ------------------------------------------------------------- training
    def train(self, n_steps: int, log_every: int = 10) -> Dict[str, float]:
        t0 = time.perf_counter()
        mesh = self.runtime.mesh
        rules = ShardingRules(mesh)
        bsh = batch_shardings(self.cfg, self.shape, rules)
        for _ in range(n_steps):
            host = self.data.batch_at(self.step_idx)
            batch = jax.tree.map(jax.device_put, host, bsh)
            metrics = self.runtime.step(batch)
            metrics = {k: float(v) for k, v in metrics.items()}
            metrics["step"] = self.step_idx
            self.metrics_log.append(metrics)
            if log_every and self.step_idx % log_every == 0:
                print(f"step {self.step_idx:5d} loss {metrics['loss']:.4f} "
                      f"gnorm {metrics['grad_norm']:.3f}", flush=True)
            self.step_idx += 1
        return {"seconds": time.perf_counter() - t0,
                "final_loss": self.metrics_log[-1]["loss"]}

    # ------------------------------------------------------------- elastic
    def rescale(self, n_devices: int) -> RescaleEvent:
        ev = self.runtime.rescale_to(n_devices)
        print(f"[elastic] {ev.kind} {ev.from_devices}->{ev.to_devices} "
              + " ".join(f"{k}={v*1e3:.1f}ms" for k, v in ev.stages.items()),
              flush=True)
        return ev

    @property
    def state(self):
        return self.runtime.state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b", choices=sorted(ARCHS))
    ap.add_argument("--shape", default="train_4k", choices=sorted(SHAPES))
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-scale reduced config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--n-devices", type=int, default=None)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = ARCHS[args.arch]
    shape = SHAPES[args.shape]
    if args.reduced:
        cfg, shape = cfg.reduced(), shape.reduced()
    enable_compile_cache()
    trainer = ElasticTrainer(cfg, shape, n_devices=args.n_devices,
                             model_par=args.model_par, seed=args.seed)
    out = trainer.train(args.steps)
    print(f"done: {out}")


if __name__ == "__main__":
    main()
