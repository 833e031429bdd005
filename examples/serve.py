"""Serving example: continuous-batching decode with KV-cache slots.

    PYTHONPATH=src python examples/serve.py
"""
import jax
import numpy as np

from repro.configs import get_config
from repro.models import model_zoo as zoo
from repro.serving.engine import Request, ServingEngine

cfg = get_config("llama3.2-3b").reduced()
params = zoo.init_params(cfg, jax.random.PRNGKey(0))
engine = ServingEngine(cfg, params, batch_size=4, max_seq=64)

rng = np.random.default_rng(0)
for rid in range(6):
    plen = int(rng.integers(3, 9))
    engine.submit(Request(rid=rid,
                          prompt=rng.integers(0, cfg.vocab_size, plen,
                                              dtype=np.int32),
                          max_new_tokens=8))
stats = engine.run_until_idle()
print(f"served 6 requests: {stats['tokens']} tokens in "
      f"{stats['seconds']:.2f}s ({stats['tok_per_s']:.1f} tok/s on CPU)")
