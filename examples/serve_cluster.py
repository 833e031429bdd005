"""Serving-cluster example: the paper's runtime ideas on a serving fleet.

A heterogeneous fleet (two 2.0x replicas, two 0.7x replicas) serves one
batch of requests twice — once with rate-oblivious round-robin routing,
once with rate-aware GreedyRefine routing on *measured* tokens/sec — and
a spot interruption hits a fast replica mid-run both times.  The doomed
replica is drained: its in-flight slots are checkpointed through the
in-memory store and re-admitted on survivors, so zero requests (and zero
decoded tokens) are lost.

    PYTHONPATH=src python examples/serve_cluster.py
"""

import jax

from repro.cluster import InstanceType, ROUTERS, ServingCluster
from repro.configs import get_config
from repro.models import model_zoo as zoo
from repro.serving.workload import PoissonArrivals, synthetic_requests

cfg = get_config("granite-8b").reduced()
params = zoo.init_params(cfg, jax.random.PRNGKey(0))
fleet = [InstanceType("fast.2x", 2.0), InstanceType("fast.2x", 2.0),
         InstanceType("slow.1x", 0.7), InstanceType("slow.1x", 0.7)]


for name, router_cls in ROUTERS.items():
    cluster = ServingCluster(cfg, params, fleet, router=router_cls(),
                             dt=1.0, batch_size=2, max_seq=32,
                             rebalance_lead=6.0, notice_deadline=4.0)
    # open-loop offered load: 3 req/s Poisson, scheduled one arrival
    # event at a time on the shared runtime loop
    reqs = synthetic_requests(20, cfg.vocab_size, seed=0)
    cluster.attach_arrivals(PoissonArrivals(reqs, 3.0, seed=0))
    cluster.inject_interruption(t=4.0, replica_rid=0)   # FIS analogue
    out = cluster.run()
    print(f"{name:12s} makespan={out['virtual_seconds']:5.0f}s "
          f"p99={out['p99_latency']:5.1f}s "
          f"agg={out['tok_per_s']:.2f} tok/s "
          f"dropped={out['dropped']} migrated={out['migrated_slots']}")
