"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (one per measured point).
Sections (run all, or pick with positional names / ``--scenario``):
  fig2_overdecomp     weak-scaling analogue: time/iter vs ODF (+latency)
  fig3_loadbalance    heterogeneous fleet: no-LB vs GreedyRefine (rate-aware)
  fig5_interrupt_cpu  rescale stage breakdown, host-memory store
  fig6_interrupt_dev  rescale stage breakdown, device-resident store
  fig7_modes          interruption-handling overhead, modes A/B/C
  fig8_endtoend       total runtime vs #simultaneous interruptions
  kernels             per-kernel throughput (ref path) + allclose check
  roofline            summary over artifacts/dryrun (§Roofline)
  cluster_hetero      serving cluster: rate-aware vs round-robin routing on
                      a 2-fast/2-slow fleet + a drained spot interruption
  cluster_slo         SLO layer A/B: priority admission + deadline routing +
                      mid-stream migration vs FIFO rate-aware, Poisson
                      interactive/batch mix + a drained spot interruption
  cluster_preempt     SLO-aware preemption A/B: pause batch slots for an
                      interactive surge vs buying replicas (attainment at
                      equal-or-lower fleet dollar cost, identical tokens)
  cluster_chaos       chaos-soup A/B: hard kill + slowdown + contention +
                      endpoint failure survived via checkpoints, heartbeat
                      failure detection and straggler quarantine vs the
                      same soup with recovery off (demonstrably lost work)
  cluster_matrix      million-request scenario matrix: behaviour shapes
                      (pulse_spikes/sawtooth/staircase/epochs/
                      staged_plateau) x router x preemption x fleet on
                      the SimEngine + a 10^6-request diurnal mega-cell;
                      consolidated BENCH_matrix.json with per-cell
                      attainment/p99/tok_per_s/dollar and a global
                      sim_events_per_sec
  engine_throughput   ServingEngine A/B: chunked bulk prefill + sync-free
                      batched decode vs the streamed per-token baseline
  engine_churn        paged-cache A/B: continuous batching on a block pool
                      vs dense slots at equal kv memory, Poisson churn

``--json`` additionally persists each requested section's rows to
``BENCH_<section>.json`` at the repo root (the perf trajectory).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# `python benchmarks/run.py` puts benchmarks/ itself on sys.path; the
# repo root must be there too for `from benchmarks.measure import ...`
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

_ROWS: list = []        # rows of the section currently running (--json)


def row(name: str, us_per_call: float, derived: str = ""):
    _ROWS.append({"name": name, "us_per_call": round(us_per_call, 1),
                  "derived": derived})
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


# ------------------------------------------------------------------ fig 2
def fig2_overdecomp():
    from repro.apps.jacobi2d import run_jacobi
    for latency_us, tag in ((0, "fast-net"), (500, "cloud-tcp")):
        base = None
        for odf in (1, 2, 4, 8):
            out = run_jacobi(grid_size=512, n_pes=4, odf=odf, iters=14,
                             comm_latency_s=latency_us * 1e-6)
            us = out.time_per_iter * 1e6
            base = base or us
            row(f"fig2_overdecomp_{tag}_odf{odf}", us,
                f"speedup_vs_odf1={base/us:.2f}")


# ------------------------------------------------------------------ fig 3
def fig3_loadbalance():
    rates = {"cpu_fleet": [1.0, 0.85, 0.6, 1.0],
             "gpu_fleet": [1.0, 1.0, 0.55, 0.55]}
    from repro.apps.jacobi2d import run_jacobi
    for fleet, mult in rates.items():
        res = {}
        for strat, aware, tag in ((None, False, "nolb"),
                                  ("greedy_refine", False, "refine_blind"),
                                  ("greedy_refine", True, "refine_rate")):
            out = run_jacobi(grid_size=768, n_pes=4, odf=4, iters=20,
                             kernel="lulesh", pe_rate_multipliers=mult,
                             lb_strategy=strat, lb_every=6, rate_aware=aware)
            tail = out.per_iter[-6:]
            us = float(np.mean([m["time_per_iter"] for m in tail])) * 1e6
            res[tag] = us
            imp = (1 - us / res["nolb"]) * 100 if "nolb" in res else 0.0
            row(f"fig3_lb_{fleet}_{tag}", us, f"improvement={imp:.1f}%")


# ------------------------------------------------------------- fig 5 / 6
def _interrupt_breakdown(store_kind: str, tag: str):
    from repro.configs import ARCHS, SHAPES
    from repro.launch.train import ElasticTrainer
    cfg = ARCHS["granite-8b"].reduced()
    shape = SHAPES["train_4k"].reduced()
    tr = ElasticTrainer(cfg, shape, n_devices=1, store_kind=store_kind)
    tr.train(2, log_every=0)
    ev_shrink = tr.runtime.rescale_to(1)   # simulated interruption rescale
    tr.train(1, log_every=0)
    ev_expand = tr.runtime.rescale_to(1)
    for ev, kind in ((ev_shrink, "shrink"), (ev_expand, "expand")):
        for stage, sec in ev.stages.items():
            row(f"{tag}_{kind}_{stage}", sec * 1e6,
                f"total={ev.total:.3f}s")


def fig5_interrupt_cpu():
    _interrupt_breakdown("memory", "fig5_cpu")


def fig6_interrupt_dev():
    _interrupt_breakdown("device", "fig6_dev")


# ------------------------------------------------------------------ fig 7
def fig7_modes():
    from benchmarks.measure import calibrated_cost_model
    from repro.core.cloud import CloudManager, Mode
    cost = calibrated_cost_model(state_bytes=16 * 64e6)
    for accel, hw in ((False, "cpu"), (True, "gpu")):
        cost_hw = cost.__class__(**{**cost.__dict__, "accelerator": accel})
        for mode in Mode:
            cm = CloudManager(n_instances=16, mode=mode, cost=cost_hw,
                              total_iters=5000, iter_seconds=0.2)
            cm.inject_interruption(t=100.0, count=1)
            rep = cm.run()
            total_overhead = rep.total_time - rep.ideal_time
            row(f"fig7_modes_{hw}_mode{mode.value}",
                total_overhead * 1e6,
                f"overhead_s={total_overhead:.1f};"
                f"rescales={len(rep.rescales)}")


# ------------------------------------------------------------------ fig 8
def fig8_endtoend():
    from benchmarks.measure import calibrated_cost_model
    from repro.core.cloud import CloudManager, Mode
    cost = calibrated_cost_model(state_bytes=16 * 64e6)
    for accel, hw, iters in ((False, "cpu", 5000), (True, "gpu", 30000)):
        cost_hw = cost.__class__(**{**cost.__dict__, "accelerator": accel})
        for n_int in (0, 1, 2, 4, 8):
            for mode in (Mode.B_REACTIVE, Mode.C_PROACTIVE):
                cm = CloudManager(n_instances=16, mode=mode, cost=cost_hw,
                                  total_iters=iters, iter_seconds=0.2)
                if n_int:
                    cm.inject_interruption(t=100.0, count=n_int)
                rep = cm.run()
                row(f"fig8_endtoend_{hw}_mode{mode.value}_int{n_int}",
                    rep.total_time * 1e6,
                    f"overhead={100*rep.overhead_frac:.2f}%")


# ------------------------------------------------------------------ kernels
def kernels():
    import jax
    import jax.numpy as jnp
    from repro.kernels.jacobi.ref import jacobi_step_ref
    from repro.models.layers import blockwise_attention
    from repro.models.mamba2 import ssd_intra_chunk_ref

    g = jax.random.normal(jax.random.PRNGKey(0), (1024, 1024))
    f = jax.jit(jacobi_step_ref)
    f(g).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        out = f(g)
    out.block_until_ready()
    us = (time.perf_counter() - t0) / 20 * 1e6
    row("kernel_jacobi_ref_1024", us,
        f"GBps={1024*1024*4*5/(us/1e6)/1e9:.1f}")

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 1024, 8, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 1024, 2, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 1024, 2, 64), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: blockwise_attention(
        q, k, v, causal=True, block_q=256, block_kv=256))
    f(q, k, v).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        out = f(q, k, v)
    out.block_until_ready()
    us = (time.perf_counter() - t0) / 5 * 1e6
    flops = 2 * 2 * 1024 * 1024 * 8 * 64 / 2  # causal half
    row("kernel_flash_ref_1k", us, f"GFLOPs={flops/(us/1e6)/1e9:.1f}")

    b, nc, l, h, p, n = 1, 8, 128, 8, 64, 64
    xs = jax.random.split(jax.random.PRNGKey(1), 5)
    xr = jax.random.normal(xs[0], (b, nc, l, h, p))
    dtr = jax.nn.softplus(jax.random.normal(xs[1], (b, nc, l, h)))
    dacs = jnp.cumsum(-jnp.abs(jax.random.normal(xs[2], (b, nc, l, h))) * .1,
                      axis=2)
    Br = jax.random.normal(xs[3], (b, nc, l, n))
    Cr = jax.random.normal(xs[4], (b, nc, l, n))
    f = jax.jit(ssd_intra_chunk_ref)
    jax.block_until_ready(f(xr, dtr, dacs, Br, Cr))
    t0 = time.perf_counter()
    for _ in range(5):
        out = f(xr, dtr, dacs, Br, Cr)
    jax.block_until_ready(out)
    us = (time.perf_counter() - t0) / 5 * 1e6
    row("kernel_ssd_ref_1k", us, f"chunk={l}")


# ------------------------------------------------------------------ cluster
def cluster_hetero(arrival: str = "batch", quick: bool = False):
    """Serving-cluster A/B (paper §III/§IV on the serving workload).

    A 2-fast/2-slow replica fleet serves the same request stream under
    round-robin and rate-aware routing; one fast replica receives a spot
    interruption mid-run and is drained (slots checkpointed + migrated).
    ``arrival`` selects the offered-load model: ``batch`` (closed-loop,
    everything at t=0), ``poisson:<rate>`` or ``trace:<file>``
    (open-loop, scheduled one arrival event at a time).  Rate-aware
    routing must win on p99 latency AND aggregate tokens/sec, and the
    drain must drop zero requests.
    """
    import jax
    from repro.cluster import (InstanceType, ROUTERS, ServingCluster)
    from repro.configs import get_config
    from repro.models import model_zoo as zoo
    from repro.serving.workload import make_arrivals, synthetic_requests

    cfg = get_config("granite-8b").reduced()
    params = zoo.init_params(cfg, jax.random.PRNGKey(0))
    fleet = [InstanceType("fast.2x", 2.0), InstanceType("fast.2x", 2.0),
             InstanceType("slow.1x", 0.7), InstanceType("slow.1x", 0.7)]
    n_requests, max_seq = (12, 32) if quick else (24, 48)

    results = {}
    for name, router_cls in ROUTERS.items():
        cl = ServingCluster(cfg, params, fleet, router=router_cls(),
                            dt=1.0, batch_size=2, max_seq=max_seq,
                            rebalance_lead=6.0, notice_deadline=4.0)
        reqs = synthetic_requests(n_requests, cfg.vocab_size, seed=0,
                                  prompt_len=(3, 9), max_new=(4, 12))
        cl.attach_arrivals(make_arrivals(arrival, reqs, seed=0))
        cl.inject_interruption(t=4.0, replica_rid=0)
        out = cl.run(max_time=10_000)
        results[name] = out
        # count loss only over requests actually offered (a short trace
        # file truncates the request list; that is not a drain drop)
        offered = [r for r in reqs if r.rid in cl.metrics.traces]
        lost = sum(r.max_new_tokens - len(r.out_tokens) for r in offered)
        tag = f"cluster_hetero_{name}"
        row(f"{tag}_p50", out["p50_latency"] * 1e6,
            f"virtual_s={out['p50_latency']:.1f};arrival={arrival}")
        row(f"{tag}_p99", out["p99_latency"] * 1e6,
            f"virtual_s={out['p99_latency']:.1f}")
        row(f"{tag}_throughput", 0.0,
            f"tok_per_s={out['tok_per_s']:.2f};"
            f"makespan_s={out['virtual_seconds']:.0f}")
        row(f"{tag}_drain", out["interruption_overhead_s"] * 1e6,
            f"dropped={out['dropped']};migrated={out['migrated_slots']};"
            f"tokens_lost={lost}")
        assert out["dropped"] == 0 and lost == 0, \
            f"{name}: drain dropped work"
    ra, rr = results["rate_aware"], results["round_robin"]
    wins = (ra["p99_latency"] < rr["p99_latency"]
            and ra["tok_per_s"] > rr["tok_per_s"])
    row("cluster_hetero_summary", 0.0,
        f"rate_aware_beats_round_robin={wins};"
        f"p99={ra['p99_latency']:.1f}vs{rr['p99_latency']:.1f};"
        f"tok_per_s={ra['tok_per_s']:.2f}vs{rr['tok_per_s']:.2f}")
    assert wins, "rate-aware routing did not beat round-robin"


# ------------------------------------------------------------------ SLOs
def cluster_slo(quick: bool = False):
    """SLO scheduling A/B (the elastic-scheduler deadline layer on top of
    §III rate-aware balancing).

    The same 2-fast/2-slow fleet serves an identical seeded Poisson mix
    of interactive (tight deadline) and batch (loose deadline,
    lazily-admitted) requests, with the same injected spot interruption:

    * FIFO      — ``RateAwareRouter``, FIFO admission, no rebalancer
                  (PR-1 behaviour);
    * SLO-aware — ``DeadlineAwareRouter`` (GreedyRefine + predicted-miss
                  repair), priority admission (batch held until backlog
                  headroom), and the recurring mid-stream migration pass.

    SLO-aware scheduling must strictly improve interactive-class deadline
    attainment AND interactive p99 latency, drop nothing, and — because
    greedy decode is placement/migration-independent — emit bit-identical
    per-request tokens to the FIFO run.
    """
    import jax
    from repro.cluster import (DeadlineAwareRouter, InstanceType,
                               RateAwareRouter, ServingCluster)
    from repro.configs import get_config
    from repro.models import model_zoo as zoo
    from repro.runtime import FaultTrace
    from repro.serving.workload import (PoissonArrivals, SLOClass,
                                        classed_requests)

    cfg = get_config("granite-8b").reduced()
    params = zoo.init_params(cfg, jax.random.PRNGKey(0))
    fleet = [InstanceType("fast.2x", 2.0), InstanceType("fast.2x", 2.0),
             InstanceType("slow.1x", 0.7), InstanceType("slow.1x", 0.7)]
    interactive = SLOClass("interactive", 0, deadline=12.0)
    batch = SLOClass("batch", 2, deadline=400.0, admit_lazily=True)
    n_requests, rate = (18, 2.5) if quick else (36, 2.0)

    def one_run(slo_aware: bool):
        trace = FaultTrace(rebalance_lead=6.0, notice_deadline=4.0)
        trace.inject(4.0, 0)
        kw = dict(dt=1.0, batch_size=2, max_seq=48, trace=trace)
        if slo_aware:
            cl = ServingCluster(cfg, params, fleet,
                                router=DeadlineAwareRouter(),
                                admission="priority",
                                batch_admit_headroom=24.0,
                                rebalance_interval=2.0, **kw)
        else:
            cl = ServingCluster(cfg, params, fleet,
                                router=RateAwareRouter(), **kw)
        reqs = classed_requests(n_requests, cfg.vocab_size,
                                interactive_frac=0.5, seed=0,
                                interactive=interactive, batch=batch)
        cl.attach_arrivals(PoissonArrivals(reqs, rate, seed=0))
        out = cl.run(max_time=10_000)
        return cl, reqs, out

    results = {}
    for tag, slo_aware in (("fifo", False), ("slo_aware", True)):
        cl, reqs, out = one_run(slo_aware)
        results[tag] = (reqs, out)
        row(f"cluster_slo_{tag}_interactive_p99",
            out["p99_latency_interactive"] * 1e6,
            f"attainment={out['attainment_interactive']:.3f};"
            f"virtual_s={out['p99_latency_interactive']:.1f}")
        row(f"cluster_slo_{tag}_batch",
            out["p99_latency_batch"] * 1e6,
            f"attainment={out['attainment_batch']:.3f}")
        row(f"cluster_slo_{tag}_fleet", 0.0,
            f"tok_per_s={out['tok_per_s']:.2f};dropped={out['dropped']};"
            f"migrated={out['migrated_slots']};"
            f"rebalance_migrations={out['rebalance_migrations']}")
        assert out["dropped"] == 0, f"{tag}: dropped requests"
        assert out["completed"] == n_requests, f"{tag}: incomplete run"

    (fifo_reqs, fifo), (slo_reqs, slo) = (results["fifo"],
                                          results["slo_aware"])
    for a, b in zip(fifo_reqs, slo_reqs):
        assert a.out_tokens == b.out_tokens, \
            f"req{a.rid}: SLO scheduling changed decoded tokens"
    att_f = fifo["attainment_interactive"]
    att_s = slo["attainment_interactive"]
    p99_f = fifo["p99_latency_interactive"]
    p99_s = slo["p99_latency_interactive"]
    wins = att_s > att_f and p99_s < p99_f
    row("cluster_slo_summary", 0.0,
        f"slo_beats_fifo={wins};"
        f"attainment={att_s:.3f}vs{att_f:.3f};"
        f"p99_interactive={p99_s:.1f}vs{p99_f:.1f};"
        f"identical_tokens=True;"
        f"migrations={slo['rebalance_migrations']}")
    assert wins, (
        f"SLO-aware did not strictly improve interactive attainment/p99: "
        f"{att_s:.3f} vs {att_f:.3f}, {p99_s:.1f} vs {p99_f:.1f}")
    assert slo["rebalance_migrations"] > 0, \
        "the mid-stream rebalancer never migrated a slot"


# ------------------------------------------------------------- preemption
def cluster_preempt(quick: bool = False):
    """SLO-aware preemption A/B (migratable WorkUnits as the paper's one
    mechanism, preemption as a ControlPlane *policy* on top).

    A fleet saturated with long batch-class decodes receives a seeded
    interactive surge.  Both runs share the deadline-aware router and an
    SLO-pressure autoscaler; they differ ONLY in the preemption policy:

    * off — the base (hold-only) policy: interactive work waits for a
      batch slot to free naturally; decided deadline misses push the
      autoscaler into buying extra replicas (dollars for attainment).
    * on  — ``SLOPreemption``: batch slots are *paused* through the same
      pack/unpack mechanism as a drain (slot freed, snapshot retained),
      interactive work admits immediately, and the paused streams resume
      bit-identically once the surge clears.

    Preemption must strictly improve interactive attainment at
    equal-or-lower fleet dollar cost, with bit-identical per-request
    token streams and zero dropped/incomplete requests.
    """
    import jax
    from repro.cluster import (DeadlineAwareRouter, InstanceType,
                               ServingCluster, SLOPreemption)
    from repro.configs import get_config
    from repro.models import model_zoo as zoo
    from repro.serving.engine import Request
    from repro.serving.workload import SLOClass

    cfg = get_config("granite-8b").reduced()
    params = zoo.init_params(cfg, jax.random.PRNGKey(0))
    n_rep = 2 if quick else 3
    fleet = [InstanceType("std.1x", 1.0, cost_per_hour=1.0)
             for _ in range(n_rep)]
    interactive = SLOClass("interactive", 0, deadline=22.0)
    batch = SLOClass("batch", 2, deadline=2000.0, admit_lazily=True)
    n_batch = 2 * n_rep + 2              # saturate every slot + a queue
    n_int = 2 * n_rep                    # one surge wave per slot-pair
    surge_t = 8.0

    def requests():
        rng = np.random.default_rng(7)
        reqs = []
        for rid in range(n_batch):       # long batch decodes at t=0
            reqs.append((0.0, Request(
                rid=rid,
                prompt=rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(6, 10)),
                                    dtype=np.int32),
                max_new_tokens=int(rng.integers(30, 38)), slo=batch)))
        for rid in range(n_batch, n_batch + n_int):   # the surge
            reqs.append((surge_t, Request(
                rid=rid,
                prompt=rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(3, 6)),
                                    dtype=np.int32),
                max_new_tokens=int(rng.integers(4, 7)),
                slo=interactive)))
        return reqs

    def one_run(preempt: bool):
        cl = ServingCluster(
            cfg, params, fleet, router=DeadlineAwareRouter(),
            dt=1.0, batch_size=2, max_seq=48, decode_block=2,
            preemption=(SLOPreemption(max_preempts_per_pass=2 * n_rep)
                        if preempt else None),
            autoscaler_kw=dict(scale_up_backlog=100_000.0,
                               scale_up_patience=2.0,
                               replacement_latency=12.0,
                               max_replicas=n_rep + 2,
                               slo_scale_up=True))
        reqs = requests()
        for at, req in reqs:
            cl.submit(req, at=at)
        out = cl.run(max_time=10_000)
        return cl, [r for _, r in reqs], out

    results = {}
    for tag, preempt in (("off", False), ("on", True)):
        cl, reqs, out = one_run(preempt)
        results[tag] = (reqs, out)
        row(f"cluster_preempt_{tag}_interactive", 0.0,
            f"attainment={out['attainment_interactive']:.3f};"
            f"p99={out['p99_latency_interactive']:.1f}s")
        row(f"cluster_preempt_{tag}_fleet", 0.0,
            f"dollar_cost={out['fleet_dollar_cost']:.4f};"
            f"replicas={len(cl.replicas)};"
            f"preemptions={out['preemptions']};"
            f"resumes={out['resumes']}")
        assert out["dropped"] == 0, f"{tag}: dropped requests"
        assert out["completed"] == n_batch + n_int, f"{tag}: incomplete"

    (off_reqs, off), (on_reqs, on) = results["off"], results["on"]
    for a, b in zip(off_reqs, on_reqs):
        assert a.out_tokens == b.out_tokens, \
            f"req{a.rid}: preemption changed decoded tokens"
    att_off, att_on = (off["attainment_interactive"],
                       on["attainment_interactive"])
    cost_off, cost_on = off["fleet_dollar_cost"], on["fleet_dollar_cost"]
    wins = att_on > att_off and cost_on <= cost_off + 1e-9
    row("cluster_preempt_summary", 0.0,
        f"preempt_beats_scaleup={wins};"
        f"attainment={att_on:.3f}vs{att_off:.3f};"
        f"dollar_cost={cost_on:.4f}vs{cost_off:.4f};"
        f"preemptions={on['preemptions']};resumes={on['resumes']};"
        f"identical_tokens=True")
    assert on["preemptions"] > 0 and on["resumes"] == on["preemptions"], \
        "SLO preemption never paused (or never resumed) a batch slot"
    assert off["preemptions"] == 0, "baseline run must not preempt"
    assert wins, (
        f"preemption did not strictly improve interactive attainment at "
        f"equal-or-lower cost: attainment {att_on:.3f} vs {att_off:.3f}, "
        f"dollars {cost_on:.4f} vs {cost_off:.4f}")


# ----------------------------------------------------- vertical elasticity
def cluster_vertical(quick: bool = False):
    """Vertical elasticity A/B: in-place resize + QoS vs horizontal-only.

    A small fleet saturated with batch-class decodes takes an
    interactive surge, then a quiet tail.  Both arms see the same
    requests and the same *peak* slot capacity; they differ only in how
    capacity appears:

    * horizontal — a fixed batch width per replica; the autoscaler buys
      up to two extra replicas on sustained backlog and pays a
      ``replacement_latency`` before they serve (then bills them until
      idle scale-down).
    * vertical — the fleet is pinned, and a ``FixedThresholdVertical``
      recommender grows each replica's lanes in place through the
      canonical pack/unpack path (no drain, surviving slots untouched)
      the moment backlog per lane crosses the threshold — and shrinks
      back in the quiet tail, with ``QoSPolicy`` holding BestEffort
      arrivals out of the Guaranteed reservation and ordering any
      shrink evictions BestEffort-first.

    Vertical must reach at-least-equal interactive attainment at
    strictly lower fleet dollar cost, with zero lost WorkUnits and
    bit-identical per-request streams across the arms.
    """
    import jax
    from repro.cluster import DeadlineAwareRouter, InstanceType, \
        ServingCluster
    from repro.configs import get_config
    from repro.models import model_zoo as zoo
    from repro.serving.engine import Request
    from repro.serving.workload import SLOClass
    from repro.vertical import FixedThresholdVertical, QoSPolicy

    cfg = get_config("granite-8b").reduced()
    params = zoo.init_params(cfg, jax.random.PRNGKey(0))
    n_rep = 2
    base_batch, max_batch = 2, 4
    fleet = [InstanceType("std.1x", 1.0, spot=False, cost_per_hour=1.0)
             for _ in range(n_rep)]
    interactive = SLOClass("interactive", 0, deadline=26.0)
    batch = SLOClass("batch", 2, deadline=4000.0, admit_lazily=True)
    n_batch = 6 if quick else 8
    n_int = 4 if quick else 6
    surge_t = 6.0
    decode_span = (18, 24) if quick else (28, 36)

    def requests():
        rng = np.random.default_rng(11)
        reqs = []
        for rid in range(n_batch):       # the batch floor at t=0
            reqs.append((0.0, Request(
                rid=rid,
                prompt=rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(6, 10)),
                                    dtype=np.int32),
                max_new_tokens=int(rng.integers(*decode_span)),
                slo=batch)))
        for rid in range(n_batch, n_batch + n_int):   # the surge
            reqs.append((surge_t, Request(
                rid=rid,
                prompt=rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(3, 6)),
                                    dtype=np.int32),
                max_new_tokens=int(rng.integers(4, 7)),
                slo=interactive)))
        return reqs

    def one_run(vertical: bool):
        if vertical:
            qos = QoSPolicy()
            kw = dict(
                vertical=FixedThresholdVertical(
                    min_batch=base_batch, max_batch=max_batch, step=2,
                    grow_backlog=12.0, shrink_backlog=3.0,
                    cooldown=4.0, qos=qos),
                qos=qos,
                # the fleet is pinned: capacity moves only vertically
                autoscaler_kw=dict(scale_up_backlog=1e9,
                                   slo_scale_up=False,
                                   max_replicas=n_rep))
        else:
            # equal peak capacity: up to 2 extra replicas at base_batch
            # lanes each == n_rep replicas at max_batch lanes
            kw = dict(
                autoscaler_kw=dict(scale_up_backlog=12.0 * base_batch,
                                   scale_up_patience=2.0,
                                   replacement_latency=12.0,
                                   max_replicas=n_rep + 2,
                                   scale_down_idle=20.0,
                                   slo_scale_up=True))
        cl = ServingCluster(cfg, params, fleet,
                            router=DeadlineAwareRouter(), dt=1.0,
                            batch_size=base_batch, max_seq=48,
                            decode_block=2, admission="priority", **kw)
        reqs = requests()
        for at, req in reqs:
            cl.submit(req, at=at)
        out = cl.run(max_time=10_000)
        return cl, [r for _, r in reqs], out

    results = {}
    for tag, vertical in (("horizontal", False), ("vertical", True)):
        cl, reqs, out = one_run(vertical)
        results[tag] = (reqs, out)
        row(f"cluster_vertical_{tag}_interactive", 0.0,
            f"attainment={out['attainment_interactive']:.3f};"
            f"p99={out['p99_latency_interactive']:.1f}s")
        row(f"cluster_vertical_{tag}_fleet", 0.0,
            f"dollar_cost={out['fleet_dollar_cost']:.4f};"
            f"replicas={len(cl.replicas)};"
            f"grows={out['vertical_grows']};"
            f"shrinks={out['vertical_shrinks']};"
            f"evictions={out['vertical_evictions']};"
            f"qos_guaranteed_slot_s={out['qos_guaranteed_slot_s']:.1f};"
            f"qos_best_effort_slot_s={out['qos_best_effort_slot_s']:.1f}")
        assert out["dropped"] == 0, f"{tag}: dropped requests"
        assert out["completed"] == n_batch + n_int, f"{tag}: incomplete"

    (h_reqs, h), (v_reqs, v) = results["horizontal"], results["vertical"]
    for a, b in zip(h_reqs, v_reqs):
        assert a.out_tokens == b.out_tokens, \
            f"req{a.rid}: vertical resize changed decoded tokens"
    att_h, att_v = (h["attainment_interactive"],
                    v["attainment_interactive"])
    cost_h, cost_v = h["fleet_dollar_cost"], v["fleet_dollar_cost"]
    wins = att_v >= att_h - 1e-9 and cost_v < cost_h - 1e-9
    row("cluster_vertical_summary", 0.0,
        f"vertical_beats_horizontal={wins};"
        f"attainment={att_v:.3f}vs{att_h:.3f};"
        f"dollar_cost={cost_v:.4f}vs{cost_h:.4f};"
        f"grows={v['vertical_grows']};shrinks={v['vertical_shrinks']};"
        f"evictions={v['vertical_evictions']};lost=0;"
        f"identical_tokens=True")
    assert v["vertical_grows"] > 0, "vertical arm never grew a replica"
    assert v["vertical_shrinks"] > 0, \
        "vertical arm never shrank back in the quiet tail"
    assert h["vertical_grows"] == h["vertical_shrinks"] == 0, \
        "horizontal arm must not resize"
    assert wins, (
        f"vertical+QoS did not match attainment at strictly lower cost: "
        f"attainment {att_v:.3f} vs {att_h:.3f}, "
        f"dollars {cost_v:.4f} vs {cost_h:.4f}")


# ------------------------------------------------------------ spot market
def cluster_spot_market(quick: bool = False):
    """Spot-market shopping A/B (priced markets + interruption models).

    One fleet of identical instances is bought on a two-market exchange:
    *volatile* opens at a quarter of the on-demand rate but carries a
    scheduled mid-run price spike with price-coupled interruption
    intensity; *steady* costs more and almost never interrupts.  Both
    runs serve the same seeded Poisson interactive/batch mix with the
    ``different_market`` fallback on spot notices; they differ ONLY in
    the exchange's shopping mode:

    * naive    — buys the cheapest spot rate *right now* (volatile),
                 then pays spike prices and eats the interruption churn;
    * adjusted — prices each market as mean rate + predicted
                 interruption rate x interruption dollars over a
                 lookahead window, sees the spike coming, and buys
                 steady up front.

    Adjusted must deliver strictly higher savings vs all-on-demand at
    equal-or-better interactive attainment, drop nothing, and emit
    bit-identical per-request tokens.
    """
    import jax
    from repro.cluster import (DeadlineAwareRouter, InstanceType,
                               ServingCluster)
    from repro.configs import get_config
    from repro.market import MarketCatalog, SpotExchange, SpotMarket
    from repro.models import model_zoo as zoo
    from repro.serving.workload import (PoissonArrivals, SLOClass,
                                        classed_requests)

    cfg = get_config("granite-8b").reduced()
    params = zoo.init_params(cfg, jax.random.PRNGKey(0))
    n_rep, n_requests, rate = (2, 14, 2.5) if quick else (3, 30, 2.0)
    fleet = [InstanceType("std.1x", 1.0, cost_per_hour=1.0)
             for _ in range(n_rep)]
    interactive = SLOClass("interactive", 0, deadline=15.0)
    batch = SLOClass("batch", 2, deadline=500.0, admit_lazily=True)

    def exchange(mode):
        cat = MarketCatalog()
        cat.add_market(SpotMarket(
            "volatile", base_rate=0.25, volatility=0.06,
            spikes=((10.0, 400.0, 5.0),), interruptions_per_hour=4.0,
            price_power=3.0, seed=1))
        cat.add_market(SpotMarket(
            "steady", base_rate=0.45, volatility=0.02,
            interruptions_per_hour=0.05, seed=2))
        for it in set(fleet):
            cat.list_instance(it, markets=("volatile", "steady"))
        return SpotExchange(cat, seed=0, mode=mode, sample_until=500.0)

    def one_run(mode):
        cl = ServingCluster(
            cfg, params, fleet, router=DeadlineAwareRouter(),
            dt=1.0, batch_size=2, max_seq=48,
            admission="priority", batch_admit_headroom=24.0,
            rebalance_lead=6.0, notice_deadline=4.0,
            market=exchange(mode), fallback="different_market",
            autoscaler_kw=dict(replacement_latency=10.0,
                               scale_up_backlog=100_000.0,
                               scale_down_idle=10_000.0))
        reqs = classed_requests(n_requests, cfg.vocab_size,
                                interactive_frac=0.5, seed=0,
                                interactive=interactive, batch=batch)
        cl.attach_arrivals(PoissonArrivals(reqs, rate, seed=0))
        out = cl.run(max_time=10_000)
        return cl, reqs, out

    results = {}
    for mode in ("naive", "adjusted"):
        cl, reqs, out = one_run(mode)
        results[mode] = (reqs, out)
        row(f"cluster_spot_market_{mode}_cost", 0.0,
            f"market_dollars={out['market_dollar_cost']:.4f};"
            f"on_demand_dollars={out['on_demand_dollar_cost']:.4f};"
            f"savings={out['savings_pct']:.1f}%;"
            f"interruptions={out['spot_interruptions']}")
        row(f"cluster_spot_market_{mode}_slo", 0.0,
            f"attainment={out['attainment_interactive']:.3f};"
            f"p99_interactive={out['p99_latency_interactive']:.1f}s;"
            f"dropped={out['dropped']}")
        # the by-market/by-strategy ledger breakdown must surface in the
        # run summary (the README's market-report contract)
        for m in ("volatile", "steady"):
            assert f"market_{m}_purchases" in out, f"no {m} breakdown"
        assert "strategy_initial_purchases" in out, "no strategy breakdown"
        assert out["dropped"] == 0, f"{mode}: dropped requests"
        assert out["completed"] == n_requests, f"{mode}: incomplete run"

    (nai_reqs, nai), (adj_reqs, adj) = (results["naive"],
                                        results["adjusted"])
    for a, b in zip(nai_reqs, adj_reqs):
        assert a.out_tokens == b.out_tokens, \
            f"req{a.rid}: market shopping changed decoded tokens"
    sav_n, sav_a = nai["savings_pct"], adj["savings_pct"]
    att_n, att_a = (nai["attainment_interactive"],
                    adj["attainment_interactive"])
    wins = sav_a > sav_n and att_a >= att_n
    row("cluster_spot_market_summary", 0.0,
        f"adjusted_beats_naive={wins};"
        f"savings={sav_a:.1f}%vs{sav_n:.1f}%;"
        f"attainment={att_a:.3f}vs{att_n:.3f};"
        f"interruptions={adj['spot_interruptions']}vs"
        f"{nai['spot_interruptions']};identical_tokens=True")
    assert nai["spot_interruptions"] > 0, \
        "the naive shopper never got interrupted (no churn to avoid)"
    assert wins, (
        f"interruption-adjusted shopping did not beat naive-cheapest: "
        f"savings {sav_a:.1f}% vs {sav_n:.1f}%, attainment "
        f"{att_a:.3f} vs {att_n:.3f}")


def cluster_chaos(quick: bool = False):
    """Chaos fault model + checkpoint-based recovery A/B.

    One fixed chaos soup hits a 2-replica fleet mid-stream: a zero-notice
    ``hard_kill`` on the busiest replica, a 3x ``slowdown`` window on the
    survivor, a fabric-wide ``network_contention`` window, and a
    transient ``endpoint_failure``.  Three runs over the identical seeded
    request set:

    * fault_free    — the reference streams (per-request tokens);
    * recovery_on   — periodic WorkUnit checkpoints + heartbeat failure
                      detection + straggler quarantine: the kill is
                      discovered by silence, checkpointed slots restore
                      and re-decode their lost tail deterministically,
                      un-checkpointed requests readmit from the prompt;
    * recovery_off  — same soup, no checkpoints/detector: the killed
                      replica's work is demonstrably lost.

    Recovery must complete every request with final streams bit-identical
    to the fault-free reference (greedy decode is placement-independent)
    at strictly higher goodput than the no-recovery run, with bounded
    replayed-token overhead.
    """
    import jax
    from repro.cluster import (CheckpointPolicy, FailureDetector,
                               InstanceType, ServingCluster,
                               StragglerPolicy)
    from repro.configs import get_config
    from repro.models import model_zoo as zoo
    from repro.runtime import FaultTrace
    from repro.serving.workload import synthetic_requests

    cfg = get_config("granite-8b").reduced()
    params = zoo.init_params(cfg, jax.random.PRNGKey(0))
    n_requests = 12 if quick else 20
    fleet = [InstanceType("std.1x", 1.0, cost_per_hour=1.0)
             for _ in range(2)]

    def chaos_trace():
        trace = FaultTrace()
        # mid-step-cadence kill: tokens decoded since the last
        # checkpoint are genuinely lost and must re-decode
        trace.inject_hard_kill(9.5, 0)
        trace.inject_slowdown(4.0, 1, factor=3.0, duration=10.0)
        trace.inject_contention(5.0, factor=2.0, duration=8.0)
        trace.inject_endpoint_failure(2.0, 0, count=1)
        return trace

    def one_run(mode):
        kw = {}
        if mode == "recovery_on":
            # an interval that does NOT divide the kill time, so the
            # last checkpoint predates the kill and a real lost tail
            # gets re-decoded (the replayed-token overhead the guard
            # bounds)
            kw = dict(checkpoint=CheckpointPolicy(interval=3.0),
                      health=FailureDetector(heartbeat_interval=1.0,
                                             check_interval=1.0,
                                             suspect_after=2.5,
                                             confirm_after=5.0),
                      straggler=StragglerPolicy())
        trace = FaultTrace() if mode == "fault_free" else chaos_trace()
        cl = ServingCluster(cfg, params, fleet, trace=trace, dt=1.0,
                            batch_size=2, max_seq=32, **kw)
        reqs = synthetic_requests(n_requests, cfg.vocab_size, seed=0,
                                  prompt_len=(3, 8))
        for i, r in enumerate(reqs):
            cl.submit(r, at=0.3 * i)
        out = cl.run(max_time=10_000)
        useful = sum(len(r.out_tokens) for r in reqs if r.done)
        goodput = useful / max(out["virtual_seconds"], 1e-9)
        return reqs, out, goodput

    results = {}
    for mode in ("fault_free", "recovery_on", "recovery_off"):
        reqs, out, goodput = one_run(mode)
        results[mode] = (reqs, out, goodput)
        row(f"cluster_chaos_{mode}", 0.0,
            f"completed={out['completed']}/{n_requests};"
            f"lost={out['requests_lost']};goodput={goodput:.3f}tok/s;"
            f"hard_kills={out['hard_kills']};"
            f"checkpoints={out['checkpoints']};"
            f"recovered={out['requests_recovered']};"
            f"replayed={out['replayed_tokens']}")

    ref_reqs, _, _ = results["fault_free"]
    on_reqs, on, goodput_on = results["recovery_on"]
    off_reqs, off, goodput_off = results["recovery_off"]

    identical = all(a.out_tokens == b.out_tokens
                    for a, b in zip(ref_reqs, on_reqs))
    useful_on = sum(len(r.out_tokens) for r in on_reqs if r.done)
    replay_frac = on["replayed_tokens"] / max(useful_on, 1)
    row("cluster_chaos_summary", 0.0,
        f"goodput={goodput_on:.3f}vs{goodput_off:.3f}tok/s;"
        f"lost={on['requests_lost']}vs{off['requests_lost']};"
        f"bit_identical={identical};"
        f"recovered={on['requests_recovered']};"
        f"replay_frac={replay_frac:.3f};"
        f"hard_kills={on['hard_kills']};"
        f"recovery_latency={on['recovery_latency_s']:.1f}s")
    assert on["hard_kills"] >= 1, "the chaos soup never killed anyone"
    assert on["dropped"] == 0 and on["requests_lost"] == 0, \
        "recovery lost requests despite checkpoints + detection"
    assert on["completed"] == n_requests, "recovery run incomplete"
    assert identical, "recovered streams diverged from fault-free"
    assert off["requests_lost"] > 0, \
        "the no-recovery run lost nothing (the kill never bit)"
    assert goodput_on > goodput_off, (
        f"recovery goodput {goodput_on:.3f} tok/s did not beat "
        f"no-recovery {goodput_off:.3f} tok/s")


# ------------------------------------------------------------------ engine
def engine_throughput(quick: bool = False):
    """ServingEngine hot-path A/B: chunked bulk prefill + sync-free
    batched decode vs the streamed per-token baseline.

    Measures (a) prefill tokens/sec for a 64-token prompt — streamed
    feeds one prompt token per full-batch decode dispatch, chunked runs
    one ``make_prefill`` bucket and scatters the cache columns; (b)
    batched decode tokens/sec at decode blocks of 1 and 8 (a block-8
    window is one dispatch and zero device->host transfers).  Generated
    tokens must be bit-identical across modes, and chunked prefill must
    be >= 3x streamed.
    """
    import jax
    from repro.configs import get_config
    from repro.models import model_zoo as zoo
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.workload import prefill_heavy_requests

    cfg = get_config("granite-8b").reduced()
    params = zoo.init_params(cfg, jax.random.PRNGKey(0))
    max_seq, prompt_len = 96, 64
    max_new = 4 if quick else 16
    reps = 2 if quick else 4

    def engine(mode):
        return ServingEngine(cfg, params, batch_size=4, max_seq=max_seq,
                             prefill_mode=mode)

    # warm the compile caches (shared module-level, once per mode): both
    # prefill buckets (64-token measured prompt AND the 8-token decode
    # workload -> bucket 16) plus the block-1 and block-8 decode loops,
    # so no timed region below pays a jit compile
    for mode in ("streamed", "chunked"):
        e = engine(mode)
        for r in prefill_heavy_requests(1, cfg.vocab_size,
                                        prompt_len=prompt_len,
                                        max_new=max_new, seed=99):
            e.submit(r)
        for r in prefill_heavy_requests(1, cfg.vocab_size, prompt_len=8,
                                        max_new=max_new, seed=98,
                                        start_rid=1):
            e.submit(r)
        while e.n_active or e.n_queued:
            e.step()
        e.step_many(8)

    results = {}
    for mode in ("streamed", "chunked"):
        tps = []
        tokens = None
        for rep in range(reps):
            e = engine(mode)
            req, = prefill_heavy_requests(1, cfg.vocab_size,
                                          prompt_len=prompt_len,
                                          max_new=max_new, seed=rep)
            e.submit(req)
            t0 = time.perf_counter()
            while e.fed_tokens(0) < prompt_len - 1:
                e.step()        # streamed: one dispatch per prompt token
            jax.block_until_ready(e.sample.fed)
            tps.append((prompt_len - 1) / (time.perf_counter() - t0))
            e.run_until_idle()
            if rep == 0:
                tokens = list(req.out_tokens)
        results[mode] = {"prefill_tps": max(tps), "tokens": tokens}
        row(f"engine_prefill_{mode}", 1e6 / max(tps),
            f"prefill_tok_per_s={max(tps):.0f};prompt={prompt_len}")

    assert results["streamed"]["tokens"] == results["chunked"]["tokens"], \
        "chunked prefill diverged from the streamed baseline"
    speedup = (results["chunked"]["prefill_tps"]
               / results["streamed"]["prefill_tps"])
    row("engine_prefill_speedup", 0.0,
        f"chunked_over_streamed={speedup:.1f}x;identical_tokens=True")
    assert speedup >= 3.0, \
        f"chunked prefill only {speedup:.1f}x streamed (need >= 3x)"

    # batched decode: block-1 (one dispatch + bookkeeping per step) vs
    # block-8 (one dispatch per 8 steps, zero transfers in the window)
    n_req = 4 if quick else 8
    decode_new = 24 if quick else 48
    for block in (1, 8):
        e = engine("chunked")
        for r in prefill_heavy_requests(n_req, cfg.vocab_size,
                                        prompt_len=8, max_new=decode_new,
                                        seed=5):
            e.submit(r)
        t0 = time.perf_counter()
        emitted = 0
        while e.n_active or e.n_queued:
            emitted += e.step_many(block)["emitted"]
        jax.block_until_ready(e.sample.fed)
        dt = time.perf_counter() - t0
        row(f"engine_decode_block{block}", 1e6 * dt / max(emitted, 1),
            f"decode_tok_per_s={emitted/dt:.0f};"
            f"host_syncs={e.host_syncs};tokens={emitted}")


# ------------------------------------------------------------------ churn
def engine_churn(quick: bool = False):
    """Paged-cache A/B under slot churn (the PR-7 tentpole claim).

    The same Poisson-paced stream of short mixed-length requests is
    served twice at IDENTICAL kv-cache memory: a dense engine with
    ``dense_lanes`` slots (each slot owns a full ``max_seq`` cache
    column) vs a paged engine with twice the lanes sharing a block pool
    sized to exactly the dense engine's kv footprint
    (``dense_lanes * max_seq / block_size`` blocks).  Under churn the
    dense engine queues on lanes while the paged engine keeps more
    requests in flight on the same memory, so it must win decode
    tokens/sec; greedy decode is batch-composition independent, so the
    per-request token streams must stay bit-identical.  Each mode is
    timed best-of-``reps`` (identical work every rep — the min is the
    least-perturbed sample of the same computation, which is what a
    shared CI box needs).  A separate probe asserts the paged steady
    state performs zero device->host fetches mid-generation (continuous
    batching does not break the sync-free decode window).
    """
    import jax
    from repro.configs import get_config
    from repro.models import model_zoo as zoo
    from repro.serving.engine import ServingEngine
    from repro.serving.workload import synthetic_requests

    cfg = get_config("granite-8b").reduced()
    params = zoo.init_params(cfg, jax.random.PRNGKey(0))
    max_seq, block, dense_lanes, paged_lanes = 64, 8, 4, 8
    pool = dense_lanes * (max_seq // block)   # == dense kv memory
    n_req = 16 if quick else 40
    rng = np.random.default_rng(11)
    arrival_steps = np.cumsum(rng.exponential(1.2, n_req))

    def engine(mode):
        kw = dict(batch_size=dense_lanes) if mode == "dense" else dict(
            batch_size=paged_lanes, cache_mode="paged", block_size=block,
            kv_pool_blocks=pool)
        return ServingEngine(cfg, params, max_seq=max_seq,
                             prefill_buckets=(16,), **kw)

    def requests(seed):
        return synthetic_requests(n_req, cfg.vocab_size, seed=seed,
                                  prompt_len=(3, 15), max_new=(8, 24))

    def drive(mode, seed):
        e = engine(mode)
        reqs = requests(seed)
        i, steps = 0, 0.0
        t0 = time.perf_counter()
        while i < n_req or e.n_active or e.n_queued:
            while i < n_req and arrival_steps[i] <= steps:
                e.submit(reqs[i])
                i += 1
            e.step_many(4)
            steps += 4          # virtual clock: 4 decode steps per batch
        jax.block_until_ready(e.sample.fed)
        dt = time.perf_counter() - t0
        emitted = sum(len(r.out_tokens) for r in reqs)
        assert all(r.done for r in reqs)
        return e, {r.rid: list(r.out_tokens) for r in reqs}, emitted / dt

    for mode in ("dense", "paged"):    # warm the shared compile caches
        drive(mode, seed=99)

    reps = 2 if quick else 3
    results = {}
    for mode in ("dense", "paged"):
        best = None
        for _ in range(reps):
            e, streams, tps = drive(mode, seed=11)
            if best is None or tps > best[2]:
                best = (e, streams, tps)
        e, streams, tps = best
        occ = e.occupancy()
        results[mode] = (streams, tps, occ)
        row(f"engine_churn_{mode}", 1e6 / tps,
            f"decode_tok_per_s={tps:.0f};requests={n_req};"
            f"peak_slots={occ['max_concurrent_slots']};"
            f"peak_blocks={occ['peak_blocks_in_use']};"
            f"host_syncs={e.host_syncs}")

    # sync-free steady state: mid-generation paged decode windows must
    # perform zero device->host fetches (admission/poll cost nothing
    # while nobody completes)
    probe = engine("paged")
    for r in synthetic_requests(2, cfg.vocab_size, seed=3,
                                prompt_len=(4, 8), max_new=40):
        probe.submit(r)
    probe.step_many(4)                       # admission + first window
    syncs0 = probe.host_syncs
    for _ in range(5):
        probe.step_many(4)                   # nobody completes here
    steady_syncs = probe.host_syncs - syncs0
    probe.run_until_idle()

    (dense_streams, dense_tps, _) = results["dense"]
    (paged_streams, paged_tps, paged_occ) = results["paged"]
    identical = dense_streams == paged_streams
    speedup = paged_tps / dense_tps
    row("engine_churn_summary", 0.0,
        f"churn_speedup={speedup:.2f}x;bit_identical={identical};"
        f"paged_peak_slots={paged_occ['max_concurrent_slots']};"
        f"dense_lanes={dense_lanes};pool_blocks={pool};"
        f"steady_syncs={steady_syncs}")
    assert identical, "paged cache changed decoded tokens under churn"
    assert steady_syncs == 0, \
        f"paged steady-state decode performed {steady_syncs} host syncs"
    assert paged_occ["max_concurrent_slots"] > dense_lanes, (
        f"paged never exceeded the dense slot ceiling "
        f"({paged_occ['max_concurrent_slots']} <= {dense_lanes}) at "
        f"equal cache memory")
    assert speedup > 1.0, (
        f"paged decode only {speedup:.2f}x dense under churn "
        f"(must be strictly faster at equal cache memory)")


# ------------------------------------------------------------------ roofline
def cluster_matrix(quick: bool = False):
    """Million-request scenario matrix (ISSUE 9, arXiv:2410.10655 /
    arXiv:2510.15147 methodology).

    Behaviour shapes x {rate_aware, slo_aware} x {preemption off, on} x
    {uniform, hetero} fleets on the token-accounting ``SimEngine`` —
    40 cells of behaviour-shaped load through the REAL control plane
    (router, preemptor, autoscaler, metrics), plus one diurnal
    million-request mega-cell exercising the bounded-memory path
    (streaming metrics, digest-only journal, place_cap routing).
    Emits one consolidated BENCH_matrix.json the guard holds floors on:
    per-cell attainment, a global sim_events_per_sec, and the section
    wall clock.
    """
    from repro.cluster.cluster import ServingCluster
    from repro.cluster.control import SLOPreemption
    from repro.cluster.replica import InstanceType
    from repro.cluster.router import DeadlineAwareRouter, RateAwareRouter
    from repro.serving.shapes import make_shape

    n_cell = 60 if quick else 400
    n_mega = 20_000 if quick else 1_000_000

    # capacity model (replica.step_once): prefill chunk tokens are
    # serialized per replica at `prefill_discount/speed` virtual-seconds
    # each, while decode steps amortize across the batch lanes — so one
    # request costs (0.35*P_mean + out_mean/batch)/speed replica-seconds.
    # Workload mix (ShapedArrivals): 30% interactive (P~5.5, out~5),
    # 70% batch (P~10, out~14).
    p_mean, out_mean = 8.65, 11.3

    def fleet_rate(fleet, batch, util):
        per_req_speed_s = 0.35 * p_mean + out_mean / batch
        return util * sum(it.speed for it in fleet) / per_req_speed_s

    shapes = ["pulse_spikes", "sawtooth", "staircase", "epochs",
              "staged_plateau"]
    fleets = {
        "uniform": [InstanceType("std.1x", 4.0, spot=False)
                    for _ in range(4)],
        "hetero": ([InstanceType("fast.2x", 8.0, spot=False,
                                 cost_per_hour=2.0) for _ in range(2)]
                   + [InstanceType("slow.1x", 4.0, spot=False)
                      for _ in range(2)]),
    }
    routers = {"rate_aware": RateAwareRouter,
               "slo_aware": DeadlineAwareRouter}
    total_events, total_wall, n_cells = 0, 0.0, 0

    for fleet_name, mk_fleet in fleets.items():
        # offered mean rate = 70% of capacity, so every shape's peak
        # (1.5-3x mean) transiently overloads and its trough underloads
        # the same fleet
        rate = fleet_rate(mk_fleet, 8, 0.7)
        for shape_name in shapes:
            for router_name, router_cls in routers.items():
                for pre in (False, True):
                    cl = ServingCluster(
                        None, None, list(mk_fleet), engine="sim",
                        router=router_cls(), batch_size=8, max_seq=64,
                        decode_block=4, seed=0,
                        admission="priority" if pre else "fifo",
                        preemption=SLOPreemption() if pre else None)
                    cl.attach_arrivals(make_shape(
                        shape_name, n_cell, rate=rate, period=60.0,
                        seed=7))
                    t0 = time.perf_counter()
                    s = cl.run(max_time=200_000.0)
                    wall = time.perf_counter() - t0
                    total_events += cl.loop.dispatched
                    total_wall += wall
                    n_cells += 1
                    att = s.get("attainment_interactive", 1.0)
                    row(f"matrix_{shape_name}_{router_name}_"
                        f"{'pre' if pre else 'nopre'}_{fleet_name}",
                        wall * 1e6 / max(s["completed"], 1),
                        f"attainment={att:.3f};"
                        f"p99={s['p99_latency']:.2f};"
                        f"tok_per_s={s['tok_per_s']:.2f};"
                        f"dollar={s['fleet_dollar_cost']:.4f};"
                        f"completed={s['completed']}")

    # ---- the 10^6-request diurnal mega-cell: bounded-memory path ----
    mega_fleet = [InstanceType("std.2x", 8.0, spot=False)
                  for _ in range(8)]
    rate = fleet_rate(mega_fleet, 64, 0.6)  # peak 1.6x -> ~0.96 capacity
    day = n_mega / rate                     # the trace spans ~one "day"
    cl = ServingCluster(
        None, None, mega_fleet, engine="sim",
        router=RateAwareRouter(place_cap=128),
        batch_size=64, max_seq=64, decode_block=8, seed=0,
        journal=False, retain_traces=False, timeline_cap=10_000,
        dispatch_coalesce=0.25)
    cl.attach_arrivals(make_shape("diurnal", n_mega, rate=rate,
                                  period=day, seed=11))
    t0 = time.perf_counter()
    s = cl.run(max_time=day * 20.0)
    wall = time.perf_counter() - t0
    total_events += cl.loop.dispatched
    total_wall += wall
    n_cells += 1
    assert s["completed"] == n_mega, \
        f"mega cell dropped work: {s['completed']}/{n_mega}"
    att = s.get("attainment_interactive", 1.0)
    row("matrix_diurnal_mega", wall * 1e6 / max(s["completed"], 1),
        f"attainment={att:.3f};p99={s['p99_latency']:.2f};"
        f"tok_per_s={s['tok_per_s']:.2f};"
        f"dollar={s['fleet_dollar_cost']:.4f};"
        f"completed={s['completed']};"
        f"events={cl.loop.dispatched};"
        f"cell_events_per_sec={cl.loop.dispatched / max(wall, 1e-9):.0f}")

    row("matrix_total", total_wall * 1e6 / max(total_events, 1),
        f"sim_events_per_sec={total_events / max(total_wall, 1e-9):.0f};"
        f"events={total_events};wall_s={total_wall:.1f};"
        f"cells={n_cells}")


def roofline():
    from repro.launch.roofline import load_table
    try:
        rows = load_table()
    except Exception as e:
        row("roofline_missing", 0.0, str(e))
        return
    for r in rows:
        if "skipped" in r or "error" in r:
            continue
        bound = max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])
        row(f"roofline_{r['arch']}_{r['shape']}", bound * 1e6,
            f"dominant={r['dominant']};frac={r['roofline_fraction']:.3f};"
            f"useful={r['useful_ratio']:.2f}")


SECTIONS = [fig2_overdecomp, fig3_loadbalance, fig5_interrupt_cpu,
            fig6_interrupt_dev, fig7_modes, fig8_endtoend, kernels,
            cluster_hetero, cluster_slo, cluster_preempt,
            cluster_vertical, cluster_spot_market, cluster_chaos,
            cluster_matrix,
            engine_throughput, engine_churn, roofline]

# sections whose --json artifact keeps a historical filename
_JSON_NAME = {"cluster_matrix": "BENCH_matrix.json"}


def main() -> None:
    import inspect

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("sections", nargs="*",
                    help="section names to run (default: all)")
    ap.add_argument("--scenario", action="append", default=[],
                    help="alias for a positional section name")
    ap.add_argument("--arrival", default="batch",
                    help="offered-load model for cluster scenarios: "
                         "batch | poisson:<rate> | trace:<file>")
    ap.add_argument("--quick", action="store_true",
                    help="reduced problem sizes (CI smoke)")
    ap.add_argument("--json", action="store_true",
                    help="persist each section's rows to "
                         "BENCH_<section>.json at the repo root")
    args = ap.parse_args()
    names = list(args.sections) + list(args.scenario)
    known = {fn.__name__ for fn in SECTIONS}
    unknown = set(names) - known
    if unknown:
        ap.error(f"unknown section(s): {sorted(unknown)}; "
                 f"choose from {sorted(known)}")
    opts = {"arrival": args.arrival, "quick": args.quick}
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    for fn in SECTIONS:
        if names and fn.__name__ not in names:
            continue
        accepted = inspect.signature(fn).parameters
        t0 = time.perf_counter()
        _ROWS.clear()
        fn(**{k: v for k, v in opts.items() if k in accepted})
        elapsed = time.perf_counter() - t0
        print(f"# section {fn.__name__} took {elapsed:.1f}s", flush=True)
        if args.json:
            path = os.path.join(_REPO_ROOT, _JSON_NAME.get(
                fn.__name__, f"BENCH_{fn.__name__}.json"))
            with open(path, "w") as fh:
                json.dump({"scenario": fn.__name__,
                           "quick": args.quick,
                           "section_seconds": round(elapsed, 1),
                           "unit": "us_per_call",
                           "rows": list(_ROWS)}, fh, indent=1)
                fh.write("\n")
            print(f"# wrote {path}", flush=True)


if __name__ == "__main__":
    main()
