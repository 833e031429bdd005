"""Bring the main path up on a TPU and check what it computes.

    python chip_smoke.py              # one v5e chip (the default)
    python chip_smoke.py --four-chip  # four chips, elastic training only

One chip: granite-8b at its published widths with bf16 params, cut in
depth to whole layers that fit 16 GB, served with greedy decoding by a
dense and then a paged ``ServingEngine`` (the paged one must run the
Pallas kernel), a float32 logit check of both engines' first decode step,
and a two-replica ``ServingCluster`` drained through one spot
interruption whose tokens must match an uninterrupted run.

Four chips (``--four-chip``, and nothing else): ``ElasticTrainer`` at
granite-8b widths, cut in depth and batch, trains on 4 chips, shrinks to
2, trains, expands to 4 and trains; its losses must match an
uninterrupted 4-chip trainer.

Everything runs in this one process: a chip belongs to one process at a
time.  On any platform but ``tpu`` the script exits non-zero before any
phase runs.  A failed check raises.  Per-phase records (compile seconds,
peak device bytes, tokens, each check against its tolerance) go to
earlier lines; the last line is the JSON result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "granite-8b"
SEED = 0

# one chip: granite-8b's 36 layers are ~15.7 GB of bf16 weights; 20 whole
# layers are ~9.5 GB and leave the rest of the 16 GB for KV and activations
SERVE_LAYERS = 20
BLOCK_SIZE = 16
DECODE_BLOCK = 8


@dataclasses.dataclass(frozen=True)
class Sizes:
    lanes: int = 8
    max_seq: int = 1024
    n_requests: int = 12
    # every prompt needs the 256-token prefill bucket and no other, so
    # each engine compiles one prefill program
    prompt_len: tuple = (200, 257)
    max_new: int = 32


# first-step logits, bf16 engine vs float32 forward: relative L2 error
LOGIT_RTOL = 5e-2

# four chips: 2 layers of f32 params + AdamW moments (~10 GB) and grads
# must fit the two chips left after the shrink (the model axis halves them)
TRAIN_LAYERS = 2
TRAIN_BATCH = 8
TRAIN_SEQ = 4096
MODEL_PAR = 2
STEPS = 2                 # per leg: 4 chips, 2 chips, 4 chips
LOSS_ATOL = 5e-4          # tests/test_multidevice.py's elastic tolerance

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching a
    compiled program from the persistent cache)."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration

    def lap(self) -> float:
        out, self.seconds = self.seconds, 0.0
        return out


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def report(phase: str, **fields):
    print(f"[{phase}] {json.dumps(fields, default=float)}", flush=True)


def check(name: str, ok: bool, **numbers):
    report("check", name=name, ok=bool(ok), **numbers)
    if not ok:
        raise AssertionError(f"{name} failed: {numbers}")


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# ------------------------------------------------------------ one chip
def serve_config(layers: int = SERVE_LAYERS):
    from repro.configs import get_config
    return get_config(ARCH).with_(num_layers=layers, param_dtype="bfloat16")


def make_requests(vocab: int, sizes: Sizes):
    from repro.serving.workload import synthetic_requests
    return synthetic_requests(sizes.n_requests, vocab, seed=SEED,
                              prompt_len=sizes.prompt_len,
                              max_new=sizes.max_new)


def first_step_logits(eng, req):
    """Logits of ``eng``'s first decode step for ``req``: the request is
    admitted (prompt bulk-prefilled into the engine's own cache), then
    the engine's serve step runs once on that state without advancing
    the engine, which goes on to serve the request normally."""
    from repro.models import model_zoo as zoo
    eng.submit(req)
    eng._admit()
    (slot,) = [s for s, r in eng.slot_requests() if r is req]
    if eng.cache_mode == "paged":
        step = zoo.make_paged_serve_step(eng.cfg, eng.shape, eng.block_size,
                                         eng.pool_blocks)
    else:
        step = zoo.make_serve_step(eng.cfg, eng.shape)
    logits, _ = jax.jit(step)(eng.params, eng.state,
                              {"tokens": eng.sample.next_tok,
                               "active": eng.sample.active})
    return np.asarray(logits[slot, -1], np.float32)


def reference_logits(cfg, params, prompt):
    """float32 forward of ``prompt`` at full matmul precision; returns the
    last position's logits (what the first decode step samples from).
    Weights stay bf16 in memory and are widened one layer at a time."""
    from repro.configs.base import ShapeConfig
    from repro.models import model_zoo as zoo
    cfg32 = cfg.with_(compute_dtype="float32")
    shape = ShapeConfig("reference", len(prompt), 1, "prefill")
    fwd = jax.jit(zoo.make_prefill(cfg32, shape))
    with jax.default_matmul_precision("highest"):
        logits, _ = fwd(params, {"tokens": jnp.asarray(prompt)[None]})
    return np.asarray(logits[0, -1], np.float32)


def paged_loop_text(eng) -> str:
    """HLO of the compiled fused decode window the paged engine runs."""
    from repro.serving import engine as engine_mod
    loop = engine_mod._shared_paged_loop(
        eng.cfg, eng.shape, eng.decode_block, eng.temperature,
        eng.eos_token, eng.block_size, eng.pool_blocks)
    return loop.lower(eng.params, eng.state, eng.sample,
                      eng._prompt_buf).compile().as_text()


def serve_engine(cfg, params, mode: str, sizes: Sizes, clock: CompileClock):
    """Serve the seeded requests on one engine; returns (first-step
    logits of request 0, tokens per rid, HLO of the decode window)."""
    from repro.serving.engine import ServingEngine
    reqs = make_requests(cfg.vocab_size, sizes)
    eng = ServingEngine(cfg, params, batch_size=sizes.lanes,
                        max_seq=sizes.max_seq, cache_mode=mode,
                        block_size=BLOCK_SIZE, decode_block=DECODE_BLOCK)
    clock.lap()
    t0 = time.perf_counter()
    logits = first_step_logits(eng, reqs[0])
    for r in reqs[1:]:
        eng.submit(r)
    stats = eng.run_until_idle()
    wall = time.perf_counter() - t0
    compile_s = clock.lap()
    hlo = paged_loop_text(eng) if mode == "paged" else ""
    report(f"engine_{mode}", requests=len(reqs), lanes=sizes.lanes,
           max_seq=sizes.max_seq, block_size=eng.block_size,
           prefill_chunks=eng.chunk_prefills,
           tokens_served=sum(len(r.out_tokens) for r in reqs),
           decode_tokens=stats["tokens"], wall_s=wall, compile_s=compile_s,
           peak_bytes_in_use=peak_bytes(),
           note="wall_s includes compile_s; a bring-up run, not a benchmark")
    check(f"engine_{mode}_served_every_request",
          all(r.done and len(r.out_tokens) == r.max_new_tokens
              for r in reqs),
          done=sum(r.done for r in reqs), requests=len(reqs))
    return logits, {r.rid: list(r.out_tokens) for r in reqs}, hlo


def run_cluster(cfg, params, sizes: Sizes, *, interrupt: bool):
    from repro.cluster import InstanceType, RateAwareRouter, ServingCluster
    fleet = [InstanceType("std.1x", 1.0), InstanceType("std.1x", 1.0)]
    reqs = make_requests(cfg.vocab_size, sizes)
    # one replica step is DECODE_BLOCK virtual seconds: the notice lands
    # two windows in, while every first-wave request is mid-decode
    cl = ServingCluster(cfg, params, fleet, router=RateAwareRouter(),
                        batch_size=sizes.lanes, max_seq=sizes.max_seq,
                        decode_block=DECODE_BLOCK, dt=1.0,
                        rebalance_lead=DECODE_BLOCK,
                        notice_deadline=DECODE_BLOCK)
    for r in reqs:
        cl.submit(r, at=0.0)
    if interrupt:
        cl.inject_interruption(t=DECODE_BLOCK, replica_rid=0)
    return cl.run(max_time=100_000), reqs


def cluster_phase(cfg, params, sizes: Sizes, clock: CompileClock):
    clock.lap()
    t0 = time.perf_counter()
    _, base_reqs = run_cluster(cfg, params, sizes, interrupt=False)
    out, reqs = run_cluster(cfg, params, sizes, interrupt=True)
    wall = time.perf_counter() - t0
    same = sum(a.out_tokens == b.out_tokens
               for a, b in zip(base_reqs, reqs))
    report("cluster", replicas=2, requests=len(reqs),
           completed=out["completed"], dropped=out["dropped"],
           drains=out["drains"], migrated_slots=out["migrated_slots"],
           tokens_served=sum(len(r.out_tokens) for r in reqs),
           identical_streams=same, wall_s=wall, compile_s=clock.lap(),
           peak_bytes_in_use=peak_bytes())
    check("cluster_drained_without_loss",
          out["dropped"] == 0 and out["completed"] == len(reqs)
          and out["drains"] == 1 and out["migrated_slots"] > 0,
          dropped=out["dropped"], completed=out["completed"],
          drains=out["drains"], migrated_slots=out["migrated_slots"])
    check("cluster_tokens_identical_to_uninterrupted",
          same == len(reqs), identical=same, requests=len(reqs))


def one_chip(clock: CompileClock, cfg=None, sizes: Sizes = Sizes()):
    from repro.models import model_zoo as zoo
    cfg = cfg or serve_config()
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        zoo.init_params(cfg, jax.random.PRNGKey(SEED)))
    report("model", arch=ARCH, layers=cfg.num_layers, d_model=cfg.d_model,
           heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
           head_dim=cfg.head_dim, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
           param_dtype=cfg.param_dtype, params=zoo.num_params(cfg),
           param_bytes=sum(x.nbytes for x in jax.tree.leaves(params)),
           init_s=time.perf_counter() - t0, compile_s=clock.lap(),
           cut=f"depth 36 -> {cfg.num_layers} whole layers to fit one "
               f"16 GB chip; widths as published")

    dense_logits, dense_tokens, _ = serve_engine(cfg, params, "dense",
                                                 sizes, clock)
    paged_logits, paged_tokens, hlo = serve_engine(cfg, params, "paged",
                                                   sizes, clock)
    check("paged_decode_loop_runs_pallas_kernel", "tpu_custom_call" in hlo)
    report("engines", requests=len(dense_tokens), identical_streams=sum(
        dense_tokens[k] == paged_tokens[k] for k in dense_tokens),
        note="reported, not required: on the chip the paged kernel and "
             "the dense attention core round differently")

    prompt = make_requests(cfg.vocab_size, sizes)[0].prompt
    ref = reference_logits(cfg, params, prompt)
    errs = {"dense_vs_f32": rel_l2(dense_logits, ref),
            "paged_vs_f32": rel_l2(paged_logits, ref),
            "paged_vs_dense": rel_l2(paged_logits, dense_logits)}
    report("logits", prompt_len=len(prompt), rtol=LOGIT_RTOL,
           argmax_dense=int(np.argmax(dense_logits)),
           argmax_paged=int(np.argmax(paged_logits)),
           argmax_f32=int(np.argmax(ref)), compile_s=clock.lap(),
           peak_bytes_in_use=peak_bytes(), **errs)
    check("first_step_logits_vs_float32",
          np.isfinite(dense_logits).all() and np.isfinite(paged_logits).all()
          and max(errs.values()) <= LOGIT_RTOL, rtol=LOGIT_RTOL, **errs)

    cluster_phase(cfg, params, sizes, clock)


# ---------------------------------------------------------- four chips
def train_config(layers: int = TRAIN_LAYERS):
    from repro.configs import get_config
    return get_config(ARCH).with_(num_layers=layers)


def elastic_phase(cfg, shape, clock: CompileClock, *, n: int = 4,
                  model_par: int = MODEL_PAR):
    from repro.launch.train import ElasticTrainer
    clock.lap()
    base = ElasticTrainer(cfg, shape, n_devices=n, model_par=model_par,
                          seed=SEED)
    base.train(3 * STEPS, log_every=0)
    la = [m["loss"] for m in base.metrics_log]
    del base
    t0 = time.perf_counter()
    tr = ElasticTrainer(cfg, shape, n_devices=n, model_par=model_par,
                        seed=SEED)
    tr.train(STEPS, log_every=0)
    for devices in (n // 2, n):
        ev = tr.rescale(devices)
        report("rescale", kind=ev.kind, from_devices=ev.from_devices,
               to_devices=ev.to_devices, total_s=ev.total,
               **{f"{k}_s": v for k, v in ev.stages.items()})
        tr.train(STEPS, log_every=0)
    lb = [m["loss"] for m in tr.metrics_log]
    diffs = [abs(x - y) for x, y in zip(la, lb)]
    report("elastic", layers=cfg.num_layers, batch=shape.global_batch,
           seq=shape.seq_len, model_par=model_par,
           uninterrupted=la, rescaled=lb, max_abs_diff=max(diffs),
           atol=LOSS_ATOL, wall_s=time.perf_counter() - t0,
           compile_s=clock.lap(), peak_bytes_in_use=peak_bytes(),
           cut=f"depth 36 -> {cfg.num_layers}, batch 256 -> "
               f"{shape.global_batch} (train_4k), f32 params + AdamW")
    check("elastic_losses_match_uninterrupted",
          len(la) == len(lb) == 3 * STEPS and all(np.isfinite(la))
          and max(diffs) <= LOSS_ATOL,
          max_abs_diff=max(diffs), atol=LOSS_ATOL)


def four_chips(clock: CompileClock):
    from repro.configs.base import ShapeConfig
    if len(jax.devices()) != 4:
        raise SystemExit(f"--four-chip needs 4 chips, found "
                         f"{len(jax.devices())}")
    elastic_phase(train_config(),
                  ShapeConfig("train_4k", TRAIN_SEQ, TRAIN_BATCH, "train"),
                  clock)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip elastic-training phase")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    report("setup", device_kind=dev.device_kind,
           devices=len(jax.devices()), jax=jax.__version__,
           compile_cache=enable_compile_cache())
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chip:
        four_chips(clock)
    else:
        one_chip(clock)
    report("done", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
