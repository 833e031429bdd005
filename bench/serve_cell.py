"""Serving cells: a paged ``ServingEngine`` driven through submit/step_many.

The harness is the engine's client and its streaming front end.  It
queues the whole backlog at the start (an offline mix) and calls
``step_many(decode_block)``.  After each call it copies the device's
per-lane progress (``fed``) and hands the copy to a reader thread, which
fetches it as soon as the device has finished that window and checks it
against the host's projection: that is when the window's tokens reach
the client.  The main loop waits for window k-1 to be read only after
window k is queued, so at most two windows are in flight and the reads
cost the device no idle time.

Every program the window can reach is compiled during set-up: the
prefill chunk programs that the mix's prompt lengths need (by the
engine's own chunk choice) and the one decode window.  Compile events
inside the window are counted and printed.

After the window: the peak device memory is read, in traced runs the
(module, instruction) -> model scope map of the engine's programs is
taken (over the scopes that the model module declares), a seeded sample
of finished requests (the longest answer among them, and others from as
many different lanes) is kept on the host, the engine is freed, and the
float32 reference judges every served token.

Everything about the model comes from the module ``bench/models/<model>.py``
that the configuration file's ``"model"`` key names (``core.load_model``):
its sizes, its seeded weights, its reference, its counts and the names
of its scopes.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import queue
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

import core
import trace_reduce
import traffic

LIMITS_DIR = Path(__file__).resolve().parent / "limits"
span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Rec:
    """What the host saw of one request."""
    prompt_len: int
    max_new: int
    admit_window: Optional[int] = None
    lane: Optional[int] = None


@dataclasses.dataclass
class Window:
    """One ``step_many`` call: what the host projection says it did."""
    k: int
    steps: int
    emitted: int
    counts: Dict[int, int]            # rid -> tokens generated so far
    completed: List[int]
    admitted: List[int]
    advance: Dict[int, tuple]         # rid -> (fed before, fed after)
    expect_fed: Dict[int, int]        # lane -> fed the device must hold
    lanes: Dict[int, int]             # rid -> lane, for rids on a lane
    mismatches: int = 0


class Stream:
    """Reads each window's progress copy as soon as the device has it."""

    def __init__(self):
        self._q: "queue.Queue" = queue.Queue()
        self._cv = threading.Condition()
        self.done = -1
        self.reads = 0
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            win, probe = item
            fed = np.asarray(probe)
            win.mismatches = sum(int(fed[s]) != v
                                 for s, v in win.expect_fed.items())
            with self._cv:
                self.reads += 1
                self.done = win.k
                self._cv.notify_all()

    def push(self, win, probe):
        self._q.put((win, probe))

    def wait(self, k: int, timeout: float = 600.0):
        with self._cv:
            if not self._cv.wait_for(lambda: self.done >= k, timeout):
                raise RuntimeError(f"decode window {k} never finished")

    def close(self):
        self._q.put(None)
        self._t.join()


_PROBE = jax.jit(lambda fed: fed + 0)


def _chunk_plan(eng, prompt_len: int):
    """(bucket, first) of each prefill chunk the engine issues for a
    prompt of this length, by the engine's own chunk choice."""
    plan, off, rem = [], 0, prompt_len - 1
    while rem > 0:
        c, n = eng._pick_chunk(rem, room=eng.max_seq - off)
        if not c:
            break
        plan.append((c, off == 0))
        off, rem = off + n, rem - n
    return tuple(plan)


# the plan of a prompt that gets no chunk at all (a recurrent model's
# prompt shorter than its smallest bucket): the engine then writes the
# lane's cache length eagerly, which compiles programs of its own
NO_CHUNK = (0, True)


def _warm_prompts(eng, mix: dict) -> List[int]:
    """One prompt length for each prefill program the mix can reach."""
    need, reps = set(), []
    for p in traffic.warm_lengths(mix):
        sig = set(_chunk_plan(eng, p) or (NO_CHUNK,)) - need
        if sig:
            need |= sig
            reps.append(p)
    return reps


def _drive_until_idle(eng, k: int, limit: int = 10_000):
    for _ in range(limit):
        if not (eng.n_active or eng.n_queued):
            return
        eng.step_many(k)
    raise RuntimeError("warm-up did not drain")


def _record(eng, k: int, stats, prev_fed, recs):
    counts, adv, expect, lanes = {}, {}, {}, {}
    admitted = []
    for slot, r in eng.slot_requests():
        fed = eng.fed_tokens(slot)
        counts[r.rid] = max(0, fed - len(r.prompt) + 1)
        expect[slot] = fed
        adv[r.rid] = fed
        lanes[r.rid] = slot
    completed = []
    for r in eng.pop_completed():
        counts[r.rid] = len(r.out_tokens)
        adv[r.rid] = len(r.prompt) + len(r.out_tokens) - 1
        completed.append(r.rid)
    for rid in list(adv):
        if rid not in prev_fed:
            admitted.append(rid)
            before = recs[rid].prompt_len - 1
        else:
            before = prev_fed[rid]
        adv[rid] = (before, adv[rid])
        prev_fed[rid] = adv[rid][1]
    for rid in completed:
        prev_fed.pop(rid, None)
    return Window(k, stats["steps"], stats["emitted"], counts,
                  completed, admitted, adv, expect, lanes)


@dataclasses.dataclass
class RunData:
    """Everything a metric reader may read."""
    dims: object                  # the sizes, as ``model.dims`` gives them
    peak: dict
    lanes: int
    seconds: float
    wall_s: float
    windows: List[Window]
    recs: Dict[int, Rec]
    trace: object = None          # trace_reduce.TraceSummary
    model: object = None          # the module of bench/models/ dims are of
    # (module, instruction) -> model scope of the engine's programs
    scopes: Optional[Dict[tuple, str]] = None


def _limits(cell_name: str) -> dict:
    return json.loads((LIMITS_DIR / f"{cell_name}.json").read_text())


def _lanes(reqs, recs) -> int:
    """Distinct known lanes that served ``reqs``."""
    return len({recs[r.rid].lane for r in reqs} - {None})


def _sample(done: List, recs: Dict[int, Rec], seed: int, check: dict):
    """Seeded sample of finished requests: the longest answer, then one
    request from each lane not yet sampled, in a seeded order, until
    ``min_lanes`` lanes are covered and ``served_tokens`` served tokens,
    or ``max_requests`` are taken."""
    if not done:
        return []
    rng = np.random.default_rng(int(seed) % (1 << 63) ^ 0x5EED)
    longest = max(done, key=lambda r: (len(r.out_tokens), -r.rid))
    rest = [r for r in done if r is not longest]
    rest = [rest[i] for i in rng.permutation(len(rest))]
    # requests on lanes not yet sampled first, the rest after them
    out, lanes = [longest], {recs[longest.rid].lane}
    fresh = []
    for r in rest:
        lane = recs[r.rid].lane
        if lane is not None and lane not in lanes:
            lanes.add(lane)
            fresh.append(r)
    rest = fresh + [r for r in rest if r not in fresh]
    served = len(longest.out_tokens)
    for r in rest:
        if len(out) >= check["max_requests"] or (
                served >= check["served_tokens"]
                and _lanes(out, recs) >= check["min_lanes"]):
            break
        out.append(r)
        served += len(r.out_tokens)
    return [(r.rid, np.asarray(r.prompt).copy(), list(r.out_tokens))
            for r in out]


def run(cell: dict, cfg_file: dict, model, seed: int, seconds: float,
        t_start: float, counter, *, tracer=None, mix=None, limits=None,
        peak=None, control=False):
    """One run of a serving cell of the configuration ``cfg_file``, whose
    model module (``core.load_model``) is ``model``; returns (result,
    compared, RunData)."""
    from repro.serving.engine import Request, ServingEngine
    dep = cfg_file["deployment"]
    mix = mix or traffic.load_mix(cell["traffic"])
    cfg = model.program_config(cfg_file)
    dims = model.dims(cfg_file)
    peak = peak or core.peaks(jax.devices()[0].device_kind)

    params = jax.block_until_ready(model.program_params(dims, seed))
    eng = ServingEngine(cfg, params, batch_size=dep["lanes"],
                        max_seq=dep["max_seq"], cache_mode="paged",
                        block_size=dep["block_size"],
                        kv_pool_blocks=dep["kv_pool_blocks"])
    k_steps = eng.decode_block
    items = traffic.generate(mix, seed, dims.vocab, dep["max_seq"])

    # warm every program the window can reach, with throw-away prompts
    wrng = np.random.default_rng(1)
    warm = _warm_prompts(eng, mix)
    for i, p in enumerate(warm):
        eng.submit(Request(-1 - i, wrng.integers(0, dims.vocab, p,
                                                 dtype=np.int32), 2))
    _drive_until_idle(eng, k_steps)
    eng.pop_completed()
    jax.block_until_ready(_PROBE(eng.sample.fed))
    stream = Stream()
    jax.block_until_ready((eng.state, eng.sample))
    core.log("setup", lanes=dep["lanes"], max_seq=dep["max_seq"],
             block_size=dep["block_size"], pool_blocks=dep["kv_pool_blocks"],
             decode_block=k_steps, warm_prompt_lengths=warm,
             warm_prefill_programs=sorted(
                 {x for p in warm for x in _chunk_plan(eng, p)}),
             **traffic.describe(mix, traffic.request_count(mix)))

    reqs = {it.rid: Request(it.rid, it.prompt, it.max_new) for it in items}
    recs = {it.rid: Rec(len(it.prompt), it.max_new) for it in items}
    windows: List[Window] = []
    prev_fed: Dict[int, int] = {}
    # what set-up allocated is never collected again: a collection inside
    # the window then scans only what the window allocates
    gc.collect()
    gc.freeze()
    if tracer is not None:
        tracer.start()
    setup_s = time.perf_counter() - t_start
    counter.armed = True

    t0 = time.perf_counter()
    for it in items:
        eng.submit(reqs[it.rid])
    with span("bench.window"):
        while time.perf_counter() - t0 < seconds and (
                eng.n_active or eng.n_queued):
            with span("bench.step_many"):
                stats = eng.step_many(k_steps)
            win = _record(eng, len(windows), stats, prev_fed, recs)
            windows.append(win)
            stream.push(win, _PROBE(eng.sample.fed))
            with span("bench.window_wait"):
                stream.wait(win.k - 1)
        stream.wait(len(windows) - 1)
        jax.block_until_ready((eng.state, eng.sample))
        wall_s = time.perf_counter() - t0
    counter.armed = False
    if tracer is not None:
        tracer.stop()
    stream.close()
    gc.unfreeze()

    for w in windows:
        for rid in w.admitted:
            recs[rid].admit_window = w.k
        for rid, lane in w.lanes.items():
            recs[rid].lane = lane

    device = core.device_info(int(cell["chips"]))
    done = [r for r in reqs.values() if r.done]
    emitted = sum(w.emitted for w in windows)
    lanes_used = [len(w.counts) for w in windows]
    core.log("window", seconds=seconds, wall_s=wall_s, windows=len(windows),
             window_compile_events=counter.events,
             window_compile_s=counter.seconds, stream_reads=stream.reads,
             generated_tokens=emitted, lanes=dep["lanes"],
             mean_lanes_busy=float(np.mean(lanes_used)) if lanes_used else 0,
             completed=len(done),
             peak_bytes_in_use=device["memory_peak_bytes"])

    scopes = None
    if tracer is not None:
        # lowered through jit's cache: the programs the window ran
        t = time.perf_counter()
        scopes = trace_reduce.scope_map(eng.program_hlo(), model.SCOPES)
        core.log("scopes", seconds=time.perf_counter() - t,
                 instructions=len(scopes))
    sample = _sample(done, recs, seed, mix["check"])
    del eng, params, reqs, done
    gc.collect()

    t_ref = time.perf_counter()
    gaps = [model.served_gap(dims, seed, p, out) for _, p, out in sample]
    gap = max(gaps, default=float("inf"))
    ctl = None
    if control:
        ctl = max(model.served_gap(dims, seed, p, out, control=True)
                  for _, p, out in sample)
        core.log("control", program_gaps=gaps, control_max=ctl)
    limits = limits or _limits(cell["name"])
    mismatch = sum(w.mismatches for w in windows)
    lanes_sampled = len({recs[rid].lane for rid, _, _ in sample} - {None})
    compared = {"max_logit_gap": {
        "value": gap, "limit": limits["max_logit_gap"],
        "rule": f"widest gap below the float32 reference's best, over "
                f"{sum(len(o) for _, _, o in sample)} served tokens of "
                f"{len(sample)} requests on {lanes_sampled} lanes"},
        "stream_mismatches": {
            "value": mismatch, "limit": 0,
            "rule": "lanes whose device progress differs from the host "
                    "projection, over all windows"}}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    core.log("reference", seconds=time.perf_counter() - t_ref,
             sampled=[rid for rid, _, _ in sample], lanes=lanes_sampled)

    data = RunData(dims, peak, dep["lanes"], seconds, wall_s, windows, recs,
                   model=model, scopes=scopes)
    result = {"correct": bool(correct),
              "attempted": sum(r.admit_window is not None
                               for r in recs.values()),
              "failed": 0, "setup_s": setup_s, "device": device}
    if control:
        result["control"] = {"max_logit_gap": ctl,
                             "correct": ctl <= limits["max_logit_gap"]}
    return result, compared, data


# ------------------------------------------------ end-to-end metrics
def out_tok_s(data: RunData) -> float:
    return sum(w.emitted for w in data.windows) / data.wall_s
