"""Serving engine: continuous-batching decode over the model zoo.

Small but real: request queue, slot-based batching (a fixed decode batch of
``batch_size`` slots; finished sequences release their slot to the next
request), chunked bulk prefill, greedy or temperature sampling.  The decode
step is the same ``serve_step`` the dry run lowers at 32k/500k scale.

The hot path is built around three properties:

* **Chunked bulk prefill** — a request is admitted by running
  ``make_prefill`` over a fixed padded chunk bucket (one jitted function
  per bucket size, bounding recompiles) and scattering the resulting
  cache columns into the slot, instead of streaming one prompt token per
  decode step.  A P-token prompt costs one prefill dispatch (plus a
  streamed tail for prompts longer than the largest bucket) rather than
  P full-batch decode dispatches.  Under greedy decoding the bulk path
  is bit-identical to the streamed baseline (``prefill_mode="streamed"``),
  asserted in tests; with ``temperature > 0`` the two modes consume
  different numbers of rng splits (streaming burns one per prompt token)
  so their samples differ.
* **Sync-free batched decode** — ``step_many(k)`` runs k fused
  sample-and-advance steps (``make_decode_loop``) in ONE dispatch with a
  donated device-resident ``SampleState``: next-token feedback, the
  active mask, per-slot progress and the generated-token buffer all stay
  on device.  The host tracks progress with an *exact* projection (each
  active slot advances one token per step until its precomputed
  ``maxfed``), so steady-state decode performs **zero device->host
  transfers**; ``out_buf`` is fetched only when the projection says a
  slot completed, or at a drain.  ``host_syncs`` counts every fetch.
* **Paged KV cache** (``cache_mode="paged"``) — kv leaves live in ONE
  device-resident block pool (``kv_pool_blocks`` x ``block_size``
  columns) instead of dense per-lane ``max_seq`` strips; each slot
  addresses its logical positions through a per-lane block table
  (vLLM-style paging, served by the Pallas kernel in
  ``kernels/paged_attention``).  A ``BlockAllocator`` reserves a slot's
  whole block budget at admission and frees it at retire/pack, so the
  fused decode window never needs a mid-flight allocation — steady-state
  decode stays zero-sync.  Admission is capacity-gated on free blocks
  (not just free lanes): with short requests the same pool memory
  sustains more concurrent slots than ``batch_size`` dense lanes, and
  prompts longer than the largest prefill bucket are fed by *multiple*
  state-continued chunk prefills (block-table appends), subsuming
  prefill-with-history.  Token streams are bit-identical to the dense
  engine — paged and dense loops share the exact sampling body and the
  attention cores agree bit-for-bit (asserted in tests).
* **Observable** — the host work of each window runs under
  ``jax.profiler.TraceAnnotation`` spans (``serve.admit``,
  ``serve.prefill``, ``serve.decode_dispatch``, ``serve.poll`` and
  ``serve.host_sync``), one per admission or window and never per
  token: in a profiler trace they sit on the host plane, on the clock
  of the device's runs, so a device-idle gap can be named after what
  the host was doing.  With no trace active they cost next to nothing.
  ``program_hlo()`` returns the optimized HLO of every program the
  engine has dispatched, whose ``op_name`` metadata carries the
  model's named scopes (``models/``).
* **Migratable work units** — ``pack()`` captures each occupied slot
  (request progress + that slot's KV/state cache columns, as host
  arrays) into a self-contained ``WorkUnit``; ``unpack()`` admits units
  into any engine built from the same ``(cfg, max_seq)`` — including
  mid-prefill-chunk.  ``preempt()``/``resume()`` are the same checkpoint
  under pause semantics (slot freed, snapshot retained, bit-identical
  stream on resume).  This one PUP-style verb set is the substrate for
  every control-plane move: spot-drain, mid-stream rebalancing, and
  SLO-aware preemption (paper §III–IV applied to serving).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import model_zoo as zoo

span = jax.profiler.TraceAnnotation

# Padded prompt-chunk sizes for bulk prefill.  Ascending; buckets larger
# than the engine's cache are dropped at construction.  One compiled
# prefill per surviving bucket per (cfg, engine shape).
DEFAULT_PREFILL_BUCKETS: Tuple[int, ...] = (16, 64, 256)

# Relative cost of one bulk-prefilled prompt token vs one decode step.
# Bulk prefill amortizes weight reads over the whole chunk, so a prefill
# token is far cheaper than a decode token; the router and the cluster's
# virtual-time accounting both use this factor.
DEFAULT_PREFILL_DISCOUNT = 0.35


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (len,) int32
    max_new_tokens: int = 16
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # SLO metadata (an ``repro.serving.workload.SLOClass``; None = the
    # cluster's default class) + the model pool this request must run on.
    slo: Optional[Any] = None
    model_id: str = "default"
    arrival_t: Optional[float] = None   # stamped by the cluster's arrival

    @property
    def total_tokens(self) -> int:
        """Token-units of work: prompt + planned new tokens (LB load)."""
        return len(self.prompt) + self.max_new_tokens

    def deadline_t(self, default: float = float("inf")) -> float:
        """Absolute completion deadline (inf when class-less/unarrived)."""
        if self.slo is None or self.arrival_t is None:
            return default
        return self.arrival_t + self.slo.deadline


def request_cost(req: Request,
                 discount: float = DEFAULT_PREFILL_DISCOUNT) -> float:
    """Router load of an unstarted request, with prefill discounted.

    Prompt tokens are bulk-prefilled (cheap); only the decode tokens cost
    a full step each.  The last prompt token doubles as the first decode
    feed, so ``len(prompt) - 1`` tokens ride the discounted prefill path.
    """
    return max(len(req.prompt) - 1, 0) * discount + req.max_new_tokens


@dataclasses.dataclass
class SlotSnapshot:
    """A checkpointed in-flight request: enough to resume decode anywhere.

    ``cache`` holds the slot's columns in ONE canonical layout — full
    contiguous ``max_seq`` sequence axes — whatever cache mode produced
    it: a paged engine gathers the slot's blocks through its table into
    the contiguous column on ``pack`` and re-blocks into its own
    geometry on ``unpack``.  Snapshots therefore migrate between dense
    and paged engines, and between paged engines with *different block
    sizes*, bit-identically (asserted in tests/test_paged.py).
    """
    request: Request
    fed: int                    # prompt+generated tokens already in cache
    next_tok: int               # next token to feed
    cache_len: int
    cache: Dict[str, np.ndarray]  # this slot's cache columns (host)
    # sampler rng at checkpoint time (host copy) — stamped by the
    # recovery path (``checkpoint_units``) so a temperature>0 stream
    # resumed into an otherwise-empty engine replays its lost tail
    # bit-identically; migration snapshots leave it None (the live rng
    # keeps advancing)
    rng: Optional[np.ndarray] = None

    @property
    def remaining_tokens(self) -> int:
        return max(self.request.total_tokens - self.fed, 1)

    def remaining_cost(self,
                       discount: float = DEFAULT_PREFILL_DISCOUNT) -> float:
        """Remaining load with the not-yet-fed prefill part discounted."""
        rem = self.remaining_tokens
        rem_prefill = min(max(len(self.request.prompt) - 1 - self.fed, 0),
                          rem)
        return rem_prefill * discount + (rem - rem_prefill)


class BlockAllocator:
    """Free-list allocator over the paged cache's physical block pool.

    Pure host-side bookkeeping (no jax): a slot's whole reservation is
    taken in one ``allocate`` at admission and returned in one
    ``release`` at retire/pack — there is no incremental growth, which
    is what keeps the fused decode window dispatch-free.  Invariants
    (property-tested): every block is either free or owned by exactly
    one slot; ``allocate`` on an owning slot and ``release`` on a
    non-owning slot raise (leak/double-free detection, not silence).
    """

    def __init__(self, num_blocks: int):
        self.num_blocks = int(num_blocks)
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self._owned: Dict[int, Tuple[int, ...]] = {}
        self.peak_in_use = 0

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.num_blocks - len(self._free)

    def can_allocate(self, n: int) -> bool:
        return n <= len(self._free)

    def owned(self, slot: int) -> Tuple[int, ...]:
        return self._owned.get(slot, ())

    def allocate(self, slot: int, n: int) -> Tuple[int, ...]:
        if slot in self._owned:
            raise ValueError(f"slot {slot} already owns blocks (leak)")
        if n > len(self._free):
            raise ValueError(
                f"pool exhausted: want {n}, free {len(self._free)}")
        blocks = tuple(self._free.pop() for _ in range(max(n, 0)))
        self._owned[slot] = blocks
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return blocks

    def release(self, slot: int) -> Tuple[int, ...]:
        if slot not in self._owned:
            raise ValueError(f"slot {slot} owns no blocks (double free)")
        blocks = self._owned.pop(slot)
        self._free.extend(reversed(blocks))
        return blocks

    def check_invariants(self):
        """Raises unless free + owned exactly partition the pool."""
        free = set(self._free)
        owned = [b for bs in self._owned.values() for b in bs]
        assert len(free) == len(self._free), "duplicate free blocks"
        assert len(set(owned)) == len(owned), "block owned twice"
        assert not (free & set(owned)), "block both free and owned"
        assert len(free) + len(owned) == self.num_blocks, "blocks leaked"


# One jitted fn per (cfg, shape[, bucket/block]): replicas in a cluster
# share the compiled graphs instead of recompiling per engine.
_LOOP_CACHE: Dict[Tuple, Any] = {}
_PREFILL_CACHE: Dict[Tuple, Any] = {}


def _shared_loop(cfg: ModelConfig, shape: ShapeConfig, n_steps: int,
                 temperature: float, eos_token: Optional[int] = None):
    key = (cfg, shape, n_steps, float(temperature), eos_token)
    if key not in _LOOP_CACHE:
        _LOOP_CACHE[key] = jax.jit(
            zoo.make_decode_loop(cfg, shape, n_steps, temperature,
                                 eos_token=eos_token),
            donate_argnums=(1, 2))
    return _LOOP_CACHE[key]


def _shared_bulk_prefill(cfg: ModelConfig, shape: ShapeConfig, chunk: int):
    key = (cfg, shape, chunk)
    if key not in _PREFILL_CACHE:
        _PREFILL_CACHE[key] = jax.jit(
            zoo.make_bulk_prefill(cfg, shape, chunk), donate_argnums=(1,))
    return _PREFILL_CACHE[key]


def _shared_paged_loop(cfg: ModelConfig, shape: ShapeConfig, n_steps: int,
                       temperature: float, eos_token: Optional[int],
                       block_size: int, num_blocks: int):
    key = ("paged", cfg, shape, n_steps, float(temperature), eos_token,
           block_size, num_blocks)
    if key not in _LOOP_CACHE:
        _LOOP_CACHE[key] = jax.jit(
            zoo.make_paged_decode_loop(cfg, shape, n_steps, block_size,
                                       num_blocks, temperature,
                                       eos_token=eos_token),
            donate_argnums=(1, 2))
    return _LOOP_CACHE[key]


def _shared_paged_prefill(cfg: ModelConfig, shape: ShapeConfig, chunk: int,
                          block_size: int, num_blocks: int,
                          first: bool = False):
    key = ("paged", cfg, shape, chunk, block_size, num_blocks, first)
    if key not in _PREFILL_CACHE:
        _PREFILL_CACHE[key] = jax.jit(
            zoo.make_paged_bulk_prefill(cfg, shape, chunk, block_size,
                                        num_blocks, first_chunk=first),
            donate_argnums=(1,))
    return _PREFILL_CACHE[key]


def _slot_write(sample, prompt_buf, slot, next_tok, fed, plen, maxfed,
                active, prompt_row):
    """Fused slot (re)initialization: every per-slot sample field + the
    prompt row in ONE dispatch.  Admission used to issue seven eager
    device scatters per slot; under churn that dominated the decode loop
    itself, so the whole write is a single donated jit call."""
    sample = zoo.SampleState(
        next_tok=sample.next_tok.at[slot, 0].set(next_tok),
        active=sample.active.at[slot].set(active),
        fed=sample.fed.at[slot].set(fed),
        plen=sample.plen.at[slot].set(plen),
        maxfed=sample.maxfed.at[slot].set(maxfed),
        out_buf=sample.out_buf.at[slot].set(0),
        rng=sample.rng)
    return sample, prompt_buf.at[slot].set(prompt_row)


_SLOT_WRITE = jax.jit(_slot_write, donate_argnums=(0, 1))


def _table_write(bt, slot, row):
    return bt.at[slot].set(row)


_TABLE_WRITE = jax.jit(_table_write, donate_argnums=(0,))


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, batch_size: int = 4,
                 max_seq: int = 128, temperature: float = 0.0, seed: int = 0,
                 prefill_mode: str = "chunked",
                 prefill_buckets: Tuple[int, ...] = DEFAULT_PREFILL_BUCKETS,
                 prefill_discount: float = DEFAULT_PREFILL_DISCOUNT,
                 decode_block: int = 8, eos_token: Optional[int] = None,
                 cache_mode: str = "dense", block_size: int = 16,
                 kv_pool_blocks: Optional[int] = None):
        if prefill_mode not in ("chunked", "streamed"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        if cache_mode not in ("dense", "paged"):
            raise ValueError(f"unknown cache_mode {cache_mode!r}")
        self.cfg = cfg
        self.params = params
        self.batch = batch_size
        self.max_seq = max_seq
        self.temperature = temperature
        self.prefill_mode = prefill_mode
        self.prefill_discount = prefill_discount
        self.decode_block = max(int(decode_block), 1)
        # device-side EOS early exit: a slot that samples this token
        # clears its own active flag inside the fused loop.  The host
        # projection can no longer predict completion, so eos engines
        # reconcile against device truth after every window (one fetch
        # per window instead of zero; the saved fused steps dominate).
        self.eos_token = eos_token
        self.cache_mode = cache_mode
        self.shape = ShapeConfig("serve", max_seq, batch_size, "decode")
        if cache_mode == "paged":
            if max_seq % block_size:
                raise ValueError(
                    f"max_seq={max_seq} not a multiple of "
                    f"block_size={block_size}")
            self.block_size = block_size
            self.max_blocks = max_seq // block_size
            # default pool = exactly the dense engine's kv memory; pass a
            # smaller pool to trade ceiling for memory (admission gates
            # on free blocks, so it degrades to queueing, never OOM)
            self.pool_blocks = (batch_size * self.max_blocks
                                if kv_pool_blocks is None
                                else int(kv_pool_blocks))
            self.state = zoo.init_paged_decode_state(
                cfg, self.shape, block_size, self.pool_blocks)
            self._alloc: Optional[BlockAllocator] = BlockAllocator(
                self.pool_blocks)
            # host mirror of the device block tables: pack() and the
            # allocator invariants read this; the device copy is kept in
            # lockstep by ONE fused row-write dispatch per admission
            # (releases update only the mirror — see _release_blocks)
            self._tables = np.full((batch_size, self.max_blocks),
                                   self.pool_blocks, np.int32)
        else:
            self.block_size = 0
            self.pool_blocks = 0
            self.state = zoo.init_decode_state(cfg, self.shape, fill_len=0)
            self._alloc = None
            self._tables = None
        self.sample = zoo.init_sample_state(cfg, self.shape, seed=seed)
        self._prompt_buf = jnp.zeros((batch_size, max_seq), jnp.int32)
        self._slots: List[Optional[Request]] = [None] * batch_size
        self._queue: List[Request] = []
        self._restore: List["WorkUnit"] = []
        # per-slot provenance of restored units: slot -> (uid, hops,
        # origin).  ``pack`` re-uses it so a unit keeps ONE identity and
        # one hop history across any number of pack->unpack round trips.
        self._unit_meta: Dict[int, Tuple[int, list, Optional[int]]] = {}
        self._completed: List[Request] = []
        # exact host mirrors of the device progress counters: advanced by
        # projection after every decode window, overwritten with device
        # truth at every poll
        self._fed = np.zeros(batch_size, np.int64)
        self._plen = np.ones(batch_size, np.int64)
        self._maxfed = np.zeros(batch_size, np.int64)
        self._next_tok_host = np.zeros(batch_size, np.int64)
        self._out_read = np.zeros(batch_size, np.int64)
        self.processed_tokens = 0   # prefill + decode work units (rate feed)
        self.host_syncs = 0         # device->host fetches (poll/drain only)
        self.chunk_prefills = 0     # bulk prefill dispatches issued
        self.preemptions = 0        # slots paused via preempt()
        self.resumes = 0            # paused units re-admitted via resume()
        self.resizes = 0            # in-place geometry changes via resize()
        self.resize_evictions = 0   # slots evicted (paused) by a shrink
        self._peak_slots = 0        # high-water concurrent occupied slots
        self._chunk_tokens_pending = 0
        # name -> (jitted program, arguments after params and state) of
        # each program dispatched at the current geometry; None stands
        # for the live (sample, prompt_buf), which the decode loop
        # donates.  ``program_hlo`` lowers from here.
        self._programs: Dict[str, Tuple[Any, Optional[tuple]]] = {}
        if prefill_mode == "chunked" and cfg.family in zoo.BULK_PREFILL_FAMILIES:
            self._buckets = tuple(sorted(
                c for c in prefill_buckets if 0 < c <= max_seq))
        else:
            self._buckets = ()
        if not self._buckets:
            # no bulk path (streamed mode / family without a token-only
            # prefill): every prompt token costs a full decode step, so
            # backlog must not discount prefill work
            self.prefill_discount = 1.0
        # per-leaf batch axis of the cache pytree (slot slicing/placement)
        self._cache_axes = {
            k: ax.index("cache_batch")
            for k, ax in zoo.decode_state_logical_axes(cfg).cache.items()}

    # ------------------------------------------------------------- requests
    def submit(self, req: Request):
        if len(req.prompt) > self.max_seq - 1:
            raise ValueError(
                f"request {req.rid}: prompt of {len(req.prompt)} tokens "
                f"cannot fit a max_seq={self.max_seq} cache")
        self._queue.append(req)

    def reclaim_queue(self) -> List[Request]:
        """Hand not-yet-admitted requests back (router re-dispatch)."""
        queued, self._queue = self._queue, []
        return queued

    def pop_completed(self) -> List[Request]:
        done, self._completed = self._completed, []
        return done

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self._slots)

    @property
    def n_queued(self) -> int:
        return len(self._queue) + len(self._restore)

    @property
    def free_slots(self) -> int:
        """Admittable-request capacity (what the router/EDF simulate).

        Dense: free lanes.  Paged: also bounded by free pool blocks —
        a lane without blocks to back it cannot admit — estimated at the
        per-request block need of the engine's own pending work (falling
        back to the mean reservation of running slots, then to a whole
        ``max_seq`` worth: the conservative dense-equivalent).
        """
        lanes = self.batch - self.n_active
        if self._alloc is None or lanes == 0:
            return lanes
        est = self._est_blocks_per_request()
        return min(lanes, self._alloc.free_count // max(est, 1))

    def _est_blocks_per_request(self) -> int:
        reqs = [u.snapshot.request for u in self._restore] + self._queue
        if reqs:
            need = [self._blocks_needed(self._req_maxfed(r)) for r in reqs]
            return max(1, round(sum(need) / len(need)))
        owned = [len(self._alloc.owned(s)) for s, r in
                 enumerate(self._slots) if r is not None]
        if owned:
            return max(1, round(sum(owned) / len(owned)))
        return self.max_blocks

    def occupancy(self) -> Dict[str, int]:
        """Slot/block occupancy counters (threaded into cluster metrics).

        ``max_concurrent_slots`` is the high-water mark of simultaneously
        occupied slots; ``peak_blocks_in_use`` the pool's high-water
        block usage (both 0-pool for dense engines).
        """
        return {
            "active_slots": self.n_active,
            "max_concurrent_slots": self._peak_slots,
            "blocks_in_use": self._alloc.in_use if self._alloc else 0,
            "peak_blocks_in_use":
                self._alloc.peak_in_use if self._alloc else 0,
            "pool_blocks": self.pool_blocks,
        }

    def program_hlo(self) -> Dict[str, str]:
        """Optimized HLO text of each program dispatched since the
        engine's geometry was last set: the decode window
        (``decode_loop.k<steps>``) and every prefill chunk program
        (``paged_prefill.c<bucket>[.first]`` or ``bulk_prefill.c<bucket>``).

        Each is lowered with the engine's own arguments, so jit's cache
        hands back the executable the dispatches ran and nothing is
        compiled again.  Instructions carry the model's named scopes in
        their ``op_name`` metadata, which maps a device op in a profiler
        trace (named by its instruction) to the part of the model it
        belongs to.
        """
        out = {}
        for name, (fn, rest) in self._programs.items():
            if rest is None:
                rest = (self.sample, self._prompt_buf)
            out[name] = fn.lower(self.params, self.state,
                                 *rest).compile().as_text()
        return out

    def _note_program(self, name: str, fn, rest: Optional[tuple]):
        if name not in self._programs:
            self._programs[name] = (fn, rest)

    # ----------------------------------------------------- block lifecycle
    def _req_maxfed(self, req: Request) -> int:
        return min(len(req.prompt) + req.max_new_tokens - 1,
                   self.max_seq - 1)

    def _blocks_needed(self, maxfed: int) -> int:
        """Blocks covering every position a slot will ever write.

        Decode writes kv at positions ``0 .. maxfed-1`` (the token fed
        when ``fed == maxfed-1`` is the last one entering the cache), so
        ``ceil(maxfed / block_size)`` blocks reserved up front make the
        fused window allocation-free.
        """
        return max(1, -(-int(maxfed) // self.block_size))

    def _write_table_row(self, slot: int, blocks: Tuple[int, ...]):
        """Install ``slot``'s block mapping: host mirror + ONE fused
        device dispatch (sentinel-fill past the mapped prefix).  The
        mirror is what ``pack`` and the allocator invariants read; the
        device row is what every decode/prefill dispatch routes
        through."""
        self._tables[slot] = self.pool_blocks       # sentinel-fill
        self._tables[slot, :len(blocks)] = blocks
        self.state = self.state._replace(
            block_tables=_TABLE_WRITE(self.state.block_tables,
                                      np.int32(slot), self._tables[slot]))

    def _release_blocks(self, slot: int):
        """Return a retiring slot's blocks and sentinel its host table
        row.  The *device* row is left stale on purpose: a retired lane
        is ``active=0``, so its decode writes are routed to the drop
        sentinel by the active mask and its (clamped) gathers are
        discarded — and the row is rewritten by ``_write_table_row``
        before the slot is ever dispatched again.  Skipping the device
        sentinel write keeps retirement free of device dispatches."""
        self._alloc.release(slot)
        self._tables[slot] = self.pool_blocks

    def fed_tokens(self, slot: int) -> int:
        """Tokens already in ``slot``'s cache (exact, no device sync)."""
        return int(self._fed[slot])

    def queued_requests(self) -> Tuple[Request, ...]:
        """Accepted-but-unadmitted requests (control-plane visibility)."""
        return tuple(self._queue)

    def slot_requests(self) -> List[Tuple[int, Request]]:
        """Per occupied slot: (slot, request) — the preemptor's victim
        candidates, alongside ``slot_costs`` for their remaining load."""
        return [(i, r) for i, r in enumerate(self._slots) if r is not None]

    def backlog_tokens(self) -> float:
        """Remaining load across slots + queue (the router's signal).

        Prefill-remaining tokens are weighted by ``prefill_discount``:
        they are bulk-prefilled in one dispatch, so counting them 1:1
        with decode tokens would overstate the load of prompt-heavy
        engines and mis-steer the rate-aware router.
        """
        d = self.prefill_discount
        load = sum(cost for _, cost in self.slot_costs())
        load += sum(u.snapshot.remaining_cost(d) for u in self._restore)
        load += sum(request_cost(r, d) for r in self._queue)
        return load

    def restore_costs(self, discount: Optional[float] = None) -> List[float]:
        """Remaining discounted load per not-yet-admitted restore-queue
        unit (they claim free slots ahead of fresh work — the router's
        slot-availability simulation must count them)."""
        d = self.prefill_discount if discount is None else discount
        return [u.snapshot.remaining_cost(d) for u in self._restore]

    def slot_costs(self) -> List[Tuple[int, float]]:
        """Per occupied slot: (slot, remaining discounted load).

        The cluster's rebalancer uses this to pick migration victims —
        the slot with the most remaining work moves the most load per
        snapshot/restore round-trip.
        """
        d = self.prefill_discount
        out = []
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            rem = max(int(self._maxfed[slot] - self._fed[slot]), 1)
            rem_prefill = min(
                max(int(self._plen[slot] - 1 - self._fed[slot]), 0), rem)
            out.append((slot, rem_prefill * d + (rem - rem_prefill)))
        return out

    # ------------------------------------------------------------ admission
    def _pick_chunk(self, n_prefill: int,
                    room: Optional[int] = None) -> Tuple[int, int]:
        """Bulk-prefill bucket for ``n_prefill`` prompt tokens.

        Returns ``(bucket, n_real)`` — ``bucket`` = 0 means stream.
        Pad-safe (causal attention) families take the smallest bucket
        that covers the prompt and right-pad it; recurrent families take
        the largest fully-real bucket so no pad token ever enters the
        state recurrence.

        ``room`` caps the bucket at the cache positions left past the
        chunk's start offset (multi-chunk prefill mid-prompt): a padded
        bucket larger than the room would spill the write past the end
        of the slot's logical range.  When no covering bucket fits, a
        fully-real bucket is used instead (and the next round handles
        the remainder).
        """
        if not self._buckets or n_prefill <= 0:
            return 0, 0
        room = self.max_seq if room is None else room
        if self.cfg.family in zoo.PAD_SAFE_FAMILIES:
            for c in self._buckets:
                if n_prefill <= c <= room:
                    return c, n_prefill
            best = 0
            for c in self._buckets:
                if c <= min(n_prefill, room):
                    best = c
            return best, best
        best = 0
        chunk = max(self.cfg.ssm_chunk, 1)
        for c in self._buckets:
            if c <= min(n_prefill, room) and (c <= chunk or c % chunk == 0):
                best = c
        return best, best

    def _set_cache_len(self, slot: int, value: int):
        self.state = self.state._replace(
            cache_len=self.state.cache_len.at[slot].set(value))

    def _set_sample_row(self, slot: int, *, next_tok: int, fed: int,
                        plen: int, maxfed: int, prompt: np.ndarray,
                        active: int = 1):
        row = np.zeros(self.max_seq, np.int32)
        row[:len(prompt)] = prompt
        self.sample, self._prompt_buf = _SLOT_WRITE(
            self.sample, self._prompt_buf, np.int32(slot),
            np.int32(next_tok), np.int32(fed), np.int32(plen),
            np.int32(maxfed), np.int32(active), row)
        self._fed[slot] = fed
        self._plen[slot] = plen
        self._maxfed[slot] = maxfed
        self._next_tok_host[slot] = next_tok

    def _admit_fresh(self, req: Request, slot: int):
        P = len(req.prompt)
        maxfed = self._req_maxfed(req)
        if self._alloc is not None:
            blocks = self._alloc.allocate(slot, self._blocks_needed(maxfed))
            self._write_table_row(slot, blocks)
            n_fed = self._paged_chunk_prefills(req, slot, 0, P - 1)
        else:
            with span("serve.prefill"):
                chunk, n_real = self._pick_chunk(P - 1)
                if chunk:
                    bulk = _shared_bulk_prefill(self.cfg, self.shape, chunk)
                    ctoks = np.zeros((1, chunk), np.int32)
                    ctoks[0, :n_real] = req.prompt[:n_real]
                    args = (jnp.asarray(ctoks), np.int32(slot),
                            np.int32(n_real))
                    self._note_program(f"bulk_prefill.c{chunk}", bulk,
                                       args)
                    self.state = bulk(self.params, self.state, *args)
                    self.chunk_prefills += 1
                    self._chunk_tokens_pending += n_real
            if not chunk:
                self._set_cache_len(slot, 0)
            n_fed = n_real
        self._slots[slot] = req
        self._out_read[slot] = 0
        self._set_sample_row(slot, next_tok=int(req.prompt[n_fed]),
                             fed=n_fed, plen=P, maxfed=maxfed,
                             prompt=req.prompt)

    def _paged_chunk_prefills(self, req: Request, slot: int, start: int,
                              n_prefill: int) -> int:
        """Feed ``req.prompt[start : start + n_prefill]`` into ``slot``
        by state-continued chunk prefills (block-table appends).

        Unlike the dense path (one chunk, remainder streamed through the
        decode loop), prompts beyond the largest bucket keep appending
        chunks — each attends causally over the history already in the
        slot's blocks, and recurrent leaves carry the SSD/conv state
        across the chunk boundary.  Returns the new fed count; if no
        bucket fits the (remaining, room) pair the leftover streams.
        """
        off, remaining = start, n_prefill
        with span("serve.prefill"):
            while remaining > 0:
                chunk, n_real = self._pick_chunk(remaining,
                                                 room=self.max_seq - off)
                if not chunk:
                    break
                bulk = _shared_paged_prefill(
                    self.cfg, self.shape, chunk, self.block_size,
                    self.pool_blocks, first=(off == 0))
                ctoks = np.zeros((1, chunk), np.int32)
                ctoks[0, :n_real] = req.prompt[off:off + n_real]
                args = (jnp.asarray(ctoks), np.int32(slot), np.int32(off),
                        np.int32(n_real))
                self._note_program(
                    f"paged_prefill.c{chunk}" + (".first" if off == 0
                                                 else ""), bulk, args)
                self.state = bulk(self.params, self.state, *args)
                self.chunk_prefills += 1
                self._chunk_tokens_pending += n_real
                off += n_real
                remaining -= n_real
        if off == start:
            self._set_cache_len(slot, start)
        return off

    def _install(self, snap: SlotSnapshot, slot: int):
        """Write a snapshot's cache columns into ``slot`` and resume it.

        Snapshots are *canonical contiguous* (full ``max_seq`` columns)
        regardless of the source engine's cache mode or block size —
        a paged engine re-blocks them into its own geometry here, which
        is what makes dense<->paged and cross-block-size migration
        round-trip bit-identically.
        """
        req = snap.request
        maxfed = self._req_maxfed(req)
        # recovery checkpoints carry the sampler rng: restoring into an
        # otherwise-empty sampled engine replays the exact draws of the
        # lost tail (the rng is shared across slots, so a busy engine —
        # or a greedy one, which never consumes it — keeps its own)
        if (snap.rng is not None and self.temperature > 0
                and self.n_active == 0):
            self.sample = self.sample._replace(rng=jnp.asarray(snap.rng))
        if self._alloc is not None:
            blocks = self._alloc.allocate(slot, self._blocks_needed(maxfed))
            self._write_table_row(slot, blocks)
            kv_keys = set(zoo.paged_kv_keys(self.cfg))
            new_cache = {}
            for k, arr in self.state.cache.items():
                ax = self._cache_axes[k]
                col = np.asarray(snap.cache[k])
                if k in kv_keys:
                    # contiguous column (..., S, KV, D) -> lane-dense
                    # (..., max_blocks, block_size, KV*D) -> scatter the
                    # reserved prefix through the fresh table (dense
                    # batch axis == paged block axis)
                    blocked = col.reshape(
                        col.shape[:ax] + (self.max_blocks, self.block_size,
                                          -1))
                    sel = blocked[(slice(None),) * ax
                                  + (slice(0, len(blocks)),)]
                    idx = [slice(None)] * arr.ndim
                    idx[ax] = jnp.asarray(blocks, jnp.int32)
                    new_cache[k] = arr.at[tuple(idx)].set(
                        jnp.asarray(sel, arr.dtype))
                else:
                    idx = [slice(None)] * arr.ndim
                    idx[ax] = slot
                    new_cache[k] = arr.at[tuple(idx)].set(
                        jnp.asarray(col, arr.dtype))
            self.state = self.state._replace(cache=new_cache)
        else:
            new_cache = {}
            for k, arr in self.state.cache.items():
                ax = self._cache_axes[k]
                idx = [slice(None)] * arr.ndim
                idx[ax] = slot
                new_cache[k] = arr.at[tuple(idx)].set(
                    jnp.asarray(snap.cache[k], arr.dtype))
            self.state = self.state._replace(cache=new_cache)
        self._set_cache_len(slot, snap.cache_len)
        self._slots[slot] = req
        self._out_read[slot] = len(req.out_tokens)
        self._set_sample_row(slot, next_tok=snap.next_tok, fed=snap.fed,
                             plen=len(req.prompt), maxfed=maxfed,
                             prompt=req.prompt)

    def _can_admit(self, req: Request) -> bool:
        """Paged admission gate: the head-of-queue request must fit the
        free-block pool (FIFO — later requests don't jump a blocked
        head, so admission order stays deterministic and starvation-free).
        """
        if self._alloc is None:
            return True
        return self._alloc.can_allocate(
            self._blocks_needed(self._req_maxfed(req)))

    def _admit(self):
        """Fill free slots from the restore queue, then the request queue."""
        with span("serve.admit"):
            for slot in range(self.batch):
                if self._slots[slot] is not None:
                    continue
                if self._restore:
                    head = self._restore[0].snapshot.request
                    if not self._can_admit(head):
                        break
                    u = self._restore.pop(0)
                    self._install(u.snapshot, slot)
                    # keep the unit's identity alive on the slot: a later
                    # pack() re-emits the SAME uid and extends the same
                    # hop history (the list object is shared, so
                    # provenance recorded while the slot runs lands on
                    # the right unit)
                    self._unit_meta[slot] = (u.uid, u.hops, u.origin)
                elif self._queue:
                    if not self._can_admit(self._queue[0]):
                        break
                    self._admit_fresh(self._queue.pop(0), slot)
        self._peak_slots = max(self._peak_slots, self.n_active)

    # ------------------------------------------------------------- stepping
    def step_many(self, n_steps: int) -> Dict[str, int]:
        """Admit, then run ``n_steps`` fused decode steps in ONE dispatch.

        Returns ``{"steps", "emitted", "processed", "chunk_tokens"}``.
        ``processed`` counts work units fed this call (bulk-prefilled
        chunk tokens + per-step feeds); ``emitted`` counts generated
        tokens.  Both come from the host-side exact projection — the
        device is polled only when the projection says a slot finished.
        """
        self._chunk_tokens_pending = 0
        self._admit()
        chunk_tokens = self._chunk_tokens_pending
        stats = {"steps": 0, "emitted": 0, "processed": chunk_tokens,
                 "chunk_tokens": chunk_tokens}
        occupied = [i for i, r in enumerate(self._slots) if r is not None]
        if not occupied:
            self.processed_tokens += stats["processed"]
            return stats
        before = {slot: int(self._fed[slot]) for slot in occupied}
        if self._alloc is not None:
            loop = _shared_paged_loop(self.cfg, self.shape, n_steps,
                                      self.temperature, self.eos_token,
                                      self.block_size, self.pool_blocks)
        else:
            loop = _shared_loop(self.cfg, self.shape, n_steps,
                                self.temperature, self.eos_token)
        self._note_program(f"decode_loop.k{n_steps}", loop, None)
        with span("serve.decode_dispatch"):
            self.state, self.sample = loop(self.params, self.state,
                                           self.sample, self._prompt_buf)
        stats["steps"] = n_steps
        if self.eos_token is not None:
            # EOS can end a slot at any inner step, invisibly to the host
            # projection: reconcile against device truth every window
            # (``_poll`` reads fed/active, harvests finished slots).
            self._poll()
            for slot in occupied:
                after = int(self._fed[slot])
                plen = int(self._plen[slot])
                stats["processed"] += after - before[slot]
                stats["emitted"] += (max(0, after - plen + 1)
                                     - max(0, before[slot] - plen + 1))
            self.processed_tokens += stats["processed"]
            return stats
        done_any = False
        for slot in occupied:
            after = min(before[slot] + n_steps, int(self._maxfed[slot]))
            self._fed[slot] = after
            plen = int(self._plen[slot])
            stats["processed"] += after - before[slot]
            stats["emitted"] += (max(0, after - plen + 1)
                                 - max(0, before[slot] - plen + 1))
            if after >= self._maxfed[slot]:
                done_any = True
        self.processed_tokens += stats["processed"]
        if done_any:
            self._poll()
        return stats

    def step(self) -> int:
        """One engine step (admit + ONE fused decode); returns tokens
        emitted (generated tokens only — prefill doesn't count)."""
        return self.step_many(1)["emitted"]

    def run_until_idle(self, max_steps: int = 10_000) -> Dict[str, float]:
        t0 = time.perf_counter()
        tokens = 0
        steps = 0
        while (any(r is not None for r in self._slots) or self._queue
               or self._restore) and steps < max_steps:
            block = min(self.decode_block, max_steps - steps)
            out = self.step_many(block)
            tokens += out["emitted"]
            steps += max(out["steps"], 1)
        dt = time.perf_counter() - t0
        return {"tokens": tokens, "steps": steps, "seconds": dt,
                "tok_per_s": tokens / max(dt, 1e-9)}

    # ----------------------------------------------------------- host sync
    def _fetch(self, tree):
        """The ONLY device->host path in the engine (counted)."""
        self.host_syncs += 1
        with span("serve.host_sync"):
            return jax.device_get(tree)

    def _poll(self):
        """Materialize device progress into the Request objects.

        Called when the projection says a slot completed, and at drains —
        never in the steady-state decode loop.
        """
        with span("serve.poll"):
            occupied = [i for i, r in enumerate(self._slots)
                        if r is not None]
            if not occupied:
                return
            out_buf, fed, next_tok, active = self._fetch(
                (self.sample.out_buf, self.sample.fed,
                 self.sample.next_tok, self.sample.active))
            for slot in occupied:
                req = self._slots[slot]
                self._fed[slot] = int(fed[slot])
                self._next_tok_host[slot] = int(next_tok[slot, 0])
                n = max(0, int(fed[slot]) - int(self._plen[slot]) + 1)
                new = out_buf[slot, int(self._out_read[slot]):n]
                req.out_tokens.extend(int(t) for t in new)
                self._out_read[slot] = n
                # a device-deactivated occupied slot is finished — either
                # it reached maxfed, or it sampled the EOS token and
                # early-exited
                if (fed[slot] >= self._maxfed[slot]
                        or int(active[slot]) == 0):
                    req.done = True
                    self._completed.append(req)
                    self._slots[slot] = None
                    self._unit_meta.pop(slot, None)
                    if self._alloc is not None:
                        # blocks return to the pool at the window boundary;
                        # the next _admit can hand them to a queued request
                        self._release_blocks(slot)

    # ----------------------------------------------- WorkUnit pack/unpack
    #
    # One verb set for every in-flight-request move (the paper's PUP
    # interface): ``pack``/``unpack`` for migration and drain,
    # ``preempt``/``resume`` for SLO-aware pausing, and the
    # non-destructive ``checkpoint_units`` for periodic recovery
    # checkpoints.

    def _slot_cols(self, slot: int, cache_host: Dict[str, np.ndarray],
                   kv_keys) -> Dict[str, np.ndarray]:
        """Gather one slot's cache columns in the canonical contiguous
        layout (paged engines merge the slot's blocks and pad to
        ``max_seq``; dense engines just take the batch row)."""
        cols = {}
        for k, v in cache_host.items():
            ax = self._cache_axes[k]
            if k in kv_keys:
                # gather the slot's lane-dense blocks into the canonical
                # contiguous (..., S, KV, D) column (block-size-agnostic
                # snapshot)
                blocks = list(self._alloc.owned(slot))
                rows = v.take(blocks, axis=ax)
                sh = rows.shape
                merged = rows.reshape(
                    sh[:ax] + (sh[ax] * sh[ax + 1], self.cfg.num_kv_heads,
                               self.cfg.head_dim))
                pad = self.max_seq - merged.shape[ax]
                if pad:
                    widths = [(0, 0)] * merged.ndim
                    widths[ax] = (0, pad)
                    merged = np.pad(merged, widths)
                cols[k] = merged
            else:
                cols[k] = v.take(slot, axis=ax)
        return cols

    def _snapshot_slots(self, slots: Optional[List[int]] = None
                        ) -> List[Tuple[int, SlotSnapshot]]:
        """Checkpoint and release occupied slots (the PUP 'pack' step).

        ``slots`` restricts the checkpoint to a subset (the rebalancer's
        mid-stream migration and the preemptor pick single victims);
        None takes every occupied slot.  Works at any point in a
        request's life — including right after a bulk prefill chunk,
        before the prompt is fully fed.  Returns ``(slot, snapshot)``
        pairs so ``pack`` can look up per-slot unit provenance.
        """
        self._poll()
        occupied = [i for i, r in enumerate(self._slots)
                    if r is not None and (slots is None or i in slots)]
        if not occupied:
            return []
        cache_host = {k: np.asarray(v)
                      for k, v in self._fetch(self.state.cache).items()}
        kv_keys = (set(zoo.paged_kv_keys(self.cfg))
                   if self._alloc is not None else set())
        snaps = []
        deactivate = self.sample.active
        for slot in occupied:
            snaps.append((slot, SlotSnapshot(
                request=self._slots[slot],
                fed=int(self._fed[slot]),
                next_tok=int(self._next_tok_host[slot]),
                cache_len=int(self._fed[slot]),
                cache=self._slot_cols(slot, cache_host, kv_keys),
            )))
            self._slots[slot] = None
            if self._alloc is not None:
                self._release_blocks(slot)
            deactivate = deactivate.at[slot].set(0)
        self.sample = self.sample._replace(active=deactivate)
        return snaps

    def pack(self, slots: Optional[List[int]] = None) -> List["WorkUnit"]:
        """Checkpoint + release occupied slots as migratable ``WorkUnit``s.

        A packed unit is self-contained: ``unpack`` admits it into any
        engine built from the same ``(cfg, max_seq)`` and the greedy
        stream continues bit-identically.  A slot that was itself
        restored from a unit re-emits that unit's ``uid``, hop history
        and origin — identity is per in-flight request, not per
        checkpoint, so multi-hop migration chains stay traceable.
        """
        from repro.serving.workunit import WorkUnit
        units = []
        for slot, snap in self._snapshot_slots(slots):
            meta = self._unit_meta.pop(slot, None)
            if meta is None:
                units.append(WorkUnit(snapshot=snap))
            else:
                uid, hops, origin = meta
                units.append(WorkUnit(snapshot=snap, uid=uid, hops=hops,
                                      origin=origin))
        return units

    def unpack(self, units: List["WorkUnit"]):
        """Queue packed units for admission (cache written on admit).

        Unpacked units are admitted into free slots ahead of fresh
        queued requests, so migrated/resumed work never starves behind
        new arrivals.
        """
        self._restore.extend(units)

    def slot_provenance(self) -> Dict[int, Tuple[int, Tuple["Hop", ...]]]:
        """Per restored slot: ``(unit uid, hop history so far)`` — the
        observability window onto in-flight migration chains."""
        return {slot: (uid, tuple(hops))
                for slot, (uid, hops, _origin) in self._unit_meta.items()}

    def preempt(self, slots: Optional[List[int]] = None) -> List["WorkUnit"]:
        """Pause slots mid-stream: slot freed, snapshot retained.

        Mechanically a ``pack``, but the units come back ``PAUSED`` —
        parked by a preemption policy to free capacity for more urgent
        work, not in transit to another host.  ``resume`` continues the
        decoded stream bit-identically (asserted in tests).
        """
        from repro.serving.workunit import PAUSED
        units = self.pack(slots)
        for u in units:
            u.state = PAUSED
        self.preemptions += len(units)
        return units

    def resume(self, units: List["WorkUnit"]):
        """Re-admit paused units (the other half of ``preempt``)."""
        from repro.serving.workunit import PACKED
        for u in units:
            u.state = PACKED
        self.resumes += len(units)
        self.unpack(units)

    def drain_units(self) -> Tuple[List["WorkUnit"], List[Request]]:
        """Empty the engine: packed in-flight work + the untouched queue.

        Not-yet-admitted units waiting in the restore queue ride along
        as-is — same objects, same uids — so a drained engine hands back
        everything it owned without laundering identities.
        """
        units = self.pack()
        units.extend(self._restore)
        self._restore = []
        queued, self._queue = self._queue, []
        return units, queued

    def pending_units(self) -> Tuple["WorkUnit", ...]:
        """Restore-queue units awaiting admission (control-plane and
        failure-recovery visibility)."""
        return tuple(self._restore)

    def checkpoint_units(self) -> List["WorkUnit"]:
        """NON-destructive checkpoint of every occupied slot.

        Unlike ``pack``, the slots keep decoding: the returned units
        hold a *frozen* deep copy of each request (``out_tokens``
        truncated to checkpoint progress) plus the sampler rng, so a
        hard-killed replica's work restores from its last checkpoint
        and re-decodes only the lost tail — bit-identically for greedy
        streams (and for sampled streams resumed into an empty engine,
        which replays the same rng draws).  Unit identity (uid / hop
        history / origin) is copied, not shared: provenance recorded on
        the live slot after the checkpoint stays on the live unit.
        """
        from repro.serving.workunit import WorkUnit
        self._poll()
        occupied = [i for i, r in enumerate(self._slots) if r is not None]
        if not occupied:
            return []
        cache_raw, rng_raw = self._fetch((self.state.cache,
                                          self.sample.rng))
        cache_host = {k: np.asarray(v) for k, v in cache_raw.items()}
        rng_host = np.asarray(rng_raw)
        kv_keys = (set(zoo.paged_kv_keys(self.cfg))
                   if self._alloc is not None else set())
        units = []
        for slot in occupied:
            req = self._slots[slot]
            frozen = dataclasses.replace(
                req, out_tokens=list(req.out_tokens))
            snap = SlotSnapshot(
                request=frozen,
                fed=int(self._fed[slot]),
                next_tok=int(self._next_tok_host[slot]),
                cache_len=int(self._fed[slot]),
                cache=self._slot_cols(slot, cache_host, kv_keys),
                rng=rng_host.copy(),
            )
            meta = self._unit_meta.get(slot)
            if meta is None:
                units.append(WorkUnit(snapshot=snap))
            else:
                uid, hops, origin = meta
                units.append(WorkUnit(snapshot=snap, uid=uid,
                                      hops=list(hops), origin=origin))
        return units

    # ------------------------------------------------- vertical elasticity
    @staticmethod
    def _default_evict_key(u: "WorkUnit") -> Tuple:
        """Keep-preference order under a shrink: most urgent SLO class
        first (lowest priority number), then most progress (evicting a
        nearly-done stream wastes the most sunk work), uid tiebreak."""
        prio = u.slo.priority if u.slo is not None else 1
        return (prio, -u.snapshot.fed, u.uid)

    def resize(self, *, batch_size: Optional[int] = None,
               decode_block: Optional[int] = None,
               kv_pool_blocks: Optional[int] = None,
               evict_key=None) -> List["WorkUnit"]:
        """In-place geometry change: repack every live slot through the
        canonical ``SlotSnapshot`` path and rebuild the decode state at
        the new ``(batch_size, kv_pool_blocks)`` — no drain, no restart.

        Surviving slots re-admit through ``unpack``/``_install`` (ahead
        of the queue, re-blocked into the new pool geometry) so their
        streams continue bit-identically; the sampler rng is carried
        across so temperature>0 streams keep their draw sequence.  Slots
        that no longer fit (fewer lanes, or a smaller block pool) come
        back as ``PAUSED`` WorkUnits — the same objects a ``preempt``
        would return, ready for a resume here or anywhere else.
        ``evict_key`` orders keep-preference (lowest kept first to fill
        capacity); the default keeps the most urgent SLO classes, the
        QoS layer passes BestEffort-evicts-first.

        Compiled decode/prefill functions are keyed by shape, so a
        resize costs at most one new compilation per fresh geometry and
        nothing when bouncing between already-seen sizes.
        """
        from repro.serving.workunit import PAUSED
        new_batch = self.batch if batch_size is None else int(batch_size)
        if new_batch < 1:
            raise ValueError(f"batch_size must be >= 1, got {new_batch}")
        if decode_block is not None:
            self.decode_block = max(int(decode_block), 1)
        new_pool = self.pool_blocks
        if kv_pool_blocks is not None:
            if self.cache_mode != "paged":
                raise ValueError(
                    "kv_pool_blocks only applies to cache_mode='paged'")
            new_pool = int(kv_pool_blocks)
            if new_pool < self.max_blocks:
                raise ValueError(
                    f"kv_pool_blocks={new_pool} cannot hold one full "
                    f"request ({self.max_blocks} blocks) — admission "
                    f"would wedge")
        elif self.cache_mode == "paged" and batch_size is not None:
            # pool follows the lane count by default (the dense-equivalent
            # memory budget at the new width)
            new_pool = new_batch * self.max_blocks
        if new_batch == self.batch and new_pool == self.pool_blocks:
            return []              # decode_block-only change: no repack
        units = self.pack()        # polls + harvests completions first
        units.sort(key=evict_key or self._default_evict_key)
        keep: List["WorkUnit"] = []
        evicted: List["WorkUnit"] = []
        lanes, blocks_free = new_batch, new_pool
        for u in units:
            need = (self._blocks_needed(self._req_maxfed(u.snapshot.request))
                    if self.cache_mode == "paged" else 0)
            if lanes > 0 and need <= blocks_free:
                keep.append(u)
                lanes -= 1
                blocks_free -= need
            else:
                evicted.append(u)
        rng = self.sample.rng      # carried across the rebuild
        self.batch = new_batch
        self.shape = ShapeConfig("serve", self.max_seq, new_batch, "decode")
        if self.cache_mode == "paged":
            self.pool_blocks = new_pool
            self.state = zoo.init_paged_decode_state(
                self.cfg, self.shape, self.block_size, new_pool)
            self._alloc = BlockAllocator(new_pool)
            self._tables = np.full((new_batch, self.max_blocks),
                                   new_pool, np.int32)
        else:
            self.state = zoo.init_decode_state(self.cfg, self.shape,
                                               fill_len=0)
        self.sample = zoo.init_sample_state(
            self.cfg, self.shape, seed=0)._replace(rng=rng)
        self._prompt_buf = jnp.zeros((new_batch, self.max_seq), jnp.int32)
        self._slots = [None] * new_batch
        self._unit_meta = {}
        self._fed = np.zeros(new_batch, np.int64)
        self._plen = np.ones(new_batch, np.int64)
        self._maxfed = np.zeros(new_batch, np.int64)
        self._next_tok_host = np.zeros(new_batch, np.int64)
        self._out_read = np.zeros(new_batch, np.int64)
        self._programs = {}
        # survivors re-admit ahead of everything already waiting
        self._restore = keep + self._restore
        for u in evicted:
            u.state = PAUSED
        self.resizes += 1
        self.resize_evictions += len(evicted)
        self._admit()
        return evicted
