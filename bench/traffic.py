"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and turns a seed into requests.

Lengths are lognormal, clipped, and stratified: a run of ``n`` requests
takes the ``n`` quantiles (i + 0.5) / n of prompt lengths and of output
lengths, so every seed gets the same multiset of each.  Requests are
dealt in rounds of ``round`` that hold one prompt length and one output
length from every stratum (consecutive ranks), paired the same way for
every seed: round j holds the same (prompt, output) pairs whatever the
seed, so each stretch of the backlog (the lanes filled at once, the
requests that follow) carries the same work.  The seed decides the
order of the pairs within each round, and the token ids.

An offline mix (``kind: offline``, the only kind) queues its whole
``backlog`` at the start of the window.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from statistics import NormalDist

import numpy as np

MIX_DIR = Path(__file__).resolve().parent / "traffic"
_ROUND_SEED = 20240513   # which values share a round: the same for every seed


@dataclasses.dataclass
class Item:
    rid: int
    prompt: np.ndarray      # int32 token ids
    max_new: int


def load_mix(name: str) -> dict:
    return json.loads((MIX_DIR / f"{name}.json").read_text())


def lognormal_quantiles(n: int, spec: dict) -> np.ndarray:
    """n stratified quantiles of a clipped lognormal, ascending."""
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = spec["median"] * np.exp(spec["sigma"] * np.asarray(z))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _rounds(n: int, size: int, fixed) -> list:
    """Rounds of indices into n items sorted by size: the items split
    into ``size`` strata of consecutive ranks, and round j takes one item
    of each stratum, which one drawn by ``fixed``."""
    strata = [fixed.permutation(s) for s in np.array_split(np.arange(n), size)]
    return [np.asarray([s[j] for s in strata if j < len(s)])
            for j in range(max(len(s) for s in strata))]


def pair_rounds(n: int, size: int) -> list:
    """Rounds of (prompt rank, output rank) pairs, the same for every
    seed: each round holds one prompt and one output of every stratum,
    paired at random once and for all."""
    fixed = np.random.default_rng(_ROUND_SEED)
    size = max(1, min(size, n))
    prompts, outs = _rounds(n, size, fixed), _rounds(n, size, fixed)
    return [np.stack([p, fixed.permutation(o)], 1)
            for p, o in zip(prompts, outs)]


def request_count(mix: dict) -> int:
    if mix["kind"] != "offline":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    return int(mix["backlog"])


def length_pairs(mix: dict, n: int):
    """The prompt and output lengths of a run of n requests, each
    ascending (the same for every seed)."""
    return (lognormal_quantiles(n, mix["prompt"]),
            lognormal_quantiles(n, mix["output"]))


def generate(mix: dict, seed: int, vocab: int, max_seq: int):
    """Requests of one run, in the order they are queued."""
    n = request_count(mix)
    rng = np.random.default_rng(int(seed) % (1 << 63))
    prompts, outs = length_pairs(mix, n)
    if int(prompts[-1] + outs[-1]) > max_seq:
        raise ValueError(f"mix {mix} exceeds max_seq {max_seq}")
    order = np.concatenate([r[rng.permutation(len(r))]
                            for r in pair_rounds(n, mix["round"])])
    items = []
    for rid, (p, o) in enumerate(order):
        prompt = rng.integers(0, vocab, size=int(prompts[p]), dtype=np.int32)
        items.append(Item(rid, prompt, int(outs[o])))
    return items


def warm_lengths(mix: dict) -> list:
    """Every distinct prompt length the mix can draw (any seed, any
    window up to the longest allowed), for warming the prefill shapes."""
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    return list(range(int(lo), int(hi) + 1))


def describe(mix: dict, n: int) -> dict:
    p, o = length_pairs(mix, n)
    return {"requests": n, "prompt_median": float(np.median(p)),
            "prompt_max": int(p.max()), "output_median": float(np.median(o)),
            "output_max": int(o.max()), "tokens_in": int(p.sum()),
            "tokens_out": int(o.sum())}
