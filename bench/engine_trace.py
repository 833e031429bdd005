"""What the serving engine's own spans and the model's named scopes say
about a traced run of a serving cell.  A tool for reading a run by hand,
beside the benchmark.

    python bench/engine_trace.py --out engine_trace.json -- \\
        --workload granite3b.batch --seed 1 --seconds 51 --trace 1

runs ``bench/run.py`` with the arguments after ``--`` (``run.measure``)
and prints its result line as usual.  It reads the profiler's trace
before the run removes it, and writes to ``--out``:

* ``gaps_by_span``: device-idle seconds in the window, each instant of a
  gap given to the innermost ``bench.*`` or ``serve.*`` span over it
  (``none`` where there is none); ``engine_idle_ms``: the ``serve.*``
  part per decode window (one ``serve.decode_dispatch`` span each);
  ``top_gaps``: the longest gaps, with the spans they fell in.
* ``dispatch``: the lead of each ``serve.decode_dispatch`` span over the
  ``jit_decode_loop`` run it launched, on the device clock as
  ``trace_reduce`` shifts it; a negative lead means the clocks disagree.
* ``decode_by_scope`` and ``prefill_by_scope``: device op time of the
  decode and prefill programs by model scope, and
  ``decode_top_unscoped``: the decode instructions with no scope, all
  from the run's own ``RunData`` (``trace.instr_s`` and ``scopes``, as
  the readers see them; ``decode_unscoped_share.batch`` is the share).

An engine without spans gives the parts it can.  The first device plane
is read for the gaps and the dispatch leads.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import Counter, defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

import trace_reduce as tr
from trace_reduce import UNSCOPED, device_events

SPAN_PREFIXES = ("bench.", "serve.")
ENGINE_PREFIX = "serve."
DISPATCH_SPAN = "serve.decode_dispatch"
DECODE_PROGRAM = "decode_loop"
PREFILL_PROGRAM = "paged_prefill"


# ------------------------------------------------------ device side
def idle_gaps(ops, lo: int, hi: int) -> List[Tuple[int, int]]:
    merged = tr._clip(tr._union([(a, b) for a, b, _ in ops]), lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


# -------------------------------------------------------- host side
def host_spans(planes) -> List[Tuple[str, int, int]]:
    """``bench.*`` and ``serve.*`` spans on the host planes."""
    out = []
    for p in planes:
        if p.name.startswith(tr.DEVICE_PREFIX):
            continue
        for line in p.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events
                       if e.name.startswith(SPAN_PREFIXES))
    return out


def innermost(a: int, b: int, spans) -> Dict[str, float]:
    """Seconds of [a, b) by the innermost (shortest) span over each
    instant; ``none`` where no span is.  The window span is skipped."""
    cover = [(max(a, s), min(b, e), e - s, n) for n, s, e in spans
             if n != tr.WINDOW_SPAN and min(b, e) > max(a, s)]
    cuts = sorted({a, b} | {c for x, y, _, _ in cover for c in (x, y)})
    out: Dict[str, float] = defaultdict(float)
    for u, v in zip(cuts, cuts[1:]):
        over = [(length, n) for x, y, length, n in cover
                if x <= u and v <= y]
        out[min(over)[1] if over else UNSCOPED] += (v - u) * 1e-9
    return dict(out)


def leads(dispatch_starts: Iterable[int],
          run_starts: Iterable[int]) -> List[int]:
    """Nanoseconds from each dispatch span's start to the start of the
    run it launched, dispatches and runs matched in order."""
    return [r - d for d, r in zip(sorted(dispatch_starts),
                                  sorted(run_starts))]


# ----------------------------------------------------------- report
def _unscoped(times, smap, program) -> Counter:
    """Seconds of each instruction of ``program`` with no known scope."""
    out = Counter()
    for (module, ins), t in times.items():
        if program in module and smap.get((module, ins),
                                          UNSCOPED) == UNSCOPED:
            out[ins] += t
    return out


def by_scope(data, top: int = 8) -> dict:
    """Decode and prefill op time by model scope, from a run's own
    per-instruction seconds and scope map; empty without them."""
    if not (data.trace and data.scopes):
        return {}
    times, smap = data.trace.instr_s, data.scopes
    return {
        "decode_by_scope": tr.scope_seconds(times, smap, DECODE_PROGRAM),
        "decode_top_unscoped": _unscoped(
            times, smap, DECODE_PROGRAM).most_common(top),
        "prefill_by_scope": tr.scope_seconds(times, smap, PREFILL_PROGRAM),
    }


def analyze(planes, top: int = 8) -> dict:
    """Idle gaps by span and dispatch leads in the traced window."""
    planes = list(planes)
    spans = host_spans(planes)
    marks = [(s, e) for n, s, e in spans if n == tr.WINDOW_SPAN]
    if not marks:
        raise ValueError("trace has no bench.window span")
    lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    dev = min((p for p in planes if p.name.startswith(tr.DEVICE_PREFIX)),
              key=lambda p: p.name)
    shift = tr._shift(dev, tr._launches(planes, lo, hi))
    mods, ops = device_events(dev, shift, lo, hi)

    named: Dict[str, float] = defaultdict(float)
    gaps = []
    for a, b in idle_gaps(ops, lo, hi):
        pieces = innermost(a, b, spans)
        for k, v in pieces.items():
            named[k] += v
        gaps.append((b - a, a, pieces))
    gaps.sort(key=lambda g: -g[0])
    dispatch = [s for n, s, _ in spans if n == DISPATCH_SPAN and lo <= s < hi]
    runs = [a for a, _, n in mods if DECODE_PROGRAM in n]
    lead = leads(dispatch, runs)
    engine_idle = sum(v for k, v in named.items()
                      if k.startswith(ENGINE_PREFIX))
    return {
        "window_s": (hi - lo) * 1e-9,
        "shift_ns": shift,
        "gaps_by_span": dict(named),
        "engine_idle_ms": 1e3 * engine_idle / len(dispatch)
        if dispatch else None,
        "top_gaps": [{"s": d * 1e-9, "at_s": (a - lo) * 1e-9,
                      "spans": p} for d, a, p in gaps[:top]],
        "dispatch": {"spans": len(dispatch), "decode_runs": len(runs),
                     "least_lead_ms": min(lead) * 1e-6 if lead else None,
                     "negative": sum(v < 0 for v in lead)},
    }


# ------------------------------------------------------------- tool
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("run_args", nargs=argparse.REMAINDER)
    a = ap.parse_args(argv)
    run_args = [x for x in a.run_args if x != "--"]

    import core
    import run as bench_run            # bench/run.py; puts src/ on the path

    class Tracer(bench_run.Tracer):
        seen = {}

        def summary(self, chips):
            from jax.profiler import ProfileData
            path = tr.find_xplane(self.dir)
            Tracer.seen = analyze(ProfileData.from_file(path).planes)
            return super().summary(chips)

    got = bench_run.measure(run_args, Tracer)
    if got is None:
        return 1
    line, compared, data = got
    out = Path(a.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(dict(Tracer.seen, **by_scope(data)),
                              indent=1, default=float))
    core.emit(line, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
