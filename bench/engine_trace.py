"""What the serving engine's own spans and the model's named scopes say
about a traced run of a serving cell.  A tool for reading a run by hand,
beside the benchmark: no metric of ``BENCHMARK.json`` reads it.

    python bench/engine_trace.py --out engine_trace.json -- \\
        --workload granite3b.batch --seed 1 --seconds 51 --trace 1

runs ``bench/run.py`` with the arguments after ``--`` and prints its
result line as usual.  It keeps a weak reference to the engine the run
builds, reads that engine's ``program_hlo()`` when the traced window
closes, and before the profiler's trace is removed writes to ``--out``:

* ``gaps_by_span``: device-idle seconds in the window, each instant of a
  gap given to the innermost ``bench.*`` or ``serve.*`` span over it
  (``none`` where there is none); ``engine_idle_ms``: the ``serve.*``
  part per decode window (one ``serve.decode_dispatch`` span each);
  ``top_gaps``: the longest gaps, with the spans they fell in.
* ``dispatch``: the lead of each ``serve.decode_dispatch`` span over the
  ``jit_decode_loop`` run it launched, on the device clock as
  ``trace_reduce`` shifts it; a negative lead means the clocks disagree.
* ``decode_by_scope`` and ``prefill_by_scope``: device op time of the
  decode and prefill programs by the innermost model scope of each
  instruction; ``decode_unscoped_share``: the percent of decode op time
  with no scope; ``decode_top_unscoped``: the instructions that take it.

An engine without spans or ``program_hlo()`` gives the parts it can.
The first device plane is read.
"""

from __future__ import annotations

import argparse
import bisect
import json
import re
import sys
import weakref
from collections import Counter, defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

import trace_reduce as tr

SCOPES = ("qkv", "kv_write", "attention", "attn_out", "mlp", "logits",
          "sample")
UNSCOPED = "none"
SPAN_PREFIXES = ("bench.", "serve.")
ENGINE_PREFIX = "serve."
DISPATCH_SPAN = "serve.decode_dispatch"
DECODE_PROGRAM = "decode_loop"
PREFILL_PROGRAM = "paged_prefill"

_MODULE = re.compile(r"HloModule\s+([^\s,]+)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


# ----------------------------------------------------------- scopes
def scope_of(op_name: str) -> str:
    """The innermost model scope in an ``op_name`` path, e.g.
    ``jit(f)/while/body/attention/dot`` -> ``attention``."""
    found = UNSCOPED
    for part in op_name.split("/"):
        if part in SCOPES:
            found = part
    return found


def scope_map(hlo: Dict[str, str]) -> Dict[Tuple[str, str], str]:
    """(module, instruction) -> scope over HLO texts.  Programs that
    share a module name (the prefill chunk sizes) and give one
    instruction name different scopes map it to ``mixed``."""
    out: Dict[Tuple[str, str], str] = {}
    for text in hlo.values():
        m = _MODULE.search(text)
        if not m:
            continue
        module = m.group(1)
        for line in text.splitlines():
            ins = _INSTR.match(line)
            if not ins:
                continue
            op = _OP_NAME.search(line)
            sc = scope_of(op.group(1)) if op else UNSCOPED
            key = (module, ins.group(1))
            out[key] = sc if out.get(key, sc) == sc else "mixed"
    return out


def instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[4] fusion(..)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


# ------------------------------------------------------ device side
def device_events(plane, shift: int, lo: int, hi: int):
    """Module runs ``(start, end, base name)`` and ops ``(start, end,
    event name)`` of one device plane that overlap [lo, hi), on the
    host's clock, each sorted by start."""
    mods, ops = [], []
    for line in plane.lines:
        if line.name not in (tr.OPS_LINE, tr.MODULES_LINE):
            continue
        for e in line.events:
            a = e.start_ns + shift
            b = a + e.duration_ns
            if b <= lo or a >= hi:
                continue
            if line.name == tr.MODULES_LINE:
                mods.append((a, b, tr._base(e.name)))
            else:
                ops.append((a, b, e.name))
    return sorted(mods), sorted(ops)


def op_time(mods, ops, lo: int, hi: int) -> Dict[Tuple[str, str], float]:
    """Seconds in [lo, hi) of each (module, instruction), each op under
    the module run that encloses its start; control-flow ops that
    enclose their bodies are left out, as ``trace_reduce`` leaves them."""
    starts = [m[0] for m in mods]
    out: Dict[Tuple[str, str], float] = defaultdict(float)
    for a, b, name in ops:
        if tr._op(name) in tr.CONTAINERS:
            continue
        i = bisect.bisect_right(starts, a) - 1
        module = mods[i][2] if i >= 0 and mods[i][1] >= a else ""
        out[(module, instruction(name))] += (min(b, hi) - max(a, lo)) * 1e-9
    return dict(out)


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
def _by_scope(times, smap, program):
    by, unscoped = defaultdict(float), Counter()
    for (module, ins), t in times.items():
        if program not in module:
            continue
        sc = smap.get((module, ins), "unmapped")
        by[sc] += t
        if sc in (UNSCOPED, "unmapped"):
            unscoped[ins] += t
    return dict(by), unscoped


def analyze(planes, hlo: Optional[Dict[str, str]] = None,
            top: int = 8) -> dict:
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
    res = {
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
    if hlo:
        smap = scope_map(hlo)
        times = op_time(mods, ops, lo, hi)
        dec, dec_un = _by_scope(times, smap, DECODE_PROGRAM)
        pre, _ = _by_scope(times, smap, PREFILL_PROGRAM)
        total = sum(dec.values())
        res.update({
            "programs": sorted(hlo),
            "decode_by_scope": dec,
            "decode_unscoped_share": 100.0 * dec.get(UNSCOPED, 0.0) / total
            if total else None,
            "decode_top_unscoped": dec_un.most_common(top),
            "prefill_by_scope": pre,
        })
    return res


# ------------------------------------------------------------- tool
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("run_args", nargs=argparse.REMAINDER)
    a = ap.parse_args(argv)
    run_args = [x for x in a.run_args if x != "--"]

    import run as bench_run            # bench/run.py; puts src/ on the path
    from repro.serving import engine as engine_mod

    built = []
    init = engine_mod.ServingEngine.__init__

    def remember(self, *args, **kw):
        init(self, *args, **kw)
        built.append(weakref.ref(self))

    class Tracer(bench_run.Tracer):
        hlo = None

        def stop(self):
            super().stop()
            eng = built[-1]() if built else None
            if hasattr(eng, "program_hlo"):
                Tracer.hlo = eng.program_hlo()

        def summary(self, chips):
            from jax.profiler import ProfileData
            path = tr.find_xplane(self.dir)
            res = analyze(ProfileData.from_file(path).planes, Tracer.hlo)
            out = Path(a.out)
            out.parent.mkdir(parents=True, exist_ok=True)
            out.write_text(json.dumps(res, indent=1, default=float))
            return super().summary(chips)

    engine_mod.ServingEngine.__init__ = remember
    bench_run.Tracer = Tracer
    sys.argv = ["bench/run.py"] + run_args
    return bench_run.main()


if __name__ == "__main__":
    sys.exit(main())
