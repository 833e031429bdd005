"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

Device planes are those named ``/device:TPU:<n>``.  On each, the line of
XLA ops gives busy time (the union of op intervals) and per-op device
time; the line of XLA modules gives the time of each jitted program.
The benchmark's host spans (``jax.profiler.TraceAnnotation`` names that
start with ``bench.``) sit on the host plane, on the same clock; the
span ``bench.window`` marks the measured window, and every idle gap on a
device inside it is named after the host span that overlaps it most.

Device timestamps can sit a millisecond or so off the host's clock.
Each launch of a program on the host (``PJRT_LoadedExecutable_Execute``)
precedes that program's run on the device, so the device line is shifted
by the least amount that puts no run before its launch (runs and
launches matched in order).

Op events are named by their HLO instruction (``%paged_attention.1 =
bf16[...] custom-call(...)``); ops are counted under the instruction's
name without its ``.N`` suffix (``paged_attention``), and under (module,
instruction): each op goes to the program run that encloses its start,
so that ``scope_map`` of the programs' HLO texts can give each the
model scope it was traced in: the innermost of the ``jax.named_scope``
names that the run's model module declares (``SCOPES`` of
``bench/models/<model>.py``) in the instruction's ``op_name``.

``reduce_planes`` works on any objects with the attributes of
``jax.profiler.ProfileData`` (planes -> lines -> events with ``name``,
``start_ns`` and ``duration_ns``), so tests can hand it small traces.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Collection, Dict, Iterable, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
LAUNCH = "PJRT_LoadedExecutable_Execute"
# control-flow ops whose events enclose the ops of their bodies: counted
# in busy time, left out of per-op time (their bodies are counted)
CONTAINERS = ("while", "conditional", "call")
UNSCOPED = "none"
UNMAPPED = "unmapped"          # an instruction the scope map lacks

_MODULE = re.compile(r"HloModule\s+([^\s,]+)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@dataclasses.dataclass
class TraceSummary:
    window_s: float                      # length of the traced window
    busy_s: float                        # union of device ops, mean/chip
    chips: int
    op_s: Dict[str, float]               # per op name, summed over chips
    op_count: Dict[str, int]
    module_s: Dict[str, float]           # per jitted program, summed
    module_count: Dict[str, int]
    gaps: List[Tuple[str, float]]        # idle gaps, longest first
    # per (module, instruction), summed over chips (``op_time``)
    instr_s: Dict[Tuple[str, str], float] = dataclasses.field(
        default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def ops_matching(self, needle: str) -> Tuple[float, int]:
        s = sum(v for k, v in self.op_s.items() if needle in k)
        n = sum(v for k, v in self.op_count.items() if needle in k)
        return s, n

    def modules_matching(self, needle: str) -> Tuple[float, int]:
        s = sum(v for k, v in self.module_s.items() if needle in k)
        n = sum(v for k, v in self.module_count.items() if needle in k)
        return s, n

    def breakdown(self, n: int = 10) -> dict:
        mods = sorted(self.module_s.items(), key=lambda kv: -kv[1])
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])
        # programs first, then the ops that took most time inside them
        top = (mods[:n // 2] + ops)[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:n]]}


def _base(name: str) -> str:
    """``jit_decode_loop(1234)`` -> ``jit_decode_loop``."""
    return name.split("(", 1)[0].strip()


def _op(name: str) -> str:
    """``%paged_attention.1 = bf16[..] custom-call(..)`` ->
    ``paged_attention``; ``%fusion.12 = ..`` -> ``fusion``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    stem, _, tail = head.rpartition(".")
    return stem if stem and tail.isdigit() else head


def instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[4] fusion(..)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def scope_of(op_name: str, scopes: Collection[str]) -> str:
    """The innermost of ``scopes`` in an ``op_name`` path, e.g.
    ``jit(f)/while/body/attention/dot`` -> ``attention``."""
    found = UNSCOPED
    for part in op_name.split("/"):
        if part in scopes:
            found = part
    return found


def scope_map(hlo: Dict[str, str],
              scopes: Iterable[str]) -> Dict[Tuple[str, str], str]:
    """(module, instruction) -> the innermost of the model's ``scopes``
    over HLO texts.  Programs that share a module name (the prefill
    chunk sizes) and give one instruction name different scopes map it
    to ``mixed``."""
    scopes = frozenset(scopes)
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
            sc = scope_of(op.group(1), scopes) if op else UNSCOPED
            key = (module, ins.group(1))
            out[key] = sc if out.get(key, sc) == sc else "mixed"
    return out


def scope_seconds(instr_s: Dict[Tuple[str, str], float],
                  smap: Dict[Tuple[str, str], str],
                  program: str) -> Dict[str, float]:
    """Seconds of each scope in the modules whose name holds
    ``program``; an instruction the map lacks counts as ``unmapped``."""
    out: Dict[str, float] = defaultdict(float)
    for (module, ins), t in instr_s.items():
        if program in module:
            out[smap.get((module, ins), UNMAPPED)] += t
    return dict(out)


def device_events(plane, shift: int, lo: int, hi: int):
    """Module runs ``(start, end, base name)`` and ops ``(start, end,
    event name)`` of one device plane that overlap [lo, hi), on the
    host's clock, each sorted by start."""
    mods, ops = [], []
    for line in plane.lines:
        if line.name not in (OPS_LINE, MODULES_LINE):
            continue
        for e in line.events:
            a = e.start_ns + shift
            b = a + e.duration_ns
            if b <= lo or a >= hi:
                continue
            if line.name == MODULES_LINE:
                mods.append((a, b, _base(e.name)))
            else:
                ops.append((a, b, e.name))
    return sorted(mods), sorted(ops)


def op_time(mods, ops, lo: int, hi: int) -> Dict[Tuple[str, str], float]:
    """Seconds in [lo, hi) of each (module, instruction), each op under
    the module run that encloses its start; control-flow ops that
    enclose their bodies are left out."""
    starts = [m[0] for m in mods]
    out: Dict[Tuple[str, str], float] = defaultdict(float)
    for a, b, name in ops:
        if _op(name) in CONTAINERS:
            continue
        i = bisect.bisect_right(starts, a) - 1
        module = mods[i][2] if i >= 0 and mods[i][1] >= a else ""
        out[(module, instruction(name))] += (min(b, hi) - max(a, lo)) * 1e-9
    return dict(out)


def _launches(planes, lo, hi):
    out = []
    for p in planes:
        if p.name.startswith(DEVICE_PREFIX):
            continue
        for line in p.lines:
            out.extend(e.start_ns for e in line.events
                       if e.name == LAUNCH and lo <= e.start_ns < hi)
    return sorted(out)


def _shift(device_plane, launches) -> int:
    """Nanoseconds to add to the device's clock so that no program run
    starts before its launch: the window's launches matched in order to
    the runs from 10 ms before the first of them (the window opens on an
    idle device)."""
    if not launches:
        return 0
    runs = sorted(e.start_ns for line in device_plane.lines
                  if line.name == MODULES_LINE for e in line.events
                  if e.start_ns >= launches[0] - 10_000_000)
    diffs = [r - h for r, h in zip(runs[:200], launches[:200])]
    return max(0, -min(diffs)) if diffs else 0


def _union(intervals) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _host_spans(planes):
    spans = []
    for p in planes:
        if p.name.startswith(DEVICE_PREFIX):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
    return spans


def _name_gap(a, b, spans) -> str:
    """The host span overlapping [a, b) most; of equal overlaps, the
    innermost (shortest) one."""
    best, best_key = "no bench span", (0, 0)
    for name, s, e in spans:
        ov = min(b, e) - max(a, s)
        if name == WINDOW_SPAN or ov <= 0:
            continue
        if (ov, s - e) > best_key:
            best, best_key = name, (ov, s - e)
    return best


def reduce_planes(planes, window: Optional[Tuple[int, int]] = None,
                  chips: Optional[int] = None) -> TraceSummary:
    planes = list(planes)
    spans = _host_spans(planes)
    if window is None:
        marks = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
        if not marks:
            raise ValueError("trace has no bench.window span")
        window = (min(s for s, _ in marks), max(e for _, e in marks))
    lo, hi = window
    devices = sorted((p for p in planes if p.name.startswith(DEVICE_PREFIX)),
                     key=lambda p: p.name)
    if chips is not None:
        devices = devices[:chips]
    if not devices:
        raise ValueError("trace has no TPU device plane")
    op_s: Dict[str, float] = defaultdict(float)
    op_n: Dict[str, int] = defaultdict(int)
    mod_s: Dict[str, float] = defaultdict(float)
    mod_n: Dict[str, int] = defaultdict(int)
    instr_s: Dict[Tuple[str, str], float] = defaultdict(float)
    busy = 0.0
    gaps = []
    launches = _launches(planes, lo, hi)
    for p in devices:
        mods, ops = device_events(p, _shift(p, launches), lo, hi)
        for a, b, name in mods:
            mod_s[name] += (min(b, hi) - max(a, lo)) * 1e-9
            mod_n[name] += 1
        for a, b, name in ops:
            if _op(name) not in CONTAINERS:
                op_s[_op(name)] += (min(b, hi) - max(a, lo)) * 1e-9
                op_n[_op(name)] += 1
        for key, t in op_time(mods, ops, lo, hi).items():
            instr_s[key] += t
        merged = _clip(_union([(a, b) for a, b, _ in ops]), lo, hi)
        busy += sum(b - a for a, b in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_name_gap(a, b, spans), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(window_s=(hi - lo) * 1e-9,
                        busy_s=busy / len(devices), chips=len(devices),
                        op_s=dict(op_s), op_count=dict(op_n),
                        module_s=dict(mod_s), module_count=dict(mod_n),
                        gaps=gaps, instr_s=dict(instr_s))


def find_xplane(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def reduce_file(path: str, chips: Optional[int] = None) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, chips=chips)
