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
name without its ``.N`` suffix (``paged_attention``).

``reduce_planes`` works on any objects with the attributes of
``jax.profiler.ProfileData`` (planes -> lines -> events with ``name``,
``start_ns`` and ``duration_ns``), so tests can hand it small traces.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
LAUNCH = "PJRT_LoadedExecutable_Execute"
# control-flow ops whose events enclose the ops of their bodies: counted
# in busy time, left out of per-op time (their bodies are counted)
CONTAINERS = ("while", "conditional", "call")


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
    busy = 0.0
    gaps = []
    launches = _launches(planes, lo, hi)
    for p in devices:
        ivs = []
        shift = _shift(p, launches)
        for line in p.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                a = e.start_ns + shift
                b = a + e.duration_ns
                if b <= lo or a >= hi:
                    continue
                dur = (min(b, hi) - max(a, lo)) * 1e-9
                if line.name == OPS_LINE:
                    ivs.append((a, b))
                    if _op(e.name) not in CONTAINERS:
                        op_s[_op(e.name)] += dur
                        op_n[_op(e.name)] += 1
                else:
                    mod_s[_base(e.name)] += dur
                    mod_n[_base(e.name)] += 1
        merged = _clip(_union(ivs), lo, hi)
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
                        gaps=gaps)


def find_xplane(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def reduce_file(path: str, chips: Optional[int] = None) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, chips=chips)
