"""Trace reduction on small traces: busy and idle time, per-op and
per-program device time, and idle gaps named by the host span."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import core
import engine_trace
import trace_reduce

RECORDED = Path(__file__).resolve().parent / "data" / "probe.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_decode_loop(7)", 100, 300),
                                       ev("jit_paged_prefill(3)", 600, 200)]),
        NS(name="XLA Ops", events=[ev("%while.3 = (s32[]) while()", 100, 250),
                                   ev("fusion.1", 100, 100),
                                   ev("paged_attention", 200, 150),
                                   ev("fusion.2", 620, 180)]),
        NS(name="Steps", events=[ev("ignored", 0, 1000)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev("bench.window", 0, 1000), ev("bench.step_many", 350, 250),
        ev("bench.window_wait", 590, 40), ev("other", 0, 1000)])])
    return [host, dev]


def test_busy_is_the_union_of_ops_inside_the_window():
    s = trace_reduce.reduce_planes(planes())
    # ops cover [100, 350) and [620, 800): 430 ns of 1000
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(430e-9)
    assert s.idle_share == pytest.approx(0.57)


def test_per_op_and_per_program_time():
    s = trace_reduce.reduce_planes(planes())
    assert s.ops_matching("paged_attention") == (pytest.approx(150e-9), 1)
    assert "while" not in s.op_s        # encloses fusion.1 and the kernel
    assert s.modules_matching("decode_loop") == (pytest.approx(300e-9), 1)
    assert s.modules_matching("paged_prefill") == (pytest.approx(200e-9), 1)
    b = s.breakdown()
    assert b["device_ops"][0][0] == "jit_decode_loop"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_idle_gaps_are_named_by_the_overlapping_host_span():
    s = trace_reduce.reduce_planes(planes())
    gaps = dict((round(d * 1e9), n) for n, d in s.gaps)
    assert trace_reduce._op("%paged_attention.1 = bf16[4] custom-call()") \
        == "paged_attention"
    assert gaps[270] == "bench.step_many"      # [350, 620)
    assert gaps[200] == "no bench span"        # [800, 1000)
    assert gaps[100] == "no bench span"        # [0, 100)


def test_a_trace_without_a_device_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(planes()[:1])


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        core.peaks("TPU v99")
    assert core.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_a_cpu_run_is_refused():
    with pytest.raises(core.NoChip):
        core.require_chips(1)


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e: three rounds of a matmul and a
    paged-attention call, each followed by a host wait.  The device runs
    are shifted onto the host clock, after their launches."""
    s = trace_reduce.reduce_file(str(RECORDED))
    assert s.chips == 1
    assert 0 < s.busy_s < 0.1 * s.window_s
    assert s.ops_matching("paged_attention")[1] == 3
    assert s.modules_matching("jit__lambda")[1] == 6
    assert s.gaps[0][0] == "bench.window_wait"


def test_op_time_by_program_and_instruction():
    """Each op counts under the program run that encloses its start; the
    while loop that encloses its body is left out, as in ``op_s``."""
    s = trace_reduce.reduce_planes(planes())
    assert s.instr_s == {
        ("jit_decode_loop", "fusion.1"): pytest.approx(100e-9),
        ("jit_decode_loop", "paged_attention"): pytest.approx(150e-9),
        ("jit_paged_prefill", "fusion.2"): pytest.approx(180e-9)}
    assert sum(s.instr_s.values()) == pytest.approx(sum(s.op_s.values()))


def test_recorded_trace_op_time_agrees_with_one_walk_of_its_device():
    """On the recorded chip trace, the seconds per (program, instruction)
    that the reduction keeps equal one ``op_time`` walk over the first
    device plane's events, shifted onto the host clock, with the window
    taken from ``engine_trace``'s host spans."""
    from jax.profiler import ProfileData
    s = trace_reduce.reduce_file(str(RECORDED))
    ps = list(ProfileData.from_file(str(RECORDED)).planes)
    marks = [(a, b) for n, a, b in engine_trace.host_spans(ps)
             if n == trace_reduce.WINDOW_SPAN]
    lo, hi = min(a for a, _ in marks), max(b for _, b in marks)
    dev = next(p for p in ps if p.name.startswith(trace_reduce.DEVICE_PREFIX))
    shift = trace_reduce._shift(dev, trace_reduce._launches(ps, lo, hi))
    mods, ops = trace_reduce.device_events(dev, shift, lo, hi)
    want = trace_reduce.op_time(mods, ops, lo, hi)
    assert want and s.instr_s == pytest.approx(want)
    kernel = {k: v for k, v in s.instr_s.items() if "paged_attention" in k[1]}
    assert {m for m, _ in kernel} == {"jit__lambda"}
    assert sum(kernel.values()) == pytest.approx(
        s.ops_matching("paged_attention")[0])
