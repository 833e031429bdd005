"""Trace reduction on small traces: busy and idle time, per-op and
per-program device time, and idle gaps named by the host span."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import core
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
