"""The engine trace tool on small traces and HLO texts, and the prefill
reader on a hand-built run: gaps given to the innermost span, ops to
their program and scope, dispatch spans against the runs they launched,
and prefill device time per 1,000 prompt tokens."""

from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

import core
import engine_trace as et
import serve_cell
import trace_reduce
from metrics import _serve


DENSE = core.load_model({"model": "dense"}, "the dense model")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


HLO = """HloModule jit_decode_loop, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {
  ROOT %add.1 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(decode_loop)/while/body/mlp/add"}
}

ENTRY %main (p: f32[4]) -> (f32[4], s32[]) {
  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(decode_loop)/while/body/closed_call/mlp/add"}
  %custom-call.7 = f32[4]{0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_loop)/while/body/qkv/attention/pallas_call"}
  %copy.2 = f32[4]{0} copy(%custom-call.7)
  %while.1 = (f32[4], s32[]) while(%copy.2), condition=%cond, body=%body, metadata={op_name="jit(decode_loop)/while"}
  ROOT %tuple.9 = (f32[4], s32[]) tuple(%copy.2, %c)
}
"""
PREFILL_A = """HloModule jit_paged_prefill, is_scheduled=true
ENTRY %main (p: f32[4]) -> f32[4] {
  ROOT %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(paged_prefill)/kv_write/scatter"}
}
"""
PREFILL_B = PREFILL_A.replace("kv_write/scatter", "mlp/dot")


def test_an_instruction_takes_its_innermost_model_scope():
    def scope_of(op_name):
        return trace_reduce.scope_of(op_name, DENSE.SCOPES)
    assert scope_of("jit(f)/while/body/closed_call/kv_write/scatter") \
        == "kv_write"
    assert scope_of("jit(f)/while/body/qkv/attention/pallas_call") \
        == "attention"
    assert scope_of("jit(f)/while/body/dynamic_slice") == "none"
    # a scope is one the model declares, wherever it sits in the path
    assert trace_reduce.scope_of("jit(f)/ssm/while/body/dot",
                                 DENSE.SCOPES) == "none"
    assert trace_reduce.scope_of("jit(f)/ssm/while/body/dot",
                                 DENSE.SCOPES + ("ssm",)) == "ssm"
    assert trace_reduce.instruction("%fusion.12 = bf16[4]{0} fusion(%p)") \
        == "fusion.12"
    smap = trace_reduce.scope_map({"decode_loop.k4": HLO}, DENSE.SCOPES)
    assert smap[("jit_decode_loop", "fusion.3")] == "mlp"
    assert smap[("jit_decode_loop", "custom-call.7")] == "attention"
    assert smap[("jit_decode_loop", "copy.2")] == "none"
    assert smap[("jit_decode_loop", "while.1")] == "none"
    assert smap[("jit_decode_loop", "tuple.9")] == "none"
    assert smap[("jit_decode_loop", "add.1")] == "mlp"


def test_programs_that_share_a_module_name_and_disagree_are_mixed():
    smap = trace_reduce.scope_map({"paged_prefill.c16": PREFILL_A,
                                   "paged_prefill.c64": PREFILL_A},
                                  DENSE.SCOPES)
    assert smap[("jit_paged_prefill", "fusion.1")] == "kv_write"
    smap = trace_reduce.scope_map({"paged_prefill.c16": PREFILL_A,
                                   "paged_prefill.c64": PREFILL_B},
                                  DENSE.SCOPES)
    assert smap[("jit_paged_prefill", "fusion.1")] == "mixed"


def test_a_gap_goes_to_the_innermost_span_over_each_instant():
    spans = [("bench.window", 0, 1000), ("bench.step_many", 100, 600),
             ("serve.poll", 200, 400), ("serve.host_sync", 250, 300)]
    # nested in serve.poll inside bench.step_many
    assert et.innermost(320, 380, spans) == {"serve.poll": pytest.approx(
        60e-9)}
    got = et.innermost(240, 700, spans)
    assert got == {"serve.poll": pytest.approx((10 + 100) * 1e-9),
                   "serve.host_sync": pytest.approx(50e-9),
                   "bench.step_many": pytest.approx(200e-9),
                   "none": pytest.approx(100e-9)}


def test_dispatch_leads_match_spans_and_runs_in_order():
    assert et.leads([300, 100], [150, 320]) == [50, 20]
    assert min(et.leads([100, 300], [90, 320])) < 0


def planes():
    """Two decode windows, one prefill: the host dispatches, admits and
    polls; the device runs the programs after their launches."""
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit_paged_prefill(5)", 120, 80),
            ev("jit_decode_loop(7)", 210, 190),
            ev("jit_decode_loop(7)", 610, 190)]),
        NS(name="XLA Ops", events=[
            ev("%fusion.1 = f32[4]{0} fusion(%p)", 120, 80),
            ev("%while.1 = (f32[4]) while(%copy.2)", 210, 190),
            ev("%fusion.3 = f32[4]{0} fusion(%p)", 210, 100),
            ev("%custom-call.7 = f32[4]{0} custom-call(%f)", 310, 50),
            ev("%copy.2 = f32[4]{0} copy(%c)", 360, 40),
            ev("%while.1 = (f32[4]) while(%copy.2)", 610, 190),
            ev("%fusion.3 = f32[4]{0} fusion(%p)", 610, 190)]),
    ])
    launch = "PJRT_LoadedExecutable_Execute"
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev("bench.window", 100, 900),
        ev("bench.step_many", 100, 120), ev("serve.admit", 100, 100),
        ev("serve.prefill", 110, 80), ev(launch, 115, 5),
        ev("serve.decode_dispatch", 200, 10), ev(launch, 205, 5),
        ev("bench.step_many", 400, 210), ev("serve.poll", 400, 150),
        ev("serve.host_sync", 400, 100),
        ev("serve.decode_dispatch", 590, 10), ev(launch, 600, 5)])])
    return [host, dev]


def _scoped(ps):
    """What a traced run keeps of ``ps``: the reduction and the scope
    map of the programs ``HLO`` and ``PREFILL_A``."""
    return NS(trace=trace_reduce.reduce_planes(ps),
              scopes=trace_reduce.scope_map(
                  {"decode_loop.k4": HLO, "paged_prefill.c16": PREFILL_A},
                  DENSE.SCOPES))


def test_analyze_names_gaps_checks_dispatch_and_splits_by_scope():
    res = et.analyze(planes())
    assert res["window_s"] == pytest.approx(900e-9)
    # idle [100, 120), [200, 210), [400, 610) and [800, 1000)
    assert res["gaps_by_span"] == {
        "serve.admit": pytest.approx(10e-9),
        "serve.prefill": pytest.approx(10e-9),
        "serve.decode_dispatch": pytest.approx(20e-9),
        "serve.host_sync": pytest.approx(100e-9),
        "serve.poll": pytest.approx(50e-9),
        "bench.step_many": pytest.approx(50e-9),
        "none": pytest.approx(200e-9)}
    assert res["engine_idle_ms"] == pytest.approx(1e3 * 190e-9 / 2)
    assert res["top_gaps"][0]["s"] == pytest.approx(210e-9)
    assert res["dispatch"] == {"spans": 2, "decode_runs": 2,
                               "least_lead_ms": pytest.approx(10e-6),
                               "negative": 0}
    # decode ops: mlp 100 + 190, attention 50, unscoped copy 40; the
    # while loops enclose their bodies and are left out
    run = _scoped(planes())
    res = et.by_scope(run)
    assert res["decode_by_scope"] == {"mlp": pytest.approx(290e-9),
                                      "attention": pytest.approx(50e-9),
                                      "none": pytest.approx(40e-9)}
    assert core.load_reader("decode_unscoped_share.batch")(run) == \
        pytest.approx(100 * 40 / 380)
    assert res["decode_top_unscoped"][0][0] == "copy.2"
    assert res["prefill_by_scope"] == {"kv_write": pytest.approx(80e-9)}


def test_analyze_without_hlo_or_engine_spans():
    ps = planes()
    ps[0].lines[0].events = [e for e in ps[0].lines[0].events
                             if not e.name.startswith("serve.")]
    res = et.analyze(ps)
    assert res["engine_idle_ms"] is None
    assert et.by_scope(NS(trace=trace_reduce.reduce_planes(ps),
                          scopes=None)) == {}
    assert set(res["gaps_by_span"]) <= {"bench.step_many", "none"}


def test_the_tool_reads_a_tiny_engines_programs():
    from repro.configs import get_config
    from repro.models import model_zoo as zoo
    from repro.serving.engine import Request, ServingEngine
    cfg = get_config("granite-8b").reduced()
    params = zoo.init_state(cfg, jax.random.PRNGKey(0)).params
    eng = ServingEngine(cfg, params, batch_size=2, max_seq=96,
                        prefill_buckets=(16, 64), cache_mode="paged",
                        block_size=8)
    eng.submit(Request(0, np.arange(1, 71, dtype=np.int32), 3))
    while eng.n_active or eng.n_queued:
        eng.step_many(4)
    smap = trace_reduce.scope_map(eng.program_hlo(), DENSE.SCOPES)
    decode = {s for (m, _), s in smap.items() if m == "jit_decode_loop"}
    assert decode == set(DENSE.SCOPES) | {"none"}
    assert {m for m, _ in smap} == {"jit_decode_loop", "jit_paged_prefill"}


def _run(trace, admitted, prompt_lens):
    recs = {rid: serve_cell.Rec(n, 4) for rid, n in prompt_lens.items()}
    windows = [serve_cell.Window(k, 8, 0, {}, [], ids, {}, {}, {})
               for k, ids in enumerate(admitted)]
    return serve_cell.RunData(dims=None, peak=None, lanes=4, seconds=1.0,
                              wall_s=1.0, windows=windows, recs=recs,
                              trace=trace)


def _summary(prefill_s, instr_s=None):
    return trace_reduce.TraceSummary(
        window_s=1.0, busy_s=0.9, chips=1, op_s={}, op_count={},
        module_s={"jit_paged_prefill": prefill_s, "jit_decode_loop": 0.5},
        module_count={"jit_paged_prefill": 3, "jit_decode_loop": 2},
        gaps=[], instr_s=instr_s or {})


def test_prefill_reader_on_a_hand_built_run():
    read = core.load_reader("prefill_ms_per_ktok.batch")
    # three admissions, prompts of 1001, 501 and 499 tokens less the
    # last of each: 1998 tokens prefilled in 0.4 s of prefill programs
    run = _run(_summary(0.4), [[1, 2], [], [3]],
               {1: 1001, 2: 501, 3: 499, 4: 900})
    assert read(run) == pytest.approx(1e6 * 0.4 / 1998)
    assert read(_run(None, [[1]], {1: 10})) is None
    assert read(_run(_summary(0.0), [[1]], {1: 10})) is None
    assert read(_run(_summary(0.4), [[]], {1: 10})) is None


def test_decode_unscoped_share_on_a_hand_built_run():
    """Decode op time by scope through the programs' scope map: mlp
    0.3 s, attention 0.5 s, the unscoped copy 0.1 s and an instruction
    the map lacks 0.05 s, counted as unscoped; the prefill program's
    time is not decode's."""
    read = core.load_reader("decode_unscoped_share.batch")
    smap = trace_reduce.scope_map({"decode_loop.k4": HLO,
                                   "paged_prefill.c16": PREFILL_A},
                                  DENSE.SCOPES)
    instr = {("jit_decode_loop", "fusion.3"): 0.3,
             ("jit_decode_loop", "custom-call.7"): 0.5,
             ("jit_decode_loop", "copy.2"): 0.1,
             ("jit_decode_loop", "fusion.99"): 0.05,
             ("jit_paged_prefill", "fusion.1"): 0.2}
    run = _run(_summary(0.2, instr), [[1]], {1: 10})
    run.scopes = smap
    assert _serve.scope_seconds(run, "decode_loop") == {
        "mlp": 0.3, "attention": 0.5, "none": 0.1, "unmapped": 0.05}
    assert _serve.scope_seconds(run, "paged_prefill") == {"kv_write": 0.2}
    assert read(run) == pytest.approx(100 * (0.1 + 0.05) / 0.95)
    # a map that matches none of the trace's instructions reads 100%
    run.scopes = {("jit_decode_loop", "other.1"): "mlp"}
    assert read(run) == pytest.approx(100.0)
    run.scopes = None
    assert read(run) is None
    assert read(_run(None, [[1]], {1: 10})) is None
    run.scopes = smap
    run.trace = _summary(0.2, {("jit_paged_prefill", "fusion.1"): 0.2})
    assert read(run) is None
