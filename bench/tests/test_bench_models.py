"""The model module a configuration names: the harness reaches a model
only through ``bench/models/<model>.py`` (or, here, a stand-in under
``bench/tests/data/``), finds it by the file's ``"model"`` key, refuses
a file that names none, and splits device time by the scopes the module
declares."""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import core
import flops
import run as bench_run
import tiny
import trace_reduce
from metrics import _serve

DATA = Path(__file__).resolve().parent / "data"
STANDIN = DATA / "zamba2-2.7b.json"


def _trace(kernel_s: float):
    return trace_reduce.TraceSummary(
        window_s=1.0, busy_s=1.0, chips=1,
        op_s={"paged_attention": kernel_s}, op_count={"paged_attention": 1},
        module_s={}, module_count={}, gaps=[])


@pytest.mark.parametrize("body,says", [
    ({"name": "x"}, "needs a \"model\" key"),
    ({"name": "x", "model": "no_such_model"}, "has no module"),
    ({"name": "x", "model": "../weights"}, "needs a \"model\" key")])
def test_a_configuration_must_name_a_model_module(tmp_path, body, says):
    f = tmp_path / "cfg.json"
    f.write_text(json.dumps(body))
    spec = {"workloads": [{"name": "c", "config": "x", "traffic": "batch",
                           "chips": 1}],
            "configs": [{"name": "x", "file": str(f)}]}
    with pytest.raises(SystemExit, match=re.escape(str(f))) as e:
        core.find_cell(spec, "c")
    assert says in str(e.value)


def test_the_benchmark_configurations_name_their_models():
    spec = core.load_spec()
    for c in spec["configs"]:
        cfg_file = json.loads((core.ROOT / c["file"]).read_text())
        model = core.load_model(cfg_file, c["file"])
        assert model.dims(cfg_file).vocab == cfg_file["vocab_size"]


def test_a_hybrid_model_runs_through_the_harness_from_test_files(
        monkeypatch, capsys):
    """zamba2-2.7b at its reduced() size, Mamba-2 layers with a shared
    attention block every 2, served by the paged engine and judged by a
    stand-in module found through its file's ``"model"`` key, up to the
    result line; the readers take its counts and its two kernel calls a
    step.  A plumbing check: its gap is not asserted (see PERF.md, a
    lane's earlier request changes a short prompt's tokens)."""
    monkeypatch.setattr(core, "MODELS", DATA)
    cfg_file = json.loads(STANDIN.read_text())
    result, compared, data, counter = tiny.run(
        "batch", cfg=cfg_file, limits={"max_logit_gap": 0.02})
    assert data.model.__name__ == "bench_model_zamba2_standin"
    assert data.model.attention_layers(data.dims) == 2
    assert counter.events == 0
    gap = compared["max_logit_gap"]
    assert 0 <= gap["value"] < float("inf")
    assert result["correct"] == (gap["value"] <= gap["limit"])

    data.trace = _trace(1e-3)
    need = sum(2 * flops.roofline_seconds(
        *data.model.paged_attention_cost(data.dims, ctx), data.peak)
        for ctx in _serve.decode_ctx_by_step(data) if ctx)
    spec = {"end_to_end": [{"name": "out_tok_s", "unit": "tokens/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": [{"name": "mfu.batch", "unit": "%"},
                          {"name": "paged_attn_roofline.batch",
                           "unit": "%"}]}
    cell = {"name": "tiny.batch"}
    out = {k: result[k] for k in ("correct", "attempted", "failed",
                                  "device")}
    out["metrics"] = bench_run.end_to_end(spec, cell, data,
                                          result["setup_s"])
    out["metrics"].update(bench_run.per_layer(spec, cell, data))
    core.emit(out, compared)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line["metrics"]) == {"out_tok_s", "setup_s", "mfu.batch",
                                    "paged_attn_roofline.batch"}
    assert line["metrics"]["mfu.batch"]["value"] > 0
    assert line["metrics"]["paged_attn_roofline.batch"]["value"] == \
        pytest.approx(100.0 * need / 1e-3)
    assert line["compared"]["max_logit_gap"]["value"] == gap["value"]


@pytest.fixture(scope="module")
def dense_run():
    _, _, data, _ = tiny.run("batch", seed=5)
    return data


def test_the_dense_module_reads_as_the_direct_counts(dense_run):
    """mfu, roofline and prefill operations through ``models/dense.py``
    equal, bit for bit, the same sums written with ``flops`` directly."""
    run = dense_run
    assert run.model.__name__ == "bench_model_dense"
    pre = sum(flops.prefill_flops(run.dims, run.recs[rid].prompt_len - 1)
              for w in run.windows for rid in w.admitted)
    dec = sum(flops.decode_flops(run.dims, ctx)
              for ctx in _serve.decode_ctx_by_step(run))
    assert pre > 0 and dec > 0
    assert _serve.prefill_flops(run) == pre
    assert core.load_reader("mfu.batch")(run) == 100.0 * (pre + dec) / (
        run.wall_s * run.peak["bf16_flops_per_s"])

    run.trace = _trace(2e-3)
    need = 0.0
    for ctx in _serve.decode_ctx_by_step(run):
        if ctx:
            f, b = flops.paged_attention_cost(run.dims, ctx)
            need += run.dims.layers * flops.roofline_seconds(f, b, run.peak)
    assert core.load_reader("paged_attn_roofline.batch")(run) == \
        100.0 * need / 2e-3


class NoTrace:
    """A tracer that records nothing: the run still keeps its scope map."""

    def start(self):
        pass

    def stop(self):
        pass


def _ssm_program() -> str:
    """Optimized HLO of a small program whose work sits under a named
    scope that the dense model does not declare."""
    @jax.jit
    def ssm_step(x):
        with jax.named_scope("ssm"):
            return jnp.cumsum(x * 2.0)
    return ssm_step.lower(jnp.ones(8)).compile().as_text()


def test_a_scope_the_model_declares_reaches_the_readers(tmp_path,
                                                        monkeypatch):
    """A model module found by the file's ``"model"`` key declares
    ``ssm`` besides the dense scopes; a traced run's scope map, taken by
    the harness from the engine's programs (here with one more program
    whose ops are under ``ssm``), gives those ops ``ssm``, and
    ``_serve.scope_seconds`` reports its seconds.  No harness file is
    edited: the module is the dense one with a longer ``SCOPES``."""
    from repro.serving.engine import ServingEngine
    (tmp_path / "dense_ssm.py").write_text(
        (core.MODELS / "dense.py").read_text()
        + '\nSCOPES = SCOPES + ("ssm",)\n')
    monkeypatch.setattr(core, "MODELS", tmp_path)
    hlo = _ssm_program()
    own = ServingEngine.program_hlo
    monkeypatch.setattr(ServingEngine, "program_hlo",
                        lambda self: dict(own(self), ssm_step=hlo))
    cfg = dict(tiny.cfg_file(True), model="dense_ssm")
    _, _, data, counter = tiny.run("batch", cfg=cfg, tracer=NoTrace())
    assert counter.events == 0
    ssm = [k for k, s in data.scopes.items() if s == "ssm"]
    assert ssm and {m for m, _ in ssm} == {"jit_ssm_step"}
    assert {s for (m, _), s in data.scopes.items()
            if m == "jit_decode_loop"} == set(data.model.SCOPES[:-1]) | {
                "none"}
    dense = tuple(s for s in data.model.SCOPES if s != "ssm")
    assert set(trace_reduce.scope_map({"s": hlo}, dense).values()) == {
        "none"}

    instr = {k: 0.25 for k in ssm}
    instr[("jit_ssm_step", "lost.1")] = 0.5
    data.trace = trace_reduce.TraceSummary(
        window_s=1.0, busy_s=1.0, chips=1, op_s={}, op_count={},
        module_s={}, module_count={}, gaps=[], instr_s=instr)
    assert _serve.scope_seconds(data, "ssm_step") == {
        "ssm": 0.25 * len(ssm), "unmapped": 0.5}
