"""Dry runs of each serving cell's harness path on the CPU, tiny size.

The chip check is skipped (the run is called below ``run.py``); all the
rest runs: weights, engine, set-up warm-up, the window, the stream of
reads, the reference comparison.  A clean run is correct and compiles
nothing inside its window; a run whose decode window is broken
underneath comes out not correct.
"""

import pytest

import serve_cell
import tiny
from repro.serving import engine as engine_mod


def test_clean_run_is_correct_and_compiles_nothing_in_the_window():
    result, compared, data, counter = tiny.run("batch")
    assert result["correct"], compared
    assert counter.events == 0
    assert all(w.mismatches == 0 for w in data.windows)
    assert serve_cell.out_tok_s(data) > 0
    assert result["attempted"] > 4


def test_the_sample_spans_several_lanes():
    """The check judges requests from several lanes, not the longest
    answer alone, even where that answer covers the token target."""
    _, compared, data, _ = tiny.run("batch", seed=11)
    rule = compared["max_logit_gap"]["rule"]
    lanes = int(rule.split(" on ")[1].split()[0])
    assert lanes >= tiny.MIXES["batch"]["check"]["min_lanes"], rule


def test_the_sample_takes_the_longest_then_new_lanes():
    recs = {i: serve_cell.Rec(10, 5, lane=lane) for i, lane in
            enumerate([0, 0, 0, 1, 1, 2, 3, None])}

    class R:
        def __init__(self, rid, n):
            self.rid, self.prompt, self.out_tokens = rid, [1], [0] * n
    done = [R(i, 100 if i == 1 else 3) for i in recs]
    check = {"served_tokens": 50, "min_lanes": 4, "max_requests": 6}
    got = [rid for rid, _, _ in serve_cell._sample(done, recs, 5, check)]
    assert got[0] == 1
    assert {recs[r].lane for r in got[:4]} == {0, 1, 2, 3}
    assert len(got) == 4
    check["max_requests"] = 2
    assert len(serve_cell._sample(done, recs, 5, check)) == 2


class Fault:
    """Breaks the decode window from the moment the measured window
    opens (the harness calls ``start`` there, as it would a tracer)."""

    def __init__(self):
        self.on = False

    def start(self):
        self.on = True

    def stop(self):
        pass


def _broken(fault: str, vocab: int, switch: Fault):
    orig = engine_mod._shared_paged_loop

    def patched(*args, **kw):
        loop = orig(*args, **kw)

        def step(params, state, sample, prompt_buf):
            if not switch.on:
                return loop(params, state, sample, prompt_buf)
            if fault == "state_unchanged":
                return state, sample
            state, sample = loop(params, state, sample, prompt_buf)
            # every stored token moved by one where the loop writes it
            return state, sample._replace(
                out_buf=(sample.out_buf + 1) % vocab)
        # a traced run reads the scopes of the program the loop wraps
        step.lower = loop.lower
        return step
    return patched


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_broken_decode_window_is_not_correct(fault, monkeypatch):
    switch = Fault()
    monkeypatch.setattr(engine_mod, "_shared_paged_loop",
                        _broken(fault, 256, switch))
    result, compared, _, _ = tiny.run("batch", seconds=2.0, tracer=switch)
    assert result["correct"] is False, compared


def test_control_in_the_programs_place_is_not_correct():
    """The float32 reference with fp8 operands, judged on the same
    prompts and tokens by the same limit, fails it; the program passes."""
    result, compared, _, _ = tiny.run("batch", seed=7, control=True)
    assert result["correct"], compared
    assert result["control"]["correct"] is False, result["control"]
    assert result["control"]["max_logit_gap"] > \
        3 * compared["max_logit_gap"]["value"]
