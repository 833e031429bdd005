"""What the serving engine lets a profiler and a benchmark see.

* **Prefill tokens** — what a benchmark reads as the tokens prefilled
  (each admitted prompt less its last token, in the paged engine) is
  what the engine's chunks fed.
* **Spans** — under a profiler trace the engine's host work shows as
  ``serve.*`` spans on the host plane.
* **Scopes** — the optimized HLO of the programs the engine dispatched
  carries the model's named scopes in each instruction's ``op_name``.
"""

import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model_zoo as zoo
from repro.serving.engine import Request, ServingEngine

PAGED_SCOPES = ("qkv", "kv_write", "attention", "attn_out", "mlp",
                "logits", "sample")


@pytest.fixture(scope="module")
def model():
    cfg = get_config("granite-8b").reduced()
    return cfg, zoo.init_state(cfg, jax.random.PRNGKey(0)).params


def _requests(n, seed, plen, max_new):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(
        1, 250, rng.integers(*plen)).astype(np.int32),
        max_new_tokens=int(rng.integers(*max_new))) for i in range(n)]


def _engine(cfg, params, mode="paged"):
    kw = dict(block_size=8) if mode == "paged" else {}
    return ServingEngine(cfg, params, batch_size=2, max_seq=96,
                         prefill_buckets=(16, 64), cache_mode=mode, **kw)


def _drive(eng, reqs, k=4):
    """Submit ``reqs`` and step until idle; returns each step_many's
    stats."""
    for r in reqs:
        eng.submit(r)
    calls = []
    while eng.n_active or eng.n_queued:
        calls.append(eng.step_many(k))
    return calls


@pytest.mark.parametrize("mode", ["paged", "dense"])
def test_chunk_tokens_count_the_prompt_tokens_prefilled(model, mode):
    cfg, params = model
    eng = _engine(cfg, params, mode)
    # prompts up to 90 tokens: several need two chunks (64 + 16) in the
    # paged engine; the dense engine prefills one chunk and streams the
    # rest through the decode loop
    reqs = _requests(5, seed=3, plen=(5, 90), max_new=(2, 9))
    calls = _drive(eng, reqs)
    fed = sum(c["chunk_tokens"] for c in calls)
    if mode == "paged":
        assert fed == sum(len(r.prompt) - 1 for r in reqs)
        assert eng.chunk_prefills > len(reqs)
    else:
        assert fed == sum(eng._pick_chunk(len(r.prompt) - 1)[1]
                          for r in reqs)
    assert all(r.done for r in reqs)


def _host_event_names(log_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            names.update(e.name for e in line.events)
    return names


def test_engine_spans_land_on_the_host_plane(model, tmp_path):
    cfg, params = model
    eng = _engine(cfg, params)
    warm = _requests(2, seed=5, plen=(20, 30), max_new=(3, 4))
    _drive(eng, warm)                      # compile outside the trace
    reqs = _requests(3, seed=6, plen=(20, 30), max_new=(3, 6))
    with jax.profiler.trace(str(tmp_path)):
        _drive(eng, reqs)
    names = _host_event_names(str(tmp_path))
    for span in ("serve.admit", "serve.prefill", "serve.decode_dispatch",
                 "serve.poll", "serve.host_sync"):
        assert span in names, sorted(n for n in names if "serve" in n)


def _scopes_in(hlo: str):
    found = set()
    for line in hlo.splitlines():
        if 'op_name="' not in line:
            continue
        path = line.split('op_name="', 1)[1].split('"', 1)[0].split("/")
        found.update(p for p in path if p in PAGED_SCOPES)
    return found


class _Compiles:
    """Counts backend compiles while armed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.armed, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event == self.EVENT:
            self.n += 1


def test_program_hlo_carries_the_model_scopes(model):
    cfg, params = model
    eng = _engine(cfg, params)
    assert eng.program_hlo() == {}
    # a 70-token prompt takes a first chunk of 64 and a second with history
    reqs = [Request(0, np.arange(1, 71, dtype=np.int32), 3),
            Request(1, np.arange(1, 11, dtype=np.int32), 3)]
    _drive(eng, reqs)
    compiles = _Compiles()
    compiles.armed = True
    hlo = eng.program_hlo()
    compiles.armed = False
    # lowered with the engine's own arguments: jit hands back what ran
    assert compiles.n == 0
    assert set(hlo) == {"decode_loop.k4", "paged_prefill.c64.first",
                        "paged_prefill.c16", "paged_prefill.c16.first"}
    assert all(h.startswith("HloModule ") for h in hlo.values())
    assert _scopes_in(hlo["decode_loop.k4"]) == set(PAGED_SCOPES)
    for name in ("paged_prefill.c64.first", "paged_prefill.c16"):
        assert _scopes_in(hlo[name]) == {"qkv", "kv_write", "attention",
                                         "attn_out", "mlp"}, name


def test_resize_forgets_programs_of_the_old_geometry(model):
    cfg, params = model
    eng = _engine(cfg, params)
    _drive(eng, _requests(2, seed=8, plen=(5, 12), max_new=(2, 4)))
    assert eng.program_hlo()
    eng.resize(batch_size=3)
    assert eng.program_hlo() == {}
