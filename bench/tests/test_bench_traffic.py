"""The generator gives every seed the same work, in another order."""

import numpy as np
import pytest

import traffic

SEEDS = (1, 2, 3_000_000_123)


def _pairs(items):
    return [(len(it.prompt), it.max_new) for it in items]


def test_same_histogram_other_order():
    mix = traffic.load_mix("batch")
    runs = [traffic.generate(mix, s, 49152, 3072) for s in SEEDS]
    for key in (lambda it: len(it.prompt), lambda it: it.max_new):
        hist = [sorted(key(it) for it in r) for r in runs]
        assert hist[0] == hist[1] == hist[2]
    orders = [[len(it.prompt) for it in r] for r in runs]
    assert orders[0] != orders[1]
    assert not np.array_equal(runs[0][0].prompt[:8], runs[1][0].prompt[:8])


def test_lengths_stay_in_their_clips():
    mix = traffic.load_mix("batch")
    items = traffic.generate(mix, 5, 49152, 3072)
    p = np.array([len(it.prompt) for it in items])
    o = np.array([it.max_new for it in items])
    assert p.min() >= mix["prompt"]["min"] and p.max() <= mix["prompt"]["max"]
    assert o.min() >= mix["output"]["min"] and o.max() <= mix["output"]["max"]
    assert abs(np.median(p) - mix["prompt"]["median"]) <= 0.05 * \
        mix["prompt"]["median"] + 1


def test_offline_mix_is_queued_at_once():
    mix = traffic.load_mix("batch")
    items = traffic.generate(mix, 9, 100, 3072)
    assert len(items) == mix["backlog"]
    assert [it.rid for it in items] == list(range(len(items)))


def test_an_unknown_kind_is_refused():
    with pytest.raises(ValueError):
        traffic.request_count({"kind": "open_loop", "backlog": 4})


@pytest.mark.parametrize("n", [1024, 82])
def test_every_round_holds_one_item_of_each_stratum(n):
    size = 16 if n == 1024 else 10
    strata = np.array_split(np.arange(n), size)
    stratum_of = {int(i): s for s, block in enumerate(strata) for i in block}
    rounds = traffic.pair_rounds(n, size)
    for col in (0, 1):
        got = np.concatenate([r[:, col] for r in rounds])
        assert sorted(got) == list(range(n))
        for r in rounds[:n // size]:
            assert sorted(stratum_of[int(i)] for i in r[:, col]) == \
                list(range(size))


@pytest.mark.parametrize("seeds", [(1, 2), (7, 3_000_000_123)])
def test_each_round_holds_the_same_pairs_for_every_seed(seeds):
    """Round j carries the same (prompt, output) pairs whatever the
    seed, in another order."""
    mix = traffic.load_mix("batch")
    size = mix["round"]
    runs = [traffic.generate(mix, seed, 100, 3072) for seed in seeds]
    for j in range(4):
        a, b = (r[j * size:(j + 1) * size] for r in runs)
        assert sorted(_pairs(a)) == sorted(_pairs(b))
        assert _pairs(a) != _pairs(b)
