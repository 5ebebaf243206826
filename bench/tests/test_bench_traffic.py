"""The traffic generator: seeds reproduce schedules, the libraries of
lengths do not depend on the seed, and the mixes hold the lengths
their files state."""
import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent))

from bench import traffic as TR  # noqa: E402


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


MIXES = ("chat-server", "longprompt-offline")
BIG = 2 ** 31 + 12345


@pytest.mark.parametrize("name", MIXES)
def test_seed_reproduces_its_schedule(name):
    a = TR.schedule(mix(name), 10, BIG, 1000)
    b = TR.schedule(mix(name), 10, BIG, 1000)
    c = TR.schedule(mix(name), 10, BIG + 1, 1000)
    assert [p.arrival_s for p in a] == [p.arrival_s for p in b]
    assert all(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))
    assert [p.max_new_tokens for p in a] == [p.max_new_tokens for p in b]
    assert any(not np.array_equal(p.prompt, q.prompt)
               for p, q in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_sends_the_library_lengths(name):
    m = mix(name)
    lib_p = TR.quantile_lengths(m["prompt"], m["library"])
    lib_o = TR.quantile_lengths(m["output"], m["library"])
    warm = {len(p.prompt) for p in TR.warm_up_requests(m, 1000)}
    assert warm == set(lib_p)
    seconds = 2 * m["library"] / m["rate_per_s"]   # two whole blocks
    for seed in (0, 7, BIG):
        plan = TR.schedule(m, seconds, seed, 1000)
        assert len(plan) == 2 * m["library"]
        assert Counter(len(p.prompt) for p in plan) == \
            Counter(lib_p * 2)
        assert Counter(p.max_new_tokens for p in plan) == \
            Counter(lib_o * 2)


@pytest.mark.parametrize("name", MIXES)
def test_a_partial_block_sends_the_same_lengths_under_every_seed(name):
    m = mix(name)
    seconds = 1.5 * m["library"] / m["rate_per_s"]
    got = []
    for seed in (1, 2, BIG):
        plan = TR.schedule(m, seconds, seed, 1000)
        got.append((sorted(len(p.prompt) for p in plan),
                    sorted(p.max_new_tokens for p in plan)))
    assert len(plan) == round(1.5 * m["library"])
    assert got[0] == got[1] == got[2]


def test_spread_entries():
    assert list(TR.spread_entries(32, 32)) == list(range(32))
    assert list(TR.spread_entries(32, 4)) == [4, 12, 20, 28]
    assert list(TR.spread_entries(32, 1)) == [16]


# (median, min, max) of prompts and outputs, as the cells' table states
TABLE = {
    "chat-server": ((1024, 64, 1536), (128, 16, 512)),
    "longprompt-offline": ((1536, 512, 1984), (16, 4, 64)),
}


@pytest.mark.parametrize("name", MIXES)
def test_quantiles_and_clips_match_the_table(name):
    m = mix(name)
    for key, (median, lo, hi) in zip(("prompt", "output"), TABLE[name]):
        d = m[key]
        assert (d["median"], d["min"], d["max"]) == (median, lo, hi)
        lib = TR.quantile_lengths(d, 32)
        assert lib == sorted(lib) and lo <= lib[0] and lib[-1] == hi
        assert lib[15] <= median <= lib[16]
    assert m["library"] == 32


def test_quantiles_by_hand():
    d = {"median": 100, "sigma": 1.0, "min": 1, "max": 10 ** 6}
    # quantiles 0.25 and 0.75 of 2: exp(-+0.6745) * 100
    assert TR.quantile_lengths(d, 2) == [51, 196]


def test_server_arrivals_span_the_window_at_the_rate():
    m = mix("chat-server")

    def gaps(seed):
        t = np.asarray([p.arrival_s for p in TR.schedule(m, 20, seed,
                                                         1000)])
        assert t[0] == 0 and np.all(np.diff(t) >= 0) and t[-1] < 20
        return np.concatenate([np.diff(t), [20 - t[-1]]])

    g1, g2 = gaps(3), gaps(4)
    assert len(g1) == round(m["rate_per_s"] * 20)
    assert np.isclose(g1.sum(), 20)
    # the same set of gaps under every seed, in another order
    assert np.allclose(np.sort(g1), np.sort(g2))
    assert not np.allclose(g1, g2)


def test_offline_requests_are_all_due_at_once():
    m = mix("longprompt-offline")
    plan = TR.schedule(m, 5, 1, 1000)
    assert len(plan) == round(m["rate_per_s"] * 5)
    assert all(p.arrival_s == 0.0 for p in plan)
    assert all(len(p.prompt) + p.max_new_tokens <= 2048 for p in plan)
