"""The benchmark's traffic generator: the same seed gives the same
schedule, every seed the same work, the drawn lengths and rates are the
mix's, and a new mix is found by name."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import load  # noqa: E402

BIG = 2**40 + 123


# an open-loop mix, as a chat cell would state it in bench/traffic/
CHAT = {"loop": "open", "arrivals": "poisson", "rate_per_s": 0.5,
        "prompt": {"dist": "lognormal", "median": 1024, "sigma": 0.75,
                   "min": 128, "max": 3072},
        "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                   "min": 16, "max": 512},
        "block": 64, "temperature": 0.0}


def kw(seed, max_seq_len=4096):
    return dict(vocab=151936, seed=seed, max_seq_len=max_seq_len)


def mix_of(name):
    return CHAT if name == "chat" else load.load_mix(name)


@pytest.mark.parametrize("name", ["decode", "chat"])
def test_same_seed_same_schedule(name):
    mix = mix_of(name)
    a, b = load.take(mix, 130, **kw(BIG)), load.take(mix, 130, **kw(BIG))
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due_s) == (y.max_new, y.due_s)
    c = load.take(mix, 130, **kw(BIG + 1))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["decode", "chat"])
def test_every_seed_gets_the_same_work(name):
    mix = mix_of(name)
    n = mix["block"]
    for seed in (1, BIG, 2**31 + 5):
        reqs = load.take(mix, n, **kw(seed))
        assert sorted(len(r.prompt) for r in reqs) == \
            sorted(load.quantiles(mix["prompt"], n))
        assert sorted(r.max_new for r in reqs) == \
            sorted(load.quantiles(mix["output"], n))


def test_lengths_and_rate_match_the_mix():
    mix = CHAT
    reqs = load.take(mix, 640, **kw(7))
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    assert p.min() >= 128 and p.max() <= 3072
    assert o.min() >= 16 and o.max() <= 512
    assert abs(np.median(p) - 1024) <= 40
    assert abs(np.median(o) - 128) <= 8
    rate = len(reqs) / reqs[-1].due_s
    assert abs(rate / mix["rate_per_s"] - 1) < 0.05
    assert all(np.all(r.prompt < 151936) for r in reqs)
    dec = load.load_mix("decode")
    reqs = load.take(dec, 64, **kw(7, max_seq_len=2048))
    assert dec["block"] == 16
    assert all(64 <= len(r.prompt) <= 1024 and 256 <= r.max_new <= 1024
               for r in reqs)
    assert abs(np.median([len(r.prompt) for r in reqs]) - 256) <= 16
    assert load.clients(dec, 32) == 40 and load.clients(dec, 128) == 160


def test_new_mix_is_found_by_name(tmp_path):
    mix = {"loop": "open", "arrivals": "bursty", "burst": 8,
           "rate_per_s": 10.0, "block": 16,
           "prompt": {"dist": "uniform", "min": 64, "max": 512},
           "output": {"dist": "uniform", "min": 16, "max": 128}}
    (tmp_path / "chat-burst.json").write_text(json.dumps(mix))
    got = load.load_mix("chat-burst", root=str(tmp_path))
    reqs = load.take(got, 32, **kw(3))
    dues = [r.due_s for r in reqs]
    assert len(set(dues)) == 4                 # four bursts of eight
    assert dues[8] - dues[0] == pytest.approx(0.8)
    with pytest.raises(FileNotFoundError):
        load.load_mix("no-such-mix", root=str(tmp_path))


def test_mix_longer_than_the_cell_is_refused():
    with pytest.raises(ValueError, match="positions"):
        next(load.schedule(CHAT, **kw(1, max_seq_len=2048)))


@pytest.mark.parametrize("n", [15, 40])
def test_caught_requests_are_the_same_work_for_every_seed(n):
    mix = load.load_mix("decode")
    p, o = load.caught(mix, n)
    assert len(p) == len(o) == n
    assert np.all(o >= 1) and np.all(p + o <= 2048)
    assert np.all(p >= mix["prompt"]["min"])
    # a long answer holds its slot longer, so more long ones are caught:
    # what is left of them is more than half the mix's mean answer
    assert np.mean(o) > 0.5 * np.mean(load.quantiles(mix["output"], 1024))
    runs = [load.take(mix, n + 16, in_progress=n, **kw(seed, 2048))
            for seed in (1, BIG)]
    for reqs in runs:
        assert sorted(len(r.prompt) for r in reqs[:n]) == sorted(p)
        assert sorted(r.max_new for r in reqs[:n]) == sorted(o)
        assert sorted(r.max_new for r in reqs[n:]) == \
            sorted(load.quantiles(mix["output"], 16))
    assert [r.max_new for r in runs[0][:n]] != [r.max_new
                                                for r in runs[1][:n]]
