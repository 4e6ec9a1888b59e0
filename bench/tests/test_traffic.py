"""Every seed gets the same lengths and arrival gaps in another order."""
import numpy as np

from bench import registry, traffic

CHAT = registry.traffic("chat")


def test_open_loop_seeds_share_sizes_and_arrivals():
    a = traffic.open_loop(CHAT, 8.0, 30.0, 1, 1000)
    b = traffic.open_loop(CHAT, 8.0, 30.0, 2**31 + 12345, 1000)
    assert len(a) == len(b) == 240
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert sorted((len(r["prompt"]), r["max_tokens"]) for r in a) == \
        sorted((len(r["prompt"]), r["max_tokens"]) for r in b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert a[-1]["due"] < 30.0
    assert np.all(np.diff([r["due"] for r in a]) > 0)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


def test_lengths_follow_the_mix():
    p, o = traffic.sizes(CHAT, 4000)
    assert p.min() >= 16 and p.max() <= 2048 and o.min() >= 8 \
        and o.max() <= 512
    assert abs(np.median(p) - 200) < 15 and abs(np.median(o) - 128) < 10


def test_same_seed_same_requests():
    batch = registry.traffic("batch")
    a = traffic.requests(batch, 50, 2**33 + 5, 151552)
    b = traffic.requests(batch, 50, 2**33 + 5, 151552)
    c = traffic.requests(batch, 50, 5, 151552)
    assert a == b and a != c
    assert all(2 <= t < 151552 for r in a for t in r["prompt"])


def test_closed_loop_waves_share_lengths():
    batch = registry.traffic("batch")
    a = traffic.requests(batch, 128, 1, 1000, block=64)
    b = traffic.requests(batch, 128, 2**31 + 3, 1000, block=64)
    for lo in (0, 64):
        wa = sorted(r["max_tokens"] for r in a[lo:lo + 64])
        wb = sorted(r["max_tokens"] for r in b[lo:lo + 64])
        assert wa == wb
    assert [r["max_tokens"] for r in a] != [r["max_tokens"] for r in b]
