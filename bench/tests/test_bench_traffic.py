"""Traffic is a function of the seed, and every seed offers the same work."""
import numpy as np

from bench.lib import traffic

CHAT = {"mode": "open_loop", "server": {"slots": 8, "max_seq": 512},
        "arrivals": {"rate_rps": 3.0},
        "prompt_tokens": {"median": 128, "sigma": 0.7, "min": 32, "max": 384},
        "output_tokens": {"median": 40, "sigma": 0.7, "min": 8, "max": 128},
        "strata": 8}
LONG = {"mode": "backlog", "server": {"slots": 8, "max_seq": 4096},
        "requests": 64, "in_flight": True,
        "prompt_tokens": {"median": 384, "sigma": 0.6, "min": 128,
                          "max": 1024},
        "output_tokens": {"median": 950, "sigma": 0.6, "min": 512,
                          "max": 3072}, "strata": 8}
BIG = 2 ** 31 + 12345


def _key(reqs):
    return [(r.due, r.prompt.tolist(), r.n_new) for r in reqs]


def test_same_seed_same_traffic():
    for mix in (CHAT, LONG):
        assert _key(traffic.requests(mix, 1000, 50, BIG)) == \
            _key(traffic.requests(mix, 1000, 50, BIG))


def test_seeds_differ_in_order_only():
    a = traffic.requests(CHAT, 1000, 50, BIG)
    b = traffic.requests(CHAT, 1000, 50, 7)
    assert _key(a) != _key(b)
    assert len(a) == len(b) == 150
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.n_new for r in a) == sorted(r.n_new for r in b)
    gaps = lambda rs: sorted(np.diff([0.0] + [r.due for r in rs]).round(9))
    assert gaps(a) == gaps(b)
    assert a[-1].due < 50


def test_lengths_stay_in_range_and_fit_the_cache():
    for r in traffic.requests(CHAT, 1000, 50, 5):
        assert 32 <= len(r.prompt) <= 384 and 1 <= r.n_new <= 128
        assert len(r.prompt) + r.n_new <= 511
        assert r.prompt.min() >= 0 and r.prompt.max() < 1000
    for r in traffic.requests(LONG, 1000, 50, 5):
        assert len(r.prompt) + r.n_new <= 4095


def test_balanced_order_spreads_every_band():
    vals = np.arange(64)
    out = traffic.balanced_order(vals, np.random.default_rng(1), 8)
    assert sorted(out) == list(vals)
    for k in range(8):      # each round of 8 holds one value of each band
        assert sorted(v // 8 for v in out[8 * k:8 * k + 8]) == list(range(8))


def test_in_flight_requests_are_staggered_alike_for_every_seed():
    a = traffic.requests(LONG, 1000, 50, 1)[:8]
    b = traffic.requests(LONG, 1000, 50, 2)[:8]
    assert sorted(r.n_new for r in a) == sorted(r.n_new for r in b)
    assert len({r.n_new for r in a}) == 8


def test_prefill_buckets():
    assert traffic.prefill_buckets(CHAT) == [32, 64, 128, 256, 512]
    assert traffic.prefill_buckets(LONG) == [128, 256, 512, 1024]
