"""Seeded traffic from a mix file: arrival times, prompt and output lengths,
token ids.

Every seed offers the same work.  A mix of ``n`` requests takes, for each
quantity, the ``n`` midpoint quantiles of its distribution, at
``(i + 0.5) / n``; the seed draws only their order and the token ids.  So two
seeds give the same multiset of gaps, prompt lengths and output lengths.
Lengths are ordered in rounds that hold one value from each of ``strata``
equal bands of the sorted quantiles, so that every stretch of the queue holds
about the same mix of short and long requests, whatever the seed.

Mix keys (see ``bench/traffic/*.json``):

- ``mode``: ``open_loop`` (requests arrive on a schedule while the window
  runs) or ``backlog`` (every request is queued before it opens);
- ``server``: the served shapes, ``slots`` and ``max_seq``;
- ``arrivals``: ``{"rate_rps": r}``, Poisson gaps at rate ``r`` (open loop);
- ``prompt_tokens`` and ``output_tokens``: a lognormal
  ``{"median", "sigma", "min", "max"}`` truncated to [min, max];
- ``requests``: how many a backlog holds; ``in_flight``: whether the slots
  open the window already busy, their remaining outputs staggered;
- ``strata``: bands for the ordering of lengths (default 8).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

# independent random streams of one seed
_GAPS, _PROMPTS, _OUTPUTS, _TOKENS, _IN_FLIGHT = range(5)


@dataclasses.dataclass
class Req:
    rid: int
    due: float | None           # seconds after the window opens
    prompt: np.ndarray          # int32 token ids
    n_new: int


def rng_for(seed: int, stream: int, *more: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream, *more])


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` midpoint quantiles of a length distribution, ascending."""
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    lo = nd.cdf((math.log(spec["min"]) - mu) / sigma)
    hi = nd.cdf((math.log(spec["max"]) - mu) / sigma)
    u = lo + (np.arange(n) + 0.5) / n * (hi - lo)
    x = np.exp(mu + sigma * np.array([nd.inv_cdf(float(p)) for p in u]))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """The ``n`` midpoint quantiles of an exponential gap at ``rate``."""
    return -np.log1p(-(np.arange(n) + 0.5) / n) / rate


def balanced_order(values: np.ndarray, rng, strata: int) -> np.ndarray:
    """``values`` (ascending) reordered in rounds, each holding one value
    from every one of ``strata`` equal bands, in a random order."""
    n = len(values)
    strata = max(1, min(strata, n))
    bands = [list(rng.permutation(b)) for b in
             np.array_split(np.arange(n), strata)]
    out = []
    while any(bands):
        rnd = [b.pop() for b in bands if b]
        out.extend(rng.permutation(rnd))
    return values[np.asarray(out, np.int64)]


def _lengths(mix: dict, n: int, seed: int):
    strata = mix.get("strata", 8)
    prompts = balanced_order(quantiles(mix["prompt_tokens"], n),
                             rng_for(seed, _PROMPTS), strata)
    outputs = balanced_order(quantiles(mix["output_tokens"], n),
                             rng_for(seed, _OUTPUTS), strata)
    return prompts, outputs


def _fit(prompt: int, n_new: int, max_seq: int) -> int:
    """Outputs stop where the cache ends: a prompt and its output together
    fill at most ``max_seq - 1`` positions."""
    return max(1, min(int(n_new), max_seq - 1 - int(prompt)))


def requests(mix: dict, vocab: int, seconds: float, seed: int) -> list[Req]:
    """The requests of one run of an ``open_loop`` or ``backlog`` mix, in
    the order they are submitted.  A backlog with ``in_flight`` starts with
    one request per slot, the ``j``-th shortest full output cut to a share
    ``(j + 0.5) / slots`` of itself, so that the slots finish at staggered
    times from the moment the window opens, alike for every seed."""
    max_seq = mix["server"]["max_seq"]
    toks = rng_for(seed, _TOKENS)
    out = []

    def add(due, p, o):
        out.append(Req(len(out), due, toks.integers(0, vocab, int(p),
                                                    dtype=np.int32),
                       _fit(p, o, max_seq)))

    if mix["mode"] == "open_loop":
        n = max(1, round(mix["arrivals"]["rate_rps"] * seconds))
        gaps = rng_for(seed, _GAPS).permutation(
            exp_gaps(mix["arrivals"]["rate_rps"], n))
        prompts, outputs = _lengths(mix, n, seed)
        for due, p, o in zip(np.cumsum(gaps), prompts, outputs):
            add(float(due), p, o)
        return out
    if mix["mode"] != "backlog":
        raise ValueError(f"requests() serves open_loop and backlog mixes, "
                         f"not {mix['mode']!r}")
    if mix.get("in_flight"):
        slots = mix["server"]["slots"]
        r = rng_for(seed, _IN_FLIGHT)
        prompts = r.permutation(quantiles(mix["prompt_tokens"], slots))
        full = quantiles(mix["output_tokens"], slots)
        left = [math.ceil(o * (j + 0.5) / slots) for j, o in enumerate(full)]
        for p, o in zip(prompts, r.permutation(left)):
            add(None, p, o)
    prompts, outputs = _lengths(mix, mix["requests"], seed)
    for p, o in zip(prompts, outputs):
        add(None, p, o)
    return out


def prefill_buckets(mix: dict) -> list[int]:
    """Every prompt bucket the mix's prompt lengths can reach: powers of two
    from the shortest prompt's to the longest's, capped at ``max_seq`` as
    the server caps them."""
    spec, cap = mix["prompt_tokens"], mix["server"]["max_seq"]
    lo, hi = spec["min"], spec["max"]
    b = 1 << (int(lo) - 1).bit_length()
    out = []
    while True:
        out.append(min(b, cap))
        if b >= hi or b >= cap:
            return out
        b *= 2
