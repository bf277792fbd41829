"""The comparison's own arithmetic: which finished requests are compared,
the verdict beside its limits, and a configuration the program cannot
honour refused before it runs."""
import numpy as np
import pytest

from bench.lib import check, model
from bench.tests import tiny

LIMITS = {"max_logit_gap": 0.25, "min_tokens_compared": 1}


def _served(n, slots):
    return {rid: check.Served(np.zeros(4 + rid, np.int32), [1] * (rid % 5),
                              rid % slots) for rid in range(n)}


def test_sample_holds_the_longest_and_every_slot():
    served = _served(40, 12)
    picked = check.sample(served, 12, 2 ** 31 + 1)
    assert len(picked) == 12
    assert picked[0] is served[39]          # most positions
    assert {p.slot for p in picked} == set(range(12))


def test_sample_is_drawn_from_the_seed():
    served = _served(40, 4)
    ids = lambda seed: [len(p.prompt) for p in check.sample(served, 6, seed)]
    assert ids(5) == ids(5)
    assert ids(5) != ids(6) or ids(5) != ids(7)
    assert len(set(ids(5))) == 6
    assert check.sample({}, 6, 5) == []


@pytest.mark.parametrize("gap,tokens,ids_ok,want", [
    (0.1, 10, True, True),
    (0.25, 10, True, True),
    (0.3, 10, True, False),
    (0.1, 0, True, False),
    (0.1, 10, False, False),
])
def test_verdict_holds_each_number_to_its_limit(gap, tokens, ids_ok, want):
    correct, checks = check.verdict(
        {"max_logit_gap": gap, "tokens_compared": tokens}, ids_ok, LIMITS)
    assert correct is want
    assert checks["max_logit_gap"] == (gap, 0.25)
    assert checks["tokens_compared"] == (tokens, 1)


def test_verdict_without_readings_is_not_correct():
    correct, checks = check.verdict({}, True, LIMITS)
    assert not correct
    assert checks["max_logit_gap"][0] == float("inf")


def test_an_epsilon_the_program_cannot_honour_is_refused():
    assert model.program_config("tiny", tiny.CONFIG).num_layers == 2
    with pytest.raises(ValueError, match="rms_norm_eps"):
        model.program_config("tiny", {**tiny.CONFIG, "rms_norm_eps": 1e-5})
