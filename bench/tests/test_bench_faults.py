"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole of ``run_cell`` but the look for a chip, on a
small configuration on the CPU, with one fault planted in the program:
a decode step that returns its cache unchanged, half of the batch left out
of the step, a token altered where it is produced.  The sound run beside
them comes out correct."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench.lib import harness
from bench.tests import tiny


def _run(mode="open_loop"):
    out = harness.run_cell(tiny.cell(mode), 2 ** 31 + 99, 0.6, False,
                           jax.devices()[:1], time.perf_counter(),
                           "/nonexistent")
    assert out["checks"]["tokens_compared"]["value"] > 0
    return out


@pytest.mark.parametrize("mode", ["open_loop", "backlog"])
def test_sound_run_is_correct(mode):
    out = _run(mode)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}


def _decode_fault(monkeypatch, fault):
    from repro.models import api
    own = api.decode_step

    def broken(params, cache, token, pos, cfg):
        logits, new = own(params, cache, token, pos, cfg)
        return fault(logits, cache, new)
    monkeypatch.setattr(api, "decode_step", broken)


def test_state_left_unchanged_is_caught(monkeypatch):
    _decode_fault(monkeypatch, lambda logits, old, new: (logits, old))
    out = _run()
    assert not out["correct"]


def test_half_the_batch_left_out_is_caught(monkeypatch):
    def half(logits, old, new):
        b = logits.shape[0] // 2
        return jnp.concatenate([logits[:b], logits[:b]]), new
    _decode_fault(monkeypatch, half)
    out = _run("backlog")
    assert not out["correct"]


def test_altered_token_is_caught(monkeypatch):
    from repro.serving import continuous
    own = continuous.pin_logits
    monkeypatch.setattr(continuous, "pin_logits",
                        lambda logits: jnp.roll(own(logits), 1, axis=-1))
    out = _run()
    assert not out["correct"]
