"""The breakdown tool drives the harness's own traced run on a small
configuration on the CPU, and adds the server's counters over the window."""
import jax

from bench.lib import serve, trace
from bench.tests import tiny
from bench.tools import breakdown


def test_breakdown_adds_the_window_counters(tmp_path):
    own = serve.serve, trace.read_xplane
    out = breakdown.traced(tiny.cell("backlog"), 2 ** 31 + 99, 0.6,
                           jax.devices()[:1], str(tmp_path / "trace"))
    assert (serve.serve, trace.read_xplane) == own    # hooks taken off
    assert out["correct"], out["checks"]
    c = out["counters"]
    assert set(c) == {"prompt_tokens", "prefill_tokens", "decode_steps"}
    assert c["decode_steps"] > 0
    assert 0 < c["prompt_tokens"] <= c["prefill_tokens"]
    assert 0 <= out["prefill_pad_share"] < 100
    # the CPU's trace has no device plane, so nothing to break down
    assert out["inside"] is None
