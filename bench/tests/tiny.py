"""Small cells for the CPU tests: every width tiny, the harness whole."""
from bench.lib import manifest

CONFIG = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "torch_dtype": "bfloat16", "reference": "llama",
}

MIXES = {
    "open_loop": {
        "mode": "open_loop", "server": {"slots": 4, "max_seq": 64},
        "arrivals": {"rate_rps": 20.0},
        "prompt_tokens": {"median": 12, "sigma": 0.6, "min": 4, "max": 40},
        "output_tokens": {"median": 6, "sigma": 0.6, "min": 2, "max": 16},
        "strata": 4, "check": {"requests": 4}},
    "backlog": {
        "mode": "backlog", "server": {"slots": 4, "max_seq": 64},
        "requests": 16, "in_flight": True,
        "prompt_tokens": {"median": 12, "sigma": 0.6, "min": 4, "max": 40},
        "output_tokens": {"median": 10, "sigma": 0.6, "min": 4, "max": 20},
        "strata": 4, "check": {"requests": 2}}
}

E2E = {"open_loop": ["ttft_p90_ms", "tpot_p90_ms", "setup_s"],
       "backlog": ["tokens_per_s", "setup_s"]}

LIMITS = {"max_logit_gap": 0.05, "min_tokens_compared": 1}


def cell(mode: str, limits: dict | None = None, **config) -> manifest.Cell:
    return manifest.Cell(
        name=f"tiny.{mode}", chips=1, config_name="tiny",
        config={**CONFIG, **config}, traffic=mode, mix=MIXES[mode],
        limits=limits or LIMITS,
        end_to_end=[{"name": n, "unit": "x"} for n in E2E[mode]],
        per_layer=[])
