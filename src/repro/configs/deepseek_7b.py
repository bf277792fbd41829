"""deepseek-7b — llama-arch dense, MHA (kv=32) [arXiv:2401.02954]."""
from repro.configs.base import ArchSpec
from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-7b", family="dense",
    num_layers=30, d_model=4096, num_heads=32, num_kv_heads=32,
    d_ff=11008, vocab_size=102400,
)

SMOKE = CONFIG.replace(
    name="deepseek-smoke", num_layers=2, d_model=128, num_heads=4,
    num_kv_heads=4, d_ff=256, vocab_size=512,
    param_dtype="float32", compute_dtype="float32",
)

# One-chip cut (TPU v5e, 16 GB HBM, 15.75 GB of it usable by a program).
#   source:   arXiv:2401.02954, Table 2 (DeepSeek LLM 7B): 30 layers,
#             d_model 4096, 32 heads x 128 (MHA), d_ff 11008, vocab 102400.
#   kept:     every published width, and bfloat16 weights and compute.
#   reduced:  num_layers 30 -> 16.  All 30 layers are 13.82 GB of weights;
#             one decode step of 4 slots x 1024 positions then needs
#             16.62 GB (compile-only rehearsal for v5e).  At 16 layers the
#             weights are 8.16 GB, and 4 slots x 1024 positions of cache
#             are 1.07 GB, which leaves room for the decode temporaries
#             (about twice the cache), the prefill output and the
#             admission scatter that is live beside the cache.
#   stands for: a 2-stage pipeline deployment; the 14 absent layers would
#             be the second chip's stage.  With fewer layers per request the
#             host's share of each step is larger than in that deployment.
ONE_CHIP = CONFIG.replace(name="deepseek-7b-1chip", num_layers=16)
ONE_CHIP_SLOTS = 4
ONE_CHIP_MAX_SEQ = 1024

SPEC = ArchSpec(
    arch_id="deepseek-7b", config=CONFIG, smoke=SMOKE,
    source="arXiv:2401.02954 (DeepSeek LLM 7B)",
    long_strategy="window", long_window=4096,
)
