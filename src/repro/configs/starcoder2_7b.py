"""starcoder2-7b [dense] — 32L d_model=4608 36H (GQA kv=4) d_ff=18432
vocab=49152 — GQA, RoPE.  [arXiv:2402.19173]"""
import dataclasses

from repro.models.config import ATTN, FFN_GELU, BlockDef, ModelConfig, reduced

CONFIG = ModelConfig(
    name="starcoder2-7b",
    family="dense",
    citation="arXiv:2402.19173",
    num_layers=32,
    d_model=4608,
    num_heads=36,
    num_kv_heads=4,
    head_dim=128,
    d_ff=18432,
    vocab_size=49152,
    pattern=(BlockDef(ATTN, FFN_GELU),),
    rope_theta=100000.0,
)

REDUCED = reduced(CONFIG, num_heads=4, num_kv_heads=2)

# One TPU v5e (16 GB HBM) serving the target while it trains the EAGLE
# draft beside it.  Every width is published; only depth and weight
# precision are cut, as the layers that are left out would lie on
# further chips as pipeline stages.  The bytes (from param_count()):
#   target, whole:  7.40B params = 29.6 GB in float32 -> cannot fit
#   per layer 217M (0.43 GB bf16); embedding + head 0.45B (0.90 GB bf16)
#   draft (1 layer, own head, 3*d fuse) 0.507B: 1.0 GB bf16 to serve,
#   and under AdamW 6.1 GB to train (two float32 moments, a bf16 update
#   and gradients)
#   8 layers: 4.4 GB target + 1.0 GB draft + 6.1 GB trainer = 11.5 GB
#   (12.6 GB peak measured on a v5e with a 16.9 GB limit), leaving the
#   rest for KV caches and batch; 16 layers would leave under 1 GB.
ONE_CHIP = dataclasses.replace(
    CONFIG, name="starcoder2-7b-8L", num_layers=8, param_dtype="bfloat16")

ONE_CHIP_CUTS = {
    "num_layers": "32 -> 8: the 32-layer target (29.6 GB in float32) "
                  "cannot share one 16 GB chip with draft training",
    "param_dtype": "float32 -> bfloat16: the serving precision; halves "
                   "the target and draft weights",
}
