"""FLOP and byte counts from a configuration's sizes, against hand
counts for StarCoder2-7B at 8 layers and StarCoder2-15B at 12."""
import json
import os

import pytest

from bench import shapes

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")

SC2_15B_12L = {
    "hidden_size": 6144, "intermediate_size": 24576,
    "num_attention_heads": 48, "num_key_value_heads": 4, "head_dim": 128,
    "num_hidden_layers": 12, "vocab_size": 49152,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "draft": {"num_hidden_layers": 1, "hidden_size": 6144,
              "num_attention_heads": 48, "num_key_value_heads": 4,
              "head_dim": 128, "intermediate_size": 12288,
              "fuse_inputs": 3}}


def _sc2_7b():
    with open(os.path.join(CONFIGS, "starcoder2-7b-8L.json")) as f:
        return json.load(f)


# hand counts: attention d*(hq + 2 hk)*hd + hq*hd*d; GELU FFN 2*d*f plus
# biases f + d; two norm scales of d
HAND = {
    "7b": {"layer": 4608 * 44 * 128 + 36 * 128 * 4608
           + 2 * 4608 * 18432 + 18432 + 4608 + 2 * 4608,
           "layers": 8, "d": 4608, "f": 18432, "v": 49152, "kvh": 4,
           "draft_f": 9216, "draft_attn": 4608 * 44 * 128 + 36 * 128 * 4608},
    "15b": {"layer": 6144 * 56 * 128 + 48 * 128 * 6144
            + 2 * 6144 * 24576 + 24576 + 6144 + 2 * 6144,
            "layers": 12, "d": 6144, "f": 24576, "v": 49152, "kvh": 4,
            "draft_f": 12288,
            "draft_attn": 6144 * 56 * 128 + 48 * 128 * 6144},
}


@pytest.mark.parametrize("name", ["7b", "15b"])
def test_counts_match_hand(name):
    doc = _sc2_7b() if name == "7b" else SC2_15B_12L
    h = HAND[name]
    d, v, L = h["d"], h["v"], h["layers"]
    assert shapes.target_layer_params(doc) == h["layer"]
    assert shapes.target_params(doc) == L * h["layer"] + 2 * v * d + d
    attn = h["layer"] - (2 * d * h["f"] + h["f"] + d + 2 * d)
    assert shapes.target_matmul_params(doc) == L * (attn + 2 * d * h["f"]) \
        + d * v
    assert shapes.token_flops(doc) == 2 * shapes.target_matmul_params(doc)
    draft_layer = h["draft_attn"] + 3 * d * h["draft_f"] + 2 * d
    assert shapes.draft_params(doc) == 3 * d * d + 2 * d * d \
        + draft_layer + d + d * v
    assert shapes.draft_step_params(doc) == shapes.draft_params(doc) \
        - 3 * d * d
    assert shapes.kv_bytes_per_token(doc) == L * 2 * h["kvh"] * 128 * 2
    assert shapes.round_bytes(doc, 3) == 2 * (
        shapes.target_matmul_params(doc)
        + 3 * shapes.draft_step_params(doc))


def test_7b_magnitudes():
    doc = _sc2_7b()
    # 217.1M a layer (the program's own count, configs/starcoder2_7b.py)
    assert shapes.target_layer_params(doc) == 217_087_488
    # 1.963B matmul weights a token, 3.93 GB in bf16
    assert shapes.target_matmul_params(doc) == 1_962_934_272
