"""Operations and bytes of the served model, from a configuration file's
sizes alone, so they count the same work whatever implements it.

Counts are of weights and multiply-adds (2 FLOPs each); norms, biases
and softmax are a rounding error at these widths and are left out of
FLOPs, which keeps every count a lower bound.
"""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _attn_params(d, hq, hk, hd):
    return d * (hq + 2 * hk) * hd + hq * hd * d


def target_layer_params(doc) -> int:
    d, f = doc["hidden_size"], doc["intermediate_size"]
    attn = _attn_params(d, doc["num_attention_heads"],
                        doc["num_key_value_heads"], doc["head_dim"])
    ffn = 2 * d * f + f + d          # up and down, with their biases
    return attn + ffn + 2 * d        # two norm scales


def target_params(doc) -> int:
    d, v = doc["hidden_size"], doc["vocab_size"]
    head = 0 if doc["tie_word_embeddings"] else d * v
    return doc["num_hidden_layers"] * target_layer_params(doc) + v * d \
        + head + d


def target_matmul_params(doc) -> int:
    """Weights a token is multiplied by in one forward pass: every layer's
    projections and the LM head (the embedding is a row lookup)."""
    d, f, v = doc["hidden_size"], doc["intermediate_size"], doc["vocab_size"]
    attn = _attn_params(d, doc["num_attention_heads"],
                        doc["num_key_value_heads"], doc["head_dim"])
    return doc["num_hidden_layers"] * (attn + 2 * d * f) + d * v


def draft_params(doc) -> int:
    """The EAGLE-3 draft: feature fuse, token/feature fc, one decoder
    layer with a SwiGLU FFN, its own head."""
    g = doc["draft"]
    d, f, v = g["hidden_size"], g["intermediate_size"], doc["vocab_size"]
    attn = _attn_params(d, g["num_attention_heads"],
                        g["num_key_value_heads"], g["head_dim"])
    layer = attn + 3 * d * f + 2 * d
    return g["fuse_inputs"] * d * d + 2 * d * d \
        + g["num_hidden_layers"] * layer + d + d * v


def draft_step_params(doc) -> int:
    """Draft weights one propose step reads at least: fc, the layers and
    the head (the feature fuse runs once per round, left out)."""
    g = doc["draft"]
    return draft_params(doc) - g["fuse_inputs"] * g["hidden_size"] ** 2


def weight_bytes(doc, params: int) -> int:
    return params * DTYPE_BYTES[doc["torch_dtype"]]


def kv_bytes_per_token(doc) -> int:
    return (doc["num_hidden_layers"] * 2 * doc["num_key_value_heads"]
            * doc["head_dim"] * DTYPE_BYTES["bfloat16"])


def round_bytes(doc, gamma: int) -> int:
    """Least HBM bytes of one speculative round: the target's matmul
    weights once (one verify block) and the draft's step weights gamma
    times.  Live KV is added by the caller per busy lane."""
    return weight_bytes(doc, target_matmul_params(doc)) \
        + gamma * weight_bytes(doc, draft_step_params(doc))


def token_flops(doc) -> int:
    """Target FLOPs of one token's forward pass (matmuls only)."""
    return 2 * target_matmul_params(doc)
