"""Integer operations a configuration needs per token, counted from its
shapes.

Every projection of a decoder layer (q, k, v, o, gate, up, down) and the
tied LM head is one W8A8 product: 2 operations (a multiply and an add) per
weight per token. The count is the same whatever backend implements the
product, exact MXU or emulated multiplier, so a share of the int8 peak
compares backends on one scale. Attention's score and value products run
in floating point and are not counted here.
"""
from __future__ import annotations


def projection_weights(conf: dict) -> int:
    """Weights that one token multiplies, over all projections and the
    head."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    hd = d // conf["num_attention_heads"]
    q = conf["num_attention_heads"] * hd
    kv = conf["num_key_value_heads"] * hd
    layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return conf["num_hidden_layers"] * layer + d * conf["vocab_size"]


def int8_ops_per_token(conf: dict) -> int:
    return 2 * projection_weights(conf)
