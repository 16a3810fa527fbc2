"""Integer operations a configuration needs per token, counted from its
shapes.

The configuration's architecture module (``bench/arch/<architectures[0]>
.py``, ``projection_weights``) counts the weights that one token
multiplies, over every projection and the LM head. Each weight is one
W8A8 product per token: 2 operations (a multiply and an add). The count is
the same whatever backend implements the product, exact MXU or emulated
multiplier, so a share of the int8 peak compares backends on one scale.
Attention's score and value products run in floating point and are not
counted here.
"""
from __future__ import annotations

import harness


def int8_ops_per_token(conf: dict) -> int:
    return 2 * harness.arch_module(conf).projection_weights(conf)
