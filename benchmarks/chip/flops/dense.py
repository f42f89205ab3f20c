"""Model FLOPs per token of the dense decoder, forward and backward.

Counted from the configuration's shapes, with nothing recomputed:

* matrix products: 6 x the matmul parameters N (2 for the forward, 4 for
  the backward), N = layers x (4 d^2 + 3 d ff) + d V: the attention
  projections, the SwiGLU MLP and the output head; the embedding lookup
  is a gather and counts nothing;
* causal attention: q k^T and p v are 2 S d each per token and layer over
  the whole S x S, of which causality needs half, so 2 S d forward and
  6 S d with the backward.
"""

from __future__ import annotations

from typing import Any, Dict


def matmul_params(cfg: Dict[str, Any]) -> int:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 4 * d * d + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def flops_per_token(cfg: Dict[str, Any], seq: int) -> Dict[str, float]:
    """FLOPs per token by part, and their ``total``."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    out = {"matmul": 6.0 * matmul_params(cfg),
           "attention": 6.0 * seq * d * layers}
    out["total"] = sum(out.values())
    return out
