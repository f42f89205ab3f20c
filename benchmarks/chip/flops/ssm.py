"""Model FLOPs per token of the Mamba2 (SSD) model, forward and backward.

Counted from the configuration's shapes, with nothing recomputed (d the
hidden size, di = expand x d, N the state size, H the heads, Q the
chunk, W the conv width, V the vocabulary):

* matrix products: 6 x the matmul parameters, N_mm = layers x
  (d (2 di + 2 N + H) + di d) + d V: the input and output projections and
  the head; the embedding lookup counts nothing;
* the depthwise conv: 6 x layers x W (di + 2 N);
* the chunked SSD at chunk Q, forward per token and layer: C B^T within
  the chunk 2 Q N (B and C are shared by the heads), the decay-masked
  product with x 2 Q di, the chunk states 2 di N, and their contribution
  to the output 2 di N; times 3 with the backward.  The chunk-to-chunk
  passing of states is O(di N / Q) per token and is left out.
"""

from __future__ import annotations

from typing import Any, Dict


def _dims(cfg):
    d = cfg["hidden_size"]
    di = cfg["expand"] * d
    n, p = cfg["state_size"], cfg["head_dim"]
    return d, di, n, di // p


def matmul_params(cfg: Dict[str, Any]) -> int:
    d, di, n, h = _dims(cfg)
    per_layer = d * (2 * di + 2 * n + h) + di * d
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def flops_per_token(cfg: Dict[str, Any], seq: int) -> Dict[str, float]:
    """FLOPs per token by part, and their ``total``."""
    d, di, n, h = _dims(cfg)
    q, layers = cfg["chunk_size"], cfg["num_hidden_layers"]
    out = {"matmul": 6.0 * matmul_params(cfg),
           "conv": 6.0 * layers * cfg["conv_kernel"] * (di + 2 * n),
           "ssd": 3.0 * layers * (2 * q * n + 2 * q * di + 4 * di * n)}
    out["total"] = sum(out.values())
    return out
