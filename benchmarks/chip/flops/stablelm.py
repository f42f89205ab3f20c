"""Model FLOPs per token of StableLM 2: the dense decoder's count
(``flops/dense.py``): 6 N + 6 S d L, N = L (4 d^2 + 3 d ff) + d V.

What the published model adds to the dense decoder costs under 0.01 % of
that and is not counted: the q/k/v biases are 3 d additions a layer and
token, and the rotary of 16 of each head's 64 dims a few operations per
rotated dim (about 1.5e5 FLOPs a token each over 24 layers, against 9.24
GFLOPs a token at seq 2048); LayerNorm, like RMSNorm, is no matrix
product.
"""

from __future__ import annotations

from flops.dense import flops_per_token, matmul_params  # noqa: F401
