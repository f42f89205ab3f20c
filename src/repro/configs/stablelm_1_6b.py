"""StableLM 2 1.6B — dense decoder, MHA (kv=32).

24L d_model=2048 32H (GQA kv=32) d_ff=5632 vocab=100352.
[hf:stabilityai/stablelm-2-1_6b]

Two registrations:

* ``stablelm-2-1.6b`` — the published model: LayerNorm with bias (eps
  1e-5), rotary over the leading quarter of each head
  (``partial_rotary_factor`` 0.25: 16 of 64 dims), q/k/v biases.
* ``stablelm-1.6b`` — the repository's variant at the same widths:
  RMSNorm with the scale as (1 + w), rotary over the whole head, no q/k/v
  bias.  The one-chip benchmark cell ``stablelm-1.6b-l4.seq2k`` runs it.
"""
import dataclasses

from repro.configs.base import ArchConfig, ArchType, AttnKind, register_arch

STABLELM_1_6B = register_arch(ArchConfig(
    name="stablelm-1.6b",
    arch_type=ArchType.DENSE,
    source="hf:stabilityai/stablelm-2-1_6b",
    n_layers=24,
    d_model=2048,
    n_heads=32,
    n_kv_heads=32,
    head_dim=64,
    d_ff=5632,
    vocab_size=100352,
    attn_kind=AttnKind.FULL,
    mlp_kind="swiglu",
))

STABLELM_2_1_6B = register_arch(dataclasses.replace(
    STABLELM_1_6B,
    name="stablelm-2-1.6b",
    norm_kind="layernorm",
    norm_eps=1e-5,
    rope_fraction=0.25,
    qkv_bias=True,
))
