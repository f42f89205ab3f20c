"""Token blocks for the training cells, from one general generator.

A traffic mix is a JSON file under ``traffic/`` (see ``registry.py``):
the sequence length, the plan that lays each block out over the chips,
the number of distinct blocks in the pool, and the generator's
parameters.  The generator is a bigram stream with a fixed number of
likely successors per token and a share of uniform noise, deterministic
in the seed (a copy of ``repro.data.pipeline.SyntheticStream``, kept here
so that no change to the program changes the traffic).

The pool is made once during set-up, all rows at once, and the window
cycles through it; every row of the pool differs from every other.
"""

from __future__ import annotations

import numpy as np


def bigram_rows(seed: int, vocab: int, seq: int, n_rows: int,
                successors: int, noise: float) -> np.ndarray:
    """(n_rows, seq + 1) int32 tokens, deterministic in ``seed``.

    Each token is followed by one of ``successors`` fixed successors,
    drawn uniformly, or with probability ``noise`` by a uniform token."""
    s = seed % (1 << 64)
    rng = np.random.default_rng([s & 0xFFFFFFFF, s >> 32, 1])
    succ = rng.integers(0, vocab, size=(vocab, successors), dtype=np.int32)
    out = np.empty((n_rows, seq + 1), np.int32)
    tok = rng.integers(0, vocab, size=n_rows, dtype=np.int32)
    out[:, 0] = tok
    for t in range(1, seq + 1):
        choice = rng.integers(0, successors, size=n_rows)
        rand_tok = rng.integers(0, vocab, size=n_rows, dtype=np.int32)
        nxt = succ[tok, choice]
        tok = np.where(rng.random(n_rows) < noise, rand_tok, nxt)
        out[:, t] = tok
    return out


def global_batch(mix: dict) -> int:
    """Real rows per step: the sum over ranks of ell x m."""
    return sum(r["ell"] * r["m"] for r in mix["ranks"])


def make_pool(mix: dict, vocab: int, seed: int) -> np.ndarray:
    """(pool_blocks, global_batch, seq + 1) token blocks for one run."""
    b, n = global_batch(mix), mix["pool_blocks"]
    gen = mix["generator"]
    rows = bigram_rows(seed, vocab, mix["seq"], n * b,
                       gen["successors"], gen["noise"])
    return rows.reshape(n, b, mix["seq"] + 1)
