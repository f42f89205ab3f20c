"""Serving launcher: batched prefill + greedy decode on the local devices.

Example (CPU, reduced model)::

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
        --batch 4 --prompt-len 64 --gen 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if not cfg.has_decode:
        raise SystemExit(f"{cfg.name} is encoder-only; no decode step")
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    max_len = args.prompt_len + args.gen
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)

    prefill = jax.jit(lambda p, t: M.prefill(cfg, p, t, max_len=max_len))
    decode = jax.jit(lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos))

    t0 = time.perf_counter()
    logits, caches = prefill(params, jnp.asarray(prompts))
    next_tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    print(f"prefill: {args.batch}x{args.prompt_len} in "
          f"{time.perf_counter() - t0:.2f}s")

    out_tokens = [next_tok]
    t1 = time.perf_counter()
    for i in range(args.gen - 1):
        pos = jnp.full((args.batch,), args.prompt_len + i, jnp.int32)
        logits, caches = decode(params, caches, next_tok, pos)
        next_tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        out_tokens.append(next_tok)
    dt = time.perf_counter() - t1
    gen = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    print(f"decode: {args.gen - 1} steps x {args.batch} seqs in {dt:.2f}s "
          f"({(args.gen - 1) * args.batch / max(dt, 1e-9):.1f} tok/s)")
    for b in range(min(args.batch, 2)):
        print(f"  seq{b}: ...{prompts[b, -8:].tolist()} => "
              f"{gen[b].tolist()}")


if __name__ == "__main__":
    main()
