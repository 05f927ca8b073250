"""Serving launcher: batched greedy decoding with a KV cache.

On this CPU container use ``--preset tiny``; the same ``decode_step`` is
what the decode dry-run shapes lower on the production mesh.

Example:
  python -m repro.launch.serve --arch gemma3-1b --preset tiny \
      --batch 4 --prompt-len 8 --new-tokens 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.nn import (model_template, init_params, init_cache, decode_step,
                          encode_for_decode)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.preset == "tiny":
        cfg = cfg.reduced()

    params = init_params(model_template(cfg), jax.random.PRNGKey(args.seed))
    max_len = args.prompt_len + args.new_tokens
    enc_len = cfg.frontend_tokens if cfg.is_encoder_decoder else 0
    cache = init_cache(cfg, args.batch, max_len, enc_len=enc_len)
    if cfg.is_encoder_decoder:
        fe = jnp.ones((args.batch, cfg.frontend_tokens, cfg.frontend_dim), jnp.float32)
        cache["enc_out"] = encode_for_decode(cfg, params, fe)

    step = jax.jit(lambda p, c, t, i: decode_step(cfg, p, c, t, i))
    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(1, cfg.vocab_size, size=(args.batch, args.prompt_len))

    t0 = time.time()
    tok = jnp.asarray(prompt[:, :1], jnp.int32)
    out_tokens = [np.asarray(tok)]
    for i in range(max_len - 1):
        logits, cache = step(params, cache, tok, jnp.int32(i))
        if i + 1 < args.prompt_len:          # teacher-force the prompt
            tok = jnp.asarray(prompt[:, i + 1 : i + 2], jnp.int32)
        else:
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        out_tokens.append(np.asarray(tok))
    dt = time.time() - t0
    seqs = np.concatenate(out_tokens, axis=1)
    print(f"[serve] {cfg.name}: decoded {args.batch}x{max_len} tokens "
          f"in {dt:.2f}s ({args.batch * max_len / dt:.1f} tok/s on CPU)")
    print("[serve] first sequence:", seqs[0].tolist())


if __name__ == "__main__":
    main()
