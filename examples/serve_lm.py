"""Serving driver: batched prefill + decode with the same serve_step the
multi-pod dry-run lowers for the decode_* shape cells.

    PYTHONPATH=src python examples/serve_lm.py [--arch gemma3-1b]
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.serve import Engine, ServeConfig


def tiny(cfg):
    kw = dict(d_model=256, d_ff=1024, vocab=4096, repeats=4)
    if cfg.n_heads:
        kw.update(n_heads=4, n_kv_heads=min(cfg.n_kv_heads, 2) or 1, head_dim=64)
    if cfg.rnn_width:
        kw.update(rnn_width=256)
    if cfg.n_experts:
        kw.update(n_experts=4, top_k=2)
    pattern = tuple(dataclasses.replace(b, window=64 if b.window else 0)
                    for b in cfg.pattern)
    return dataclasses.replace(cfg, pattern=pattern, tail=(), **kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=48)
    args = ap.parse_args()

    cfg = tiny(get_config(args.arch))
    assert not cfg.encdec, "use whisper-style drivers for enc-dec archs"
    eng = Engine.from_seed(cfg, seed=0, serve_cfg=ServeConfig(max_seq=256))

    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, 32), 1, cfg.vocab)
    t0 = time.perf_counter()
    out = eng.generate(prompts, max_new_tokens=args.new_tokens)
    dt = time.perf_counter() - t0
    assert out.shape == (args.batch, 32 + args.new_tokens)
    assert bool(jnp.all(out[:, :32] == prompts))
    tps = args.batch * args.new_tokens / dt
    print(f"{args.arch} (tiny family config): generated "
          f"{args.batch}x{args.new_tokens} tokens in {dt:.1f}s "
          f"({tps:.0f} tok/s on CPU incl. compile)")
    print("sample token ids:", out[0, 32:48].tolist())


if __name__ == "__main__":
    enable_compile_cache()
    main()
