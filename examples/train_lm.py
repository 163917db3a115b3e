"""End-to-end driver: train a ~60M-param LM (of the ~100M class) for a
few hundred steps on CPU,
with MCFlash-backed bitmap data filtering, fault-tolerant checkpointing
(kill it mid-run and restart — it resumes), and XOR-delta incremental
checkpoints.

    PYTHONPATH=src python examples/train_lm.py [--steps 300] [--arch qwen3-1.7b]

The arch flag picks the *family*; dimensions are scaled to ~100M params so
a few hundred steps run on a laptop CPU.  Loss drops visibly (the synthetic
corpus has learnable bigram structure).
"""
import argparse
import dataclasses
import shutil

import numpy as np

from repro.checkpoint import delta_encode, delta_sparsity  # noqa: F401
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.data import BitmapFilter
from repro.optim import AdamWConfig
from repro.train.loop import LoopConfig, TrainLoop


def scale_to_100m(cfg):
    """Keep the family, shrink to ~100M params."""
    kw = dict(d_model=768, d_ff=2048, vocab=16384,
              repeats=min(cfg.repeats, 8))
    if cfg.n_heads:
        kw.update(n_heads=8, n_kv_heads=min(cfg.n_kv_heads, 4) or 1, head_dim=64)
    if cfg.rnn_width:
        kw.update(rnn_width=512)
    if cfg.n_experts:
        kw.update(n_experts=4, top_k=2)
    pattern = tuple(dataclasses.replace(b, window=128 if b.window else 0)
                    for b in cfg.pattern)
    tail = ()
    return dataclasses.replace(cfg, pattern=pattern, tail=tail, **kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--fresh", action="store_true")
    args = ap.parse_args()
    if args.fresh:
        shutil.rmtree(args.ckpt_dir, ignore_errors=True)

    cfg = scale_to_100m(get_config(args.arch))
    from repro.models.specs import count_params
    from repro.models import lm as lm_mod
    n = count_params(lm_mod.build_specs(cfg))
    print(f"arch family {args.arch} scaled to {n/1e6:.0f}M params")

    # MCFlash-filtered data: quality x dedup bitmaps ANDed in-flash select
    # which corpus shards this run trains on.
    rng = np.random.default_rng(0)
    n_shards = 131072
    bf = BitmapFilter(n_shards)
    bf.add_pair("quality", (rng.random(n_shards) < 0.95).astype(np.uint8),
                "dedup", (rng.random(n_shards) < 0.98).astype(np.uint8))
    kept = bf.count([("quality", "dedup")])
    print(f"MCFlash bitmap filter kept {kept}/{n_shards} corpus shards "
          f"({bf.device.ledger.commands} flash commands)")

    loop = TrainLoop(
        cfg,
        LoopConfig(total_steps=args.steps, ckpt_every=100,
                   ckpt_dir=args.ckpt_dir, log_every=20),
        opt_cfg=AdamWConfig(lr=1e-3, warmup_steps=30, total_steps=args.steps),
        global_batch=4, seq_len=256)
    loop.install_preemption_handler()
    result = loop.run()

    losses = [m["loss"] for m in result["metrics"] if "loss" in m]
    print(f"\nloss: first10={np.mean(losses[:10]):.3f}  "
          f"last10={np.mean(losses[-10:]):.3f}  "
          f"(dropped {np.mean(losses[:10]) - np.mean(losses[-10:]):.3f})")

    # XOR-delta incremental checkpoint demo: encode the delta between the
    # current params and a later state, reconstruct BIT-EXACTLY (the op an
    # MCFlash SSD executes in-flash at restore time).
    import numpy as np_
    from repro.checkpoint import delta_apply
    # demo on the embedding table (the interpret-mode Pallas XOR kernel is
    # python-speed on CPU; on TPU the full tree streams through the SSD)
    base = {"embed": result["params"]["embed"]}
    later = {"embed": base["embed"] * (1 + 1e-3)}
    d = delta_encode(base, later)
    rec = delta_apply(base, d)
    exact = np_.array_equal(np_.asarray(rec["embed"]), np_.asarray(later["embed"]))
    print(f"XOR-delta checkpoint reconstruct (embed table): bit-exact={exact} "
          f"(zero-word sparsity {delta_sparsity(d):.3f})")


if __name__ == "__main__":
    enable_compile_cache()
    main()
