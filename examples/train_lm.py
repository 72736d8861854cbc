"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps,
with checkpointing, resume, and the narrow/wide (floo) collective backend.

    PYTHONPATH=src python examples/train_lm.py [--steps 200] [--small]

The --small flag (used by CI) shrinks to ~10M params / 50 steps.
"""
import argparse
import dataclasses

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch, ShapeConfig
from repro.configs.base import MeshConfig, RunConfig
from repro.train.loop import train


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm_ckpt")
    args = ap.parse_args()

    base = get_arch("llama3.2-1b")
    if args.small:
        mcfg = base.smoke(num_layers=4, d_model=256, d_ff=1024,
                          vocab_size=4096, name="lm-10m")
        shape = ShapeConfig("small", seq_len=128, global_batch=8, kind="train")
        steps = min(args.steps, 50)
    else:
        # ~100M params: 12L x d=640, GQA 10/2 heads, 50k vocab
        mcfg = dataclasses.replace(
            base, name="lm-100m", num_layers=12, d_model=640, num_heads=10,
            num_kv_heads=2, head_dim=64, d_ff=2560, vocab_size=50_304,
            tie_embeddings=True)
        shape = ShapeConfig("lm100m", seq_len=256, global_batch=8,
                            kind="train")
        steps = args.steps

    cfg = RunConfig(model=mcfg, shape=shape, mesh=MeshConfig(1, 1, 1),
                    backend="floo", learning_rate=6e-4, microbatches=2)
    print(f"params={mcfg.param_count()/1e6:.1f}M steps={steps} "
          f"tokens/step={shape.tokens}")
    res = train(cfg, num_steps=steps, ckpt_dir=args.ckpt, ckpt_every=50,
                log_every=10)
    w = max(len(res.losses) // 10, 1)
    print(f"loss first10={np.mean(res.losses[:w]):.3f} "
          f"last10={np.mean(res.losses[-w:]):.3f}")
    assert np.mean(res.losses[-w:]) < np.mean(res.losses[:w])
    print("OK")


if __name__ == "__main__":
    main()
