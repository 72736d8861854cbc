"""Quickstart: train a tiny llama-family model for 20 steps, then generate.

Runs on a single CPU device in ~a minute:
    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch, ShapeConfig
from repro.configs.base import MeshConfig, RunConfig
from repro.serve import Engine
from repro.train.loop import train

enable_compile_cache()

mcfg = get_arch("llama3.2-1b").smoke()           # reduced same-family config
shape = ShapeConfig("quickstart", seq_len=64, global_batch=8, kind="train")
cfg = RunConfig(model=mcfg, shape=shape, mesh=MeshConfig(1, 1, 1),
                learning_rate=1e-3)

print(f"arch={mcfg.name} params={mcfg.param_count()/1e6:.1f}M")
res = train(cfg, num_steps=20, log_every=5)
print(f"loss: {res.losses[0]:.3f} -> {res.final_loss:.3f} "
      f"({res.steps} steps, {np.mean(res.step_times)*1e3:.0f} ms/step)")
assert res.final_loss < res.losses[0], "loss should decrease"

engine = Engine(cfg, max_len=96)
engine.init_params()
out = engine.generate(np.ones((2, 8), np.int32), max_new_tokens=8)
print("generated:", out.tokens)
print("OK")
