"""Serve a small model with batched requests (prefill + decode engine).

    PYTHONPATH=src python examples/serve_lm.py
"""
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch, ShapeConfig
from repro.configs.base import MeshConfig, RunConfig
from repro.serve import Engine

enable_compile_cache()

mcfg = get_arch("llama3.2-1b").smoke(num_layers=4, d_model=256, d_ff=1024,
                                     vocab_size=8192, name="serve-demo")
shape = ShapeConfig("serve", seq_len=64, global_batch=8, kind="prefill")
cfg = RunConfig(model=mcfg, shape=shape, mesh=MeshConfig(1, 1, 1))

engine = Engine(cfg, max_len=128)
engine.init_params()

B, S = 8, 32
prompts = np.random.default_rng(0).integers(0, mcfg.vocab_size, (B, S),
                                            dtype=np.int32)
t0 = time.perf_counter()
out = engine.generate(prompts, max_new_tokens=16, greedy=True)
dt = time.perf_counter() - t0
print(f"batch={B} prompt={S} new=16 tokens in {dt:.2f}s "
      f"({B*out.steps/dt:.1f} tok/s)")
print("first row:", out.tokens[0])

# temperature sampling path
out2 = engine.generate(prompts, max_new_tokens=8, greedy=False,
                       temperature=0.8, seed=1)
print("sampled :", out2.tokens[0])
print("OK")
