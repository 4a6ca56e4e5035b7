"""Batched LM serving demo: prefill + KV-cache decode with the serving
engine (continuous-batching bookkeeping, greedy sampling).

    PYTHONPATH=src python examples/serve_lm.py
"""
import numpy as np
import jax

from repro import runtime
from repro.launch.train import small_config
from repro.models import registry
from repro.serve import engine

runtime.enable_compile_cache()
base = registry.load_arch("tinyllama_1_1b")
cfg = small_config(base, d_model=128, layers=2, vocab=512)
params = registry.init_params(jax.random.key(0), cfg)

loop = engine.ServeLoop(cfg, params, batch_size=4, max_len=64)
rng = np.random.default_rng(0)
requests = [
    engine.Request(uid=i, prompt=rng.integers(1, 512, size=n).astype(np.int32),
                   max_new_tokens=8 + 4 * i)
    for i, n in enumerate((5, 9, 3, 7))
]
done = loop.run(requests)
for r in done:
    print(f"request {r.uid}: prompt[{len(r.prompt)}] -> "
          f"{len(r.generated)} tokens: {r.generated}")
assert all(r.done for r in done)
print("serving loop complete")
