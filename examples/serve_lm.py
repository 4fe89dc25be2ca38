"""Serving example: batched requests with continuous batching.

Submits a burst of ragged-length prompts against a small model and drives
the slot-based engine until drain, printing per-request outputs and
aggregate throughput.

    PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import numpy as np

from repro import configs, gcv
from repro.models.transformer import init_lm
from repro.serve import ServeEngine


def main():
    cfg = configs.get_smoke("qwen3-0.6b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, slots=4, max_len=128)
    rng = np.random.default_rng(0)

    t0 = time.time()
    reqs = [eng.submit(rng.integers(0, cfg.vocab,
                                    size=int(rng.integers(4, 40))),
                       max_new=16)
            for _ in range(10)]
    eng.run()
    dt = time.time() - t0
    for r in reqs:
        print(f"req {r.rid}: prompt_len={len(r.prompt)} -> {r.out}")
    n_tok = sum(len(r.out) for r in reqs)
    print(f"\n{len(reqs)} requests, {n_tok} tokens in {dt:.1f}s "
          f"({n_tok/dt:.1f} tok/s incl. compiles)")


if __name__ == "__main__":
    gcv.use_compile_cache()
    main()
