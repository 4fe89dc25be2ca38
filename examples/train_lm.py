"""End-to-end driver: train a ~100M-param LM for a few hundred steps.

Uses the full substrate — config registry, deterministic data pipeline,
AdamW + cosine schedule, checkpointing every 100 steps, crash-resume.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import dataclasses

from repro import configs, gcv
from repro.launch.train import train
from repro.models.config import ModelConfig


def model_100m() -> ModelConfig:
    # ~100M params: a scaled-down llama3-style decoder
    base = configs.get("llama3.2-1b")
    return dataclasses.replace(
        base, name="llama-100m", n_layers=8, d_model=640, n_heads=10,
        n_kv_heads=2, d_ff=1792, head_dim=64, dtype="float32")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = model_100m()
    print(f"model: {cfg.name}  params={cfg.params_count()/1e6:.1f}M")
    # register on the fly (sys.modules) so launch.train can find it
    import sys
    import types
    mod = types.ModuleType("repro.configs.llama_100m")
    mod.config = lambda: cfg
    mod.smoke = lambda: cfg
    sys.modules["repro.configs.llama_100m"] = mod
    res = train("llama-100m", steps=args.steps, batch=args.batch,
                seq_len=args.seq_len, ckpt_dir=args.ckpt_dir,
                ckpt_every=100, log_every=20)
    print(f"loss {res['history'][0]:.3f} -> {res['final_loss']:.3f} "
          f"over {args.steps} steps; stragglers={res['stragglers']}")
    assert res["final_loss"] < res["history"][0], "loss did not improve"


if __name__ == "__main__":
    gcv.use_compile_cache()
    main()
