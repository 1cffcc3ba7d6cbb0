"""Train an LM end-to-end on the training substrate.

    PYTHONPATH=src python examples/train_lm.py                # CPU-sized demo
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

``--preset 100m`` builds a ~100M-parameter TinyLlama-family model (the
"train a ~100M model for a few hundred steps" deliverable — sized for a TPU
host; on this 1-core CPU container it runs but slowly, so the default demo
preset is smaller). Loss should drop well below ln(vocab) as the model
learns the synthetic Markov-chain data.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

from repro.common.config import get_config
from repro.training.data import DataConfig
from repro.training.train_loop import TrainConfig, train_lm


def build_cfg(preset: str):
    base = get_config("tinyllama-1.1b")
    if preset == "100m":
        # ~100M params: 12 x 640 TinyLlama-family, 8 heads (GQA kv=2)
        return dataclasses.replace(
            base, name="tinyllama-100m", num_layers=12, d_model=640,
            num_heads=8, num_kv_heads=2, head_dim=80, d_ff=1792,
            vocab_size=32000)
    if preset == "demo":
        return dataclasses.replace(
            base, name="tinyllama-demo", num_layers=4, d_model=256,
            num_heads=4, num_kv_heads=2, head_dim=64, d_ff=704,
            vocab_size=2048)
    raise SystemExit(f"unknown preset {preset}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="demo", choices=["demo", "100m"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = build_cfg(args.preset)
    n_params = cfg.param_count()
    print(f"training {cfg.name}: {n_params / 1e6:.1f}M params, "
          f"{args.steps} steps, batch {args.batch} x {args.seq}")

    tcfg = TrainConfig(lr=args.lr, total_steps=args.steps,
                       warmup=max(10, args.steps // 10),
                       ckpt_dir=args.ckpt_dir)
    dcfg = DataConfig(vocab_size=min(cfg.vocab_size, 2048),
                      seq_len=args.seq, batch_size=args.batch)
    params, history = train_lm(cfg, tcfg, dcfg)
    first, last = history[0]["loss"], history[-1]["loss"]
    print(f"loss {first:.3f} -> {last:.3f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")
    out = os.path.join("artifacts", f"train_{cfg.name}.json")
    os.makedirs("artifacts", exist_ok=True)
    with open(out, "w") as f:
        json.dump({"config": cfg.name, "params_m": n_params / 1e6,
                   "history": history}, f, indent=1)
    print(f"history -> {out}")


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
