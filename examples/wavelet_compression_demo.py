"""Gradient-compression ablation: train the same model with and without
DWT gradient compression and compare loss trajectories + exchanged bytes.

    PYTHONPATH=src python examples/wavelet_compression_demo.py [--steps 120]
"""
import argparse
import dataclasses

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs.base import ShapeConfig
from repro.configs.registry import get_config
from repro.data.pipeline import make_pipeline
from repro.runtime.train_loop import train
from repro.core import compression as CMP


def run_one(tag, cfg, run, steps_n, ckpt):
    run = dataclasses.replace(run, checkpoint_dir=ckpt, checkpoint_every=0,
                              grad_accum=1, lr=1e-3, warmup_steps=10,
                              total_steps=steps_n)
    pipe = make_pipeline(cfg, seed=0)
    shape = ShapeConfig("demo", "train", 128, 8)
    res = train(cfg, run, pipe, shape, num_steps=steps_n, log_every=0,
                resume=False)
    n = len(res.losses)
    print(f"{tag:18s} loss: {res.losses[0]:.4f} -> "
          f"{sum(res.losses[-10:]) / 10:.4f}")
    return res.losses


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    args = ap.parse_args()

    cfg, run = get_config("minitron-8b", smoke=True)
    n_params = cfg.n_params()
    print(f"model: {cfg.arch_id} ({n_params/1e6:.2f}M params)")
    print(f"cross-pod bytes/step raw: {n_params*4/1e6:.2f}MB  "
          f"dwt:2 -> {n_params*4/16/1e6:.3f}MB "
          f"({CMP.compressed_bytes_ratio(2)*100:.1f}%)\n")

    base = run_one("baseline", cfg, run, args.steps, "/tmp/wcd_base")
    comp = run_one(
        "dwt:2 compressed", cfg,
        dataclasses.replace(run, grad_compression="dwt:2"),
        args.steps, "/tmp/wcd_comp")
    comp1 = run_one(
        "dwt:1 compressed", cfg,
        dataclasses.replace(run, grad_compression="dwt:1"),
        args.steps, "/tmp/wcd_comp1")

    gap = (sum(comp[-10:]) - sum(base[-10:])) / 10
    print(f"\nfinal-loss gap (dwt:2 vs baseline): {gap:+.4f} "
          f"(error feedback keeps compressed training convergent; "
          f"16x fewer cross-pod gradient bytes)")


if __name__ == "__main__":
    main()
