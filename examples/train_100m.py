"""End-to-end driver: trains a ~100M-parameter decoder for a few hundred
steps on synthetic Markov-chain data with the production train_step
(``launch/steps.py``), here on the host mesh.

  PYTHONPATH=src python examples/train_100m.py [--steps 300] [--mode bflc]
"""
import argparse
import sys

from repro.launch.train import run_lm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--mode", choices=["standard", "bflc"], default="standard")
    args = ap.parse_args()
    ns = argparse.Namespace(
        steps=args.steps, batch=8, seq=256, lr=3e-4, mode=args.mode,
        cohorts=4, committee=4, small=False, use_all_devices=False,
        ckpt="examples_100m.ckpt", log_every=20,
    )
    final = run_lm(ns)
    print(f"final loss: {final:.3f}")


if __name__ == "__main__":
    main()
