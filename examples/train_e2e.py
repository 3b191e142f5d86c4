"""End-to-end driver: train a LM for a few hundred DP steps with adaptive
per-layer clipping, checkpoint, and report the spent privacy budget.

Defaults run a ~1.7M-param qwen3-family reduced model for 200 steps on CPU
(a few minutes); pass --arch/--steps/--batch to scale up — the same driver
runs any assigned architecture.

    PYTHONPATH=src python examples/train_e2e.py [--steps 200]
"""
import sys

from repro.launch.train import main

if __name__ == "__main__":
    argv = sys.argv[1:]
    defaults = ["--arch", "qwen3-4b", "--reduced", "--steps", "200",
                "--batch", "16", "--seq", "64", "--microbatches", "2",
                "--checkpoint-dir", "/tmp/repro_e2e_ckpt",
                "--log-every", "20"]
    # user args win
    raise SystemExit(main(defaults + argv))
