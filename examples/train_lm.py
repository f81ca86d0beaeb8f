"""End-to-end LM training: a ~20M-param llama-family model trained for a
few hundred steps on the deterministic synthetic corpus, with async
checkpointing, watchdog, and restart-resume — every substrate layer of
the framework in one run.

(The assigned full configs train identically via the same launcher on a
real pod; the CPU container sizes this demo so it finishes in minutes.
The loss should drop by >1 nat over 200 steps.)

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse
import sys

from repro.launch.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    from repro.launch import train

    losses = train.main([
        "--arch", "llama3-8b", "--smoke",
        "--steps", str(args.steps),
        "--batch", "8", "--seq", "256",
        "--lr", "3e-3", "--ckpt-dir", args.ckpt_dir,
        "--ckpt-every", "100", "--log-every", "20",
    ])
    drop = losses[0] - losses[-1]
    print(f"loss drop over {args.steps} steps: {drop:.3f} nats")
    if drop < 0.5:
        print("WARNING: expected >0.5 nats of improvement")
        sys.exit(1)


if __name__ == "__main__":
    main()
