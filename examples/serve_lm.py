"""Batched serving with continuous batching: requests stream through a
fixed-slot engine (prefill on admission, per-slot decode positions, slot
reuse on completion) — the serving-side end-to-end driver.

Run:  PYTHONPATH=src python examples/serve_lm.py [--arch zamba2-1.2b]
"""
import argparse

from repro.launch.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--requests", type=int, default=8)
    args = ap.parse_args()

    from repro.launch import serve

    serve.main(["--arch", args.arch, "--smoke",
                "--requests", str(args.requests),
                "--slots", "4", "--max-new", "12"])


if __name__ == "__main__":
    main()
