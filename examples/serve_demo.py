"""Continuous-batching serving demo: the serving CLI on a smoke-sized
gemma3 (5:1 local:global attention with ring-buffer caches), a Poisson
trace over two prompt lengths.

  PYTHONPATH=src python examples/serve_demo.py
"""
import os
import sys

if __name__ == "__main__":
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "src"))
    from repro.launch import serve

    sys.argv = [
        "serve", "--arch", "gemma3-4b", "--smoke", "--slots", "4",
        "--requests", "8", "--prompt-lens", "32", "64",
        "--gen-lens", "8", "16", "--max-len", "96",
    ]
    serve.main()
