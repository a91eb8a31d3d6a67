"""Batched serving example: continuous batching over decode slots using
the same serve_step the decode dry-run cells lower.

Run:  PYTHONPATH=src python examples/serve_batched.py [--arch <id>]
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

from repro.launch.serve import main

if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
