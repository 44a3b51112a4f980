"""The benchmark's own tests: ``pytest bench/tests`` from the checkout's
root, on the CPU (``JAX_PLATFORMS=cpu``). They put ``bench/`` and ``src/``
on the path, as ``bench/run.py`` does."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
