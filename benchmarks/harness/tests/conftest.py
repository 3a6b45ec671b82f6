"""Run from the root of the checkout with JAX on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/harness/tests
"""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
