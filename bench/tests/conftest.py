"""The benchmark's own tests run on the CPU, with the harness's directories
on the import path:

    python3 -m pytest -q bench/tests
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for sub in ("", "lib", "drivers", "configs", "tests"):
    p = os.path.join(BENCH, sub)
    if p not in sys.path:
        sys.path.insert(0, p)
