"""CPU tests of the benchmark harness; run them by path from the checkout:

    python -m pytest -q bench/tests

They never touch a chip: JAX is held to the CPU here.
"""

import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
BENCH = pathlib.Path(__file__).resolve().parents[1]
for path in (BENCH, BENCH.parent / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))
