"""The benchmark's own tests: CPU, tiny sizes, no device or topology call at
import. Run alone with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parent.parent
for p in (str(BENCH), str(BENCH.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

TINY = str(BENCH / "tests" / "BENCHMARK.tiny.json")
