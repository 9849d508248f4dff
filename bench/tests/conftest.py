"""Test set-up of the benchmark's own tests: the repository root and `src`
on the import path (for `bench.*` and `repro.*`), and 64-bit JAX types,
which the ring arithmetic needs."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
