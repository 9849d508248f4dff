import atexit
import os
import shutil
import tempfile

import jax

# The MPC core needs uint64 lanes; model code is dtype-explicit so this is
# safe to set globally for the test session. (dryrun.py manages its own
# device-count env and is NOT imported here — smoke tests must see 1 device.)
jax.config.update("jax_enable_x64", True)

# Entry points that tests start as child processes keep JAX's compile cache
# in $JAX_COMPILATION_CACHE_DIR, else inside the checkout; point them at a
# scratch directory so the test session writes nothing into the checkout.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _cache = tempfile.mkdtemp(prefix="jax_cache_tests_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache
    atexit.register(shutil.rmtree, _cache, True)
