"""The benchmark's own rehearsal tests: CPU, tiny sizes, run by hand
(``python -m pytest benchmarks/tests -q``); tier-1 does not collect them.
Nothing here is a measurement: a number from a CPU run is never written
under a metric's name outside these assertions."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "true"

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
