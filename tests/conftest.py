"""Test configuration.

Pins JAX to a virtual 8-device CPU mesh before any backend is
initialized, so sharding paths are exercised without a TPU. The tests
run on the CPU (``JAX_PLATFORMS=cpu``); the chip path is checked by
``chip_smoke.py`` on the chip, and ``tests/test_chip_compile.py``
compiles the main-path kernels for a described v5e chip.
"""

import importlib.util
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.find_spec("cap_tpu")
if _spec is None or not (_spec.origin or "").startswith(_REPO + os.sep):
    # Not installed, or an installed copy would shadow this checkout:
    # the suite must always test the code it sits next to.
    sys.path.insert(0, _REPO)

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# Persistent compilation cache: the suite is compile-dominated on CPU
# (engine programs per shape bucket); warm runs skip all of it.
from cap_tpu import compile_cache

compile_cache.enable()
