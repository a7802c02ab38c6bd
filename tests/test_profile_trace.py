"""The profile_families --trace device-timeline extraction.

The slope methodology can be inflated by host-link weather (the round-5
1046k/s ES256 outlier); --trace re-derives per-dispatch ms from the
profiler's trace-viewer JSON. This pins the parser end-to-end on a
real jax.profiler capture: device/runtime execution events are found,
host python-thread events are excluded, and the returned span divides
by the dispatch count.
"""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp


def _load_tool():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "profile_families.py")
    spec = importlib.util.spec_from_file_location("_profile_families", path)
    mod = importlib.util.module_from_spec(spec)
    saved = sys.argv
    sys.argv = [path]          # tool parses argv at import
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.argv = saved
    return mod


def test_trace_device_ms_measures_real_work():
    tool = _load_tool()

    @jax.jit
    def work(x):
        for _ in range(4):
            x = x @ x
        return jnp.sum(x)

    x = jnp.ones((256, 256))
    work(x).block_until_ready()            # compile outside the trace
    fns = [(1, lambda: work(x))]
    ms = tool.trace_device_ms(fns, reps=2)
    # Unknown runtimes legitimately return None; this box's must not.
    assert ms is not None and ms > 0

    @jax.jit
    def tiny(x):
        return jnp.sum(x)

    tiny(x).block_until_ready()
    ms_tiny = tool.trace_device_ms([(1, lambda: tiny(x))], reps=2)
    assert ms_tiny is not None
    # 4 chained 256x256 matmuls must show more device span than one sum
    assert ms > ms_tiny
