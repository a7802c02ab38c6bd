"""The system under test as a cell's configuration builds it: the keyset
and ``VerifyWorker`` on the chip, the checks and the compile counter
around them. ``run.py`` uses these in its own process; ``fleet.py``
uses the same ones in each worker process of a ``layout``.

What imports JAX or the program here runs only in the process that
holds the chip.
"""

from __future__ import annotations

import os
import sys
import tempfile
import time

from . import spec, trace


class BenchError(Exception):
    """The run cannot produce a result."""


def say(tag: str, **fields) -> None:
    print(f"{tag}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          file=sys.stderr, flush=True)


class CompileCounter:
    """Records each program JAX traces, lowers and compiles (or loads
    from its cache), from the duration events JAX reports for each.
    Times are ``time.perf_counter()``: CLOCK_MONOTONIC on Linux, one
    clock for every process of the machine."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}

    def __init__(self):
        import jax

        self.events = []          # (time, kind, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        kind = self.EVENTS.get(event)
        if kind is not None:
            self.events.append((time.perf_counter(), kind, duration))

    def between(self, t0, t1, kind="compile"):
        return [d for t, k, d in self.events if t0 <= t < t1 and k == kind]

    def last(self) -> float:
        return self.events[-1][0] if self.events else 0.0

    def per_second(self, t0: float):
        """[[second since t0, events, seconds they took], ...]."""
        return per_second(self.events, t0)


def per_second(events, t0: float):
    bins = {}
    for t, _, d in events:
        b = bins.setdefault(int(t - t0), [0, 0.0])
        b[0] += 1
        b[1] += d
    return [[k, n, round(d, 2)] for k, (n, d) in sorted(bins.items())]


def require_chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX reports platform "
                         f"{devs[0].platform!r}")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX reports "
                         f"{len(devs)}")
    return devs[:n]


def use_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout; a
    program that reads JAX_COMPILATION_CACHE_DIR takes the same one."""
    import jax

    path = os.path.join(spec.ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no size cap, whatever the environment sets: every program a cell
    # builds stays in the cache after its first run, or each later run
    # builds again what was evicted
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class TracedKeySet:
    """The keyset as the worker sees it, with the benchmark's own
    profiler spans around each call into it (traced runs only)."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if name != "verify_batch_async_raw":
            return attr
        from jax.profiler import TraceAnnotation

        def traced_dispatch(tokens):
            with TraceAnnotation("bench.keyset.dispatch"):
                collect = attr(tokens)

            def traced_collect():
                with TraceAnnotation("bench.keyset.collect"):
                    return collect()

            return traced_collect

        return traced_dispatch


def tpu_keyset(jwks_doc: dict, cfg: dict):
    from cap_tpu.jwt.jwk import parse_jwks
    from cap_tpu.jwt.tpu_keyset import TPUBatchKeySet

    return TPUBatchKeySet(parse_jwks(jwks_doc), cpu_fallback=False,
                          max_chunk=cfg["keyset"]["max_chunk"])


def start_worker(keyset, cfg: dict):
    from cap_tpu import telemetry
    from cap_tpu.serve.worker import VerifyWorker

    # As a deployed worker does (cap_tpu/fleet/worker_main.py): the
    # recorder is on, so STATS and the per-layer readers see counters.
    telemetry.enable()
    w = cfg["worker"]
    worker = VerifyWorker(
        keyset, target_batch=w["target_batch"], max_wait_ms=w["max_wait_ms"],
        max_batch=w["max_batch"], serve_native=w["serve_native"],
        vcache=w["vcache"], vcache_capacity=w["vcache_capacity"],
        fair=w["fair"], transport=w["transport"])
    if w["serve_native"] and worker.serve_chain != "native":
        worker.close()
        raise BenchError(f"serve chain is {worker.serve_chain}, not native")
    return worker


def build_native() -> float:
    from cap_tpu import _build

    t0 = time.perf_counter()
    _build.build_native()
    return time.perf_counter() - t0


def prewarm_batches(tokens, cfg: dict, sizes: dict):
    """One batch of pool tokens of each algorithm at each size in
    ``sizes`` ({alg: [n, ...]})."""
    alg_of = {k["kid"]: k["alg"] for k in cfg["keys"]}
    order = cfg["kid_order"]
    by_alg = {}
    for j, tok in enumerate(tokens):
        by_alg.setdefault(alg_of[order[j % len(order)]], []).append(tok)
    return [by_alg[alg][:n] for alg, ns in sizes.items() for n in ns]


def run_prewarm(keyset, batches) -> None:
    """Verify ``batches`` straight through the keyset, all dispatched
    before any is collected. The keyset sizes its chunks from the link
    rate it measures, so the traffic alone meets some batch shapes only
    after minutes; these batches build each one now."""
    if not hasattr(keyset, "verify_batch_async_raw"):
        for b in batches:
            keyset.verify_batch(b)
        return
    for collect in [keyset.verify_batch_async_raw(b) for b in batches]:
        collect()


def window_stats(worker) -> dict:
    s = worker.stats()
    return {"counters": dict(s.get("counters") or {}),
            "series": dict((s.get("snapshot") or {}).get("series") or {})}


def memory_peak(devs) -> int:
    return max((dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for dev in devs)


def start_profile():
    """Start JAX's profiler into a temporary directory, which it
    returns: annotations only (the benchmark's own spans and JAX's
    level-1 events), as a full host trace of a busy window takes
    minutes to read back."""
    import jax

    tdir = tempfile.TemporaryDirectory(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tdir.name, profiler_options=opts)
    return tdir


def stop_profile(tdir) -> dict:
    """Stop the profiler and reduce its trace (``trace.summarize``)."""
    import jax

    t_stop = time.perf_counter()
    jax.profiler.stop_trace()
    t_read = time.perf_counter()
    tr = trace.load(tdir.name)
    say("trace_cost", stop_s=round(t_read - t_stop, 3),
        read_s=round(time.perf_counter() - t_read, 3))
    tdir.cleanup()
    return trace.summarize(tr)
