#!/usr/bin/env python3
"""Per-span cost of ``telemetry.span``: without the profiler hook, and
with it while no profile captures and while one does; recorder off and
on; with and without the thread CPU series (``cpu=True``).

    python3 tools/span_cost.py          # prints one JSON line, ns/span

Each figure is the best of three loops of N spans. ``nohook_recoff``
is the cost a process pays before any ``TPUBatchKeySet`` is built.
"""

from __future__ import annotations

import json
import os
import platform
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N = 200_000


def per_span_ns(telemetry, cpu: bool = False) -> float:
    span = telemetry.span
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(N):
            with span(telemetry.SPAN_KEYSET_PACK, cpu=cpu):
                pass
        best = min(best, (time.perf_counter() - t0) / N * 1e9)
    return round(best, 1)


def main() -> None:
    import jax

    from cap_tpu import telemetry
    from cap_tpu.jwt import tpu_keyset

    out = {"machine": platform.machine(),
           "device_kind": jax.devices()[0].device_kind, "spans": N}
    telemetry.disable()
    out["nohook_recoff_ns"] = per_span_ns(telemetry)
    tpu_keyset.install_jax_hooks()
    out["hook_recoff_profoff_ns"] = per_span_ns(telemetry)
    telemetry.enable(telemetry.Recorder())
    out["hook_recon_profoff_ns"] = per_span_ns(telemetry)
    out["hook_recon_profoff_cpu_ns"] = per_span_ns(telemetry, cpu=True)
    telemetry.disable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            out["hook_recoff_profon_ns"] = per_span_ns(telemetry)
            telemetry.enable(telemetry.Recorder())
            out["hook_recon_profon_ns"] = per_span_ns(telemetry)
        finally:
            telemetry.disable()
            jax.profiler.stop_trace()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
