"""Finds a cell's pieces by name: its entry in ``BENCHMARK.json``, the
configuration file it names, ``traffic/<traffic>.json``, and one reader
``metrics/<metric>.py`` per per-layer metric. Adding a cell, a
configuration, a traffic mix or a metric adds files and an entry; no
file here changes. What a configuration file may state (keys, algs,
claims profiles, a worker layout) is in ``configs/README.md``."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


def _json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}") from None


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def cell(name: str, root: str = ROOT) -> dict:
    """Everything a run of cell ``name`` needs, resolved from files."""
    bench = benchmark(root)
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    return _assemble(bench, wl, root)


def cell_of(config: str, traffic_name: str, root: str = ROOT) -> dict:
    """A one-chip cell of a configuration and a traffic mix that
    BENCHMARK.json need not list yet (the knee sweep's)."""
    wl = {"name": f"{config}.{traffic_name}", "config": config,
          "traffic": traffic_name, "chips": 1}
    return _assemble(benchmark(root), wl, root)


def _assemble(bench: dict, wl: dict, root: str) -> dict:
    name = wl["name"]
    cfg = next((c for c in bench["configs"] if c["name"] == wl["config"]),
               None)
    if cfg is None:
        raise SpecError(f"no config {wl['config']!r} in BENCHMARK.json")

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "workload": wl,
        "config": _json(os.path.join(root, cfg["file"])),
        "traffic": traffic(wl["traffic"], root),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
        "run_seconds": bench["run_seconds"],
        "root": root,
    }


def traffic(name: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, "benchmark", "traffic", f"{name}.json"))


def reader(metric: str, root: str = ROOT):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(root, "benchmark", "metrics", f"{metric}.py")
    if not os.path.exists(path):
        raise SpecError(f"missing reader {os.path.relpath(path, root)}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
