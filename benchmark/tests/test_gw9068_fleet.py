"""``gw9068`` through the harness's ``layout`` path on the CPU, and the
front door's two readers.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

- The cell ``gw9068.fleet4`` cut to two workers and a 512-token pool:
  every answer agrees with the reference, and no repeat of a token
  reads differently, also when most frames leave the native relay for
  the front door's Python slow path (a bounded-load constant so small
  that any owner with work in flight counts as hot).
- ``route_slow_share`` and ``route_imbalance`` on synthetic contexts,
  and None where the router has no such counters.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from lib import faults, fleet, spec  # noqa: E402

CELL = "gw9068.fleet4"


@pytest.fixture(autouse=True)
def _cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A copy of the benchmark with ``gw9068.fleet4`` on two workers
    (two chips) and ``fleet8k`` cut to a test's size."""
    root = tmp_path_factory.mktemp("gw9068")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    path = root / "benchmark" / "traffic" / "fleet8k.json"
    t = json.loads(path.read_text())
    t.update(pool_tokens=512, conns=4, req_tokens=64,
             tampered_share=0.05, warm_tokens=128,
             warm_sizes={"RS256": [8]})
    path.write_text(json.dumps(t))
    path = root / "benchmark" / "configs" / "gw9068.json"
    cfg = json.loads(path.read_text())
    cfg["layout"] = dict(cfg["layout"], workers=2)
    path.write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        if w["name"] == CELL:
            w["chips"] = 2
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def _run(root, trace):
    import run

    args = run.parse(["--workload", CELL, "--seed", str(2 ** 34 + 9068),
                      "--seconds", "1.5", "--trace", str(trace)])
    return run.measure(args, faults.cpu_keyset, need_chip=False, root=root)


@pytest.fixture
def hot_owners(monkeypatch):
    """The router started with a bounded-load constant of 0.01: a frame
    whose owner has tokens in flight leaves the native relay for the
    Python slow path (``frontdoor.native.slow.overload``)."""
    real = fleet.subprocess.Popen

    def popen(cmd, *a, **kw):
        if "--keyset" in cmd:
            i = cmd.index("--keyset") + 1
            cmd = cmd[:i] + [cmd[i] + ";spill=0.01"] + cmd[i + 1:]
        return real(cmd, *a, **kw)

    monkeypatch.setattr(fleet.subprocess, "Popen", popen)


def _assert_sound(res):
    checks = res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    for name in ("mismatched_answers", "inconsistent_repeats",
                 "unanswered_requests", "connection_errors"):
        assert checks[name][0] == 0, (name, checks)
    assert res["diagnostics"]["rewrapped_rejects"] == 0
    assert res["correct"]
    assert res["device"]["count"] == 2


def test_gw9068_fleet_is_correct(small_root):
    res = _run(small_root, trace=0)
    _assert_sound(res)
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}


def test_gw9068_fleet_is_correct_through_the_slow_path(small_root,
                                                        hot_owners):
    """Most frames answered by the Python slow path, the rest spliced
    or split by the native relay: every repeat of a token reads the
    same, so no answer depends on its route."""
    res = _run(small_root, trace=1)
    _assert_sound(res)
    m = res["metrics"]
    assert set(m) == {"route_slow_share", "route_imbalance"}
    assert 0 < m["route_slow_share"]["value"] <= 100
    assert m["route_imbalance"]["value"] >= 1


# -- the readers ------------------------------------------------------------

def _ctx(counters):
    return {"counters": counters, "series": {}, "trace": {},
            "window_s": 5.0}


def test_route_slow_share_reads_the_slow_paths_share():
    read = spec.reader("route_slow_share")
    ctx = _ctx({"frontdoor.pool.0.tokens": 6000,
                "frontdoor.pool.1.tokens": 2000,
                "frontdoor.pool.2.relay_inflight": 99,
                "frontdoor.slow.raw_entries": 2000,
                "frontdoor.lookups": 8000})
    assert read(ctx) == pytest.approx(25.0)
    ctx["counters"]["frontdoor.slow.raw_entries"] = 0
    assert read(ctx) == 0.0


def test_route_imbalance_reads_the_busiest_pool_over_the_mean():
    read = spec.reader("route_imbalance")
    ctx = _ctx({f"frontdoor.pool.{i}.tokens": n
                for i, n in enumerate((3000, 1000, 2000, 2000))})
    assert read(ctx) == pytest.approx(1.5)
    # an idle pool counts in the mean
    ctx = _ctx({"frontdoor.pool.0.tokens": 4000,
                "frontdoor.pool.1.tokens": 0})
    assert read(ctx) == pytest.approx(2.0)


@pytest.mark.parametrize("counters", [
    {},                                              # no front door
    {"frontdoor.lookups": 8192, "frontdoor.affinity_hits": 8192,
     "frontdoor.native.slow.overload": 3},           # the parent's router
    {"frontdoor.pool.0.tokens": 0, "frontdoor.pool.1.tokens": 0,
     "frontdoor.slow.raw_entries": 0},               # nothing sent
])
def test_readers_find_nothing_without_the_counters(counters):
    for name in ("route_slow_share", "route_imbalance"):
        assert spec.reader(name)(_ctx(dict(counters))) is None, name
