"""The benchmark's own tests, on the CPU (no libtpu).

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

They check that every name in BENCHMARK.json finds its files, the
generators, the reference on forgeries, the trace reduction, that the
command refuses to run without a TPU, and that whole runs with the
timed path broken underneath (and the control in its place) come out
not correct while a sound run comes out correct.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from lib import fixtures, oracle, spec, stats, trace, traffic  # noqa: E402


@pytest.fixture(autouse=True)
def _cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")


def test_every_name_finds_its_files():
    bench = spec.benchmark()
    for wl in bench["workloads"]:
        c = spec.cell(wl["name"])
        assert c["config"]["name"] == wl["config"]
        for key in ("loop", "procs", "conns", "req_tokens", "pick",
                    "pool_tokens", "tampered_share"):
            assert key in c["traffic"], (wl["traffic"], key)
        assert any(m["name"] == "setup_s" for m in c["end_to_end"])
        assert len(c["end_to_end"]) >= 2 and c["per_layer"]
    for m in bench["per_layer"]:
        assert callable(spec.reader(m["name"]))
    with pytest.raises(spec.SpecError):
        spec.cell("no.such.cell")
    with pytest.raises(spec.SpecError):
        spec.reader("no_such_metric")


def test_readers_return_nothing_without_data():
    empty = {"counters": {}, "series": {}, "trace": {}, "window_s": 1.0}
    for m in spec.benchmark()["per_layer"]:
        assert spec.reader(m["name"])(empty) is None, m["name"]


def test_zipf_sampler_repeats_for_a_seed():
    a = traffic.zipf_picker(4096, 16, 1.0, seed=2 ** 33 + 5, proc=0)
    b = traffic.zipf_picker(4096, 16, 1.0, seed=2 ** 33 + 5, proc=0)
    c = traffic.zipf_picker(4096, 16, 1.0, seed=2 ** 33 + 6, proc=0)
    sa = [a() for _ in range(50)]
    assert sa == [b() for _ in range(50)]
    assert sa != [c() for _ in range(50)]


def simulate_fifo_hits(n_pool: int, capacity: int, draws) -> float:
    """Hit ratio of a FIFO cache of ``capacity`` over a stream of pool
    indices: the model behind the cells' expected hit ratios."""
    from collections import deque

    live, order, hits, n = set(), deque(), 0, 0
    for i in draws:
        n += 1
        if i in live:
            hits += 1
            continue
        live.add(i)
        order.append(i)
        if len(order) > capacity:
            live.discard(order.popleft())
    return hits / n if n else 0.0


def test_cycled_pool_bypasses_a_fifo_cache_half_its_size():
    t = spec.traffic("bulk8k")
    cap = spec.cell("ns16.bulk")["config"]["worker"]["vcache_capacity"]
    assert t["pool_tokens"] >= 2 * cap
    picks = [traffic.picker(t, 7, p, t["procs"]) for p in range(t["procs"])]
    draws = []
    for _ in range(3 * t["pool_tokens"] // t["req_tokens"] // t["procs"]):
        for p in picks:
            draws.extend(p())
    assert simulate_fifo_hits(t["pool_tokens"], cap, draws) == 0.0


def test_zipf_pool_hit_ratio_model():
    t = {"pick": "zipf", "zipf_s": 1.0, "pool_tokens": 131072,
         "req_tokens": 16}
    p = traffic.picker(t, 11, 0, 1)
    draws = [i for _ in range(40000) for i in p()]
    hits = simulate_fifo_hits(t["pool_tokens"], 65536, draws[-300000:])
    assert 0.8 < hits < 0.95


def test_prewarm_dispatches_every_size_before_collecting():
    from lib import sut

    log = []

    class Async:
        def verify_batch_async_raw(self, toks):
            log.append(("dispatch", {t.split("-")[0] for t in toks},
                        len(toks)))
            return lambda: log.append(("collect",))

    cfg = {"keys": [{"kid": "rs-0", "alg": "RS256"},
                    {"kid": "es-0", "alg": "ES256"}],
           "kid_order": ["rs-0", "es-0"]}
    toks = [f"{'rs' if j % 2 == 0 else 'es'}-{j}" for j in range(64)]
    sut.run_prewarm(Async(), sut.prewarm_batches(
        toks, cfg, {"RS256": [8, 16], "ES256": [32]}))
    assert log[:3] == [("dispatch", {"rs"}, 8), ("dispatch", {"rs"}, 16),
                       ("dispatch", {"es"}, 32)]
    assert log[3:] == [("collect",)] * 3


def test_arrivals_have_the_same_gaps_for_every_seed():
    g1 = traffic.arrival_gaps(500.0, 1, 0)
    g2 = traffic.arrival_gaps(500.0, 2, 0)
    a = [next(g1) for _ in range(1024)]
    b = [next(g2) for _ in range(1024)]
    assert a != b and sorted(a) == sorted(b)
    assert abs(sum(a) / len(a) - 1 / 500.0) < 1e-9


@pytest.fixture(scope="module")
def signed():
    keys = [{"kid": "rs-0", "kty": "RSA", "alg": "RS256"},
            {"kid": "es-0", "kty": "EC", "alg": "ES256"}]
    specs = fixtures.key_specs(keys, 2 ** 40 + 3, {
        "rs-0": fixtures.rsa_numbers(2 ** 40 + 3, "rs-0")})
    toks = [t for lo in range(0, 8, 4) for t in fixtures.sign_range(
        specs, ["rs-0", "es-0"], "default_claims", 2 ** 40 + 3, lo, lo + 4)]
    return fixtures.jwks_document(specs), toks, specs


def test_seed_gives_the_same_tokens(signed):
    doc, toks, specs = signed
    again = fixtures.sign_range(specs, ["rs-0", "es-0"], "default_claims",
                                2 ** 40 + 3, 0, 4)
    assert again == toks[:4]


def test_reference_rejects_every_forgery(signed):
    doc, toks, _ = signed
    keys = oracle._public_keys(doc)
    for t in toks:
        ok, claims = oracle.verdict(keys, t)
        assert ok and claims["sub"].startswith("user-")
    for kind in fixtures.TAMPER_KINDS:
        for i in range(len(toks)):
            forged = fixtures.tamper(toks, i, kind, (i + 1) % len(toks))
            assert oracle.verdict(keys, forged)[0] is False, (kind, i)


def test_comparison_on_tampered_tokens(signed):
    doc, toks, _ = signed
    keys = oracle._public_keys(doc)
    good = oracle.verdict(keys, toks[0])
    bad = oracle.verdict(keys, fixtures.tamper(toks, 0, "swapped_payload",
                                               1))
    payload = fixtures.b64u_dec(toks[0].split(".")[1])
    assert oracle.agrees((0, payload), good)
    assert not oracle.agrees((1, b"InvalidSignatureError: x"), good)
    assert not oracle.agrees((0, payload.replace(b"user", b"usex")), good)
    assert oracle.agrees((1, b"InvalidSignatureError: x"), bad)
    assert not oracle.agrees((0, payload), bad)
    assert not oracle.agrees((1, b"ThrottledError: retry_after_ms=5"), bad)


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, [_Ev(*e) for e in events]


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, [_Line(*ln) for ln in lines]


def test_trace_reduction_on_planes():
    planes = [
        _Plane("/host:CPU", [("main", [(trace.WINDOW, 100, 1000),
                                       ("bench.keyset.dispatch", 450, 300)])]),
        _Plane("/device:TPU:0", [
            ("XLA Modules", [("jit__rs_packed_rns_impl(1)", 100, 300),
                             ("jit__es_packed_rns_impl(2)", 800, 100)]),
            ("XLA Ops", [("fusion.1", 100, 200), ("fusion.2", 250, 150),
                         ("fusion.1", 800, 100)])]),
    ]
    s = trace.summarize(trace.reduce_planes(planes))
    assert s["window_s"] == pytest.approx(1e-6)
    assert s["busy_s"] == pytest.approx(400e-9)
    assert trace.program_s(s, "rs_packed") == pytest.approx(300e-9)
    assert trace.program_s(s, "es_packed") == pytest.approx(100e-9)
    assert trace.program_s(s, "ed_packed") is None
    assert s["device_ops"][0] == ["fusion", pytest.approx(450e-9)]
    assert s["idle_gaps"][0] == ["bench.keyset.dispatch",
                                 pytest.approx(400e-9)]
    ctx = {"counters": {}, "series": {}, "trace": s, "window_s": 1e-6}
    assert spec.reader("device_idle_share")(ctx) == pytest.approx(60.0)


def test_trace_reduction_on_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(str(tmp_path))
    assert tr["window"] is not None and tr["window"][1] > tr["window"][0]
    assert any(name.startswith("PjitFunction") for name, _, _ in tr["host"])


def test_series_delta_and_quantile():
    before = {"count": 2, "sum": 0.002, "buckets": {"70": 2}}
    after = {"count": 5, "sum": 0.005, "buckets": {"70": 3, "80": 2}}
    d = stats.series_delta(after, before)
    assert d == {"count": 3, "sum": pytest.approx(0.003),
                 "buckets": {70: 1, 80: 2}}
    q = stats.series_quantile(d, 0.5)
    assert stats._bound(79) < q <= stats._bound(80)
    assert stats.percentile([5, 1, 3, 2, 4], 50) == 3
    assert stats.percentile(list(range(1, 101)), 99) == 99


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "ns16.bulk",
         "--seed", "3000000017", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "ns16.bulk",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


# -- whole runs on the CPU, with the timed path broken underneath ----------

@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A copy of the benchmark whose traffic is cut to a test's size."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name in ("bulk8k", "open64"):
        path = root / "benchmark" / "traffic" / f"{name}.json"
        t = json.loads(path.read_text())
        t.update(pool_tokens=256, procs=1, conns=min(t["conns"], 2),
                 req_tokens=min(t["req_tokens"], 32), tampered_share=0.05,
                 warm_tokens=64,
                 warm_sizes={a: [8] for a in t.get("warm_sizes", {})})
        if t["loop"] == "open":
            t["rate_rps"] = 50
        path.write_text(json.dumps(t))
    # the open-loop mix has no cell yet (PERF.md, Open questions): the
    # copy lists one so its generator runs whole here too
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "ns16.serve", "config": "ns16",
                               "traffic": "open64", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("ns16.serve")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def _cpu_keyset(doc, cfg):
    from cap_tpu.jwt.jwk import parse_jwks
    from cap_tpu.jwt.keyset import StaticKeySet

    return StaticKeySet([j.key for j in parse_jwks(doc)])


def _run(root, workload, factory, seed=2 ** 35 + 1):
    import run

    args = run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", "1.5"])
    return run.measure(args, factory, need_chip=False, root=root)


@pytest.mark.parametrize("workload", ["ns16.bulk", "ns16.serve"])
def test_sound_run_is_correct(small_root, workload):
    res = _run(small_root, workload, _cpu_keyset)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    names = [m["name"] for m in spec.cell(workload,
                                          small_root)["end_to_end"]]
    assert set(res["metrics"]) <= set(names) and "setup_s" in res["metrics"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["alter_answer", "drop_half",
                                   "stale_state"])
def test_broken_timed_path_is_not_correct(small_root, fault):
    from lib import faults

    res = _run(small_root, "ns16.bulk",
               lambda doc, cfg: faults.Faulty(_cpu_keyset(doc, cfg), fault))
    assert not res["correct"], (fault, res["checks"])


def test_control_is_not_correct(small_root):
    from lib import faults

    for seed in (1, 2, 3):
        res = _run(small_root, "ns16.bulk",
                   lambda doc, cfg: faults.ControlKeySet(doc), seed=seed)
        assert not res["correct"]
        assert res["checks"]["mismatched_answers"][0] > 0


def test_traced_run_reads_its_layers_on_cpu(small_root):
    import run

    args = run.parse(["--workload", "ns16.bulk", "--seed", "9",
                      "--seconds", "1.5", "--trace", "1"])
    t0 = time.perf_counter()
    res = run.measure(args, _cpu_keyset, need_chip=False, root=small_root)
    assert time.perf_counter() - t0 < 120
    # the CPU keyset has no device trace and no native prep: every
    # reader finds nothing and its metric is left out, never 0
    assert res["correct"] and res["metrics"] == {}
