"""What a configuration file can state (``benchmark/configs/README.md``)
and the worker ``layout`` (``lib/fleet.py``), on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

- ``ns16`` makes the same bytes as before the configuration keys came:
  the JWKS document and pool tokens 0-511 (forgeries in place) are
  pinned by digest for two seeds.
- Every JOSE asymmetric alg signs deterministically, the reference
  accepts its tokens and rejects the three forgeries, and agrees with
  the program's CPU verifier.
- The ``rfc9068_at`` claims profile carries exactly RFC 9068's header
  and claims.
- Whole ``layout`` runs: two worker processes behind the program's
  front door, sound and with the timed path broken underneath.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from lib import claims, faults, fixtures, fleet, oracle, spec  # noqa: E402


@pytest.fixture(autouse=True)
def _cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")


# -- ns16's bytes -----------------------------------------------------------

# sha256 of json.dumps(JWKS document) and of "\n".join(pool tokens 0-511
# after with_tampered(..., 0.05)), computed with the benchmark's library
# as it was before configuration files could state keys, curves, algs
# and claims profiles
NS16_DIGESTS = {
    7: ("92e23661e4d8a922bfa6435a35515385c93c2b7323f432f19f56260c673e3495",
        "9dffc9ce11a4e433dc29740075e8f2ef7f2ca07fda67fe0835eaf0755b0facd7"),
    3000000401: (
        "a1cf355036588b547066f2fc4a45a248f0c866fd29551009625c9a0b7c61ed5b",
        "41949042d966490afbcdd99daf1107489a05ae80256a1eff1a579a4e199c4be9"),
}


@pytest.mark.parametrize("seed", sorted(NS16_DIGESTS))
def test_ns16_makes_the_same_bytes(seed):
    cfg = spec.cell("ns16.bulk")["config"]
    fixtures._SIGNERS.clear()
    rsa = {k["kid"]: fixtures.rsa_numbers(seed, k["kid"],
                                          k.get("bits", 2048))
           for k in cfg["keys"] if k["kty"] == "RSA"}
    specs = fixtures.key_specs(cfg["keys"], seed, rsa)
    doc = fixtures.jwks_document(specs)
    toks = fixtures.sign_range(specs, cfg["kid_order"], cfg["claims"],
                               seed, 0, 512)
    toks, _ = fixtures.with_tampered(toks, seed, 0.05)
    got = (hashlib.sha256(json.dumps(doc).encode()).hexdigest(),
           hashlib.sha256("\n".join(toks).encode()).hexdigest())
    assert got == NS16_DIGESTS[seed]


# -- every alg --------------------------------------------------------------

SEED = 2 ** 33 + 17
ALG_CASES = ["RS256", "RS384", "RS512", "PS256", "PS384", "PS512",
             "ES256", "ES384", "ES512", "EdDSA", "RS512-4096"]


def key_entry(case: str) -> dict:
    alg, _, bits = case.partition("-")
    kty, _, crv = fixtures.ALGS[alg]
    k = {"kid": case.lower(), "kty": kty, "alg": alg}
    if kty == "RSA":
        # 1024 bits keeps the test quick; PSS over SHA-512 with a
        # 64-byte salt needs at least 1,040
        k["bits"] = int(bits) if bits else (2048 if alg == "PS512" else 1024)
    if crv:
        k["crv"] = crv
    return k


@pytest.fixture(scope="module")
def pools():
    """{case: (jwks document, 64-token pool, forged positions, specs)}."""
    out = {}
    for case in ALG_CASES:
        keys = [key_entry(case)]
        rsa = {j[1]: fixtures.rsa_numbers(*j)
               for j in fixtures.rsa_jobs(keys, SEED)}
        specs = fixtures.key_specs(keys, SEED, rsa)
        toks = fixtures.sign_range(specs, [keys[0]["kid"]],
                                   "default_claims", SEED, 0, 64)
        pool, forged = fixtures.with_tampered(toks, SEED, 0.25)
        out[case] = (fixtures.jwks_document(specs), pool, forged, specs,
                     toks)
    return out


@pytest.mark.parametrize("case", ALG_CASES)
def test_alg_signs_the_same_bytes_twice(pools, case):
    doc, _, _, specs, toks = pools[case]
    fixtures._SIGNERS.clear()
    again = fixtures.sign_range(specs, [key_entry(case)["kid"]],
                                "default_claims", SEED, 0, 64)
    assert again == toks
    jwk = doc["keys"][0]
    assert jwk["alg"] == case.split("-")[0]
    if jwk["kty"] == "RSA":
        n = fixtures.b64u_dec(jwk["n"])
        assert len(n) * 8 == key_entry(case)["bits"]
    elif jwk["kty"] == "EC":
        size = fixtures.CURVES[jwk["crv"]][2]
        assert len(fixtures.b64u_dec(jwk["x"])) == size
        sig = fixtures.b64u_dec(toks[0].split(".")[2])
        assert len(sig) == 2 * size
    else:
        assert (jwk["crv"], len(fixtures.b64u_dec(jwk["x"]))) == (
            "Ed25519", 32)


@pytest.mark.parametrize("case", ALG_CASES)
def test_reference_accepts_every_token_and_rejects_forgeries(pools, case):
    doc, _, _, _, toks = pools[case]
    keys = oracle._public_keys(doc)
    for t in toks:
        ok, body = oracle.verdict(keys, t)
        assert ok and body["sub"].startswith("user-"), t
    for kind in fixtures.TAMPER_KINDS:
        for i in range(8):
            forged = fixtures.tamper(toks, i, kind, i + 1)
            assert oracle.verdict(keys, forged)[0] is False, (kind, i)


@pytest.mark.parametrize("case", ALG_CASES)
def test_reference_agrees_with_the_programs_cpu_verifier(pools, case):
    from cap_tpu.jwt.jwk import parse_jwks
    from cap_tpu.jwt.keyset import StaticKeySet

    doc, pool, forged, _, _ = pools[case]
    assert len(pool) == 64 and forged
    program = StaticKeySet([j.key for j in parse_jwks(doc)])
    want = oracle.verdicts(doc, pool)
    for i, (tok, (ok, body)) in enumerate(zip(pool, want)):
        try:
            got = program.verify_signature(tok)
        except Exception as e:  # noqa: BLE001 - a reject, compared below
            got = e
        assert ok == (not isinstance(got, Exception)), (i, got)
        assert ok == (i not in forged)
        if ok:
            assert got == body


def test_pss_salt_comes_from_the_seed(pools):
    doc, _, _, specs, toks = pools["PS256"]
    other = fixtures.sign_range(specs, ["ps256"], "default_claims",
                                SEED + 1, 0, 2)
    # another seed: another jti and another salt, both verify
    assert other[0].split(".")[2] != toks[0].split(".")[2]
    keys = oracle._public_keys(doc)
    assert all(oracle.verdict(keys, t)[0] for t in other)


@pytest.mark.parametrize("entry", [
    {"kid": "a", "kty": "EC", "alg": "RS256"},
    {"kid": "b", "kty": "RSA", "alg": "ES256"},
    {"kid": "c", "kty": "EC", "alg": "ES384"},               # P-256
    {"kid": "d", "kty": "EC", "alg": "ES256", "crv": "P-521"},
    {"kid": "e", "kty": "EC", "alg": "EdDSA", "crv": "Ed25519"},
    {"kid": "f", "kty": "OKP", "alg": "EdDSA", "crv": "X25519"},
    {"kid": "g", "kty": "RSA", "alg": "HS256"},
])
def test_key_entries_that_do_not_go_together_are_refused(entry):
    with pytest.raises(fixtures.KeyConfigError):
        fixtures.key_specs([entry], 1, {entry["kid"]: (3, 5, 7)})


def test_reference_keeps_algs_on_their_own_key_type(pools):
    """A token whose header names another family's alg, with a genuine
    signature of its own key, is refused: an RS alg never verifies on
    an EC key, ES256 never on P-384, and so on."""
    def relabel(tok, alg):
        h, p, s = tok.split(".")
        header = json.loads(fixtures.b64u_dec(h))
        header["alg"] = alg
        return (fixtures.b64u(json.dumps(header).encode()) + "." + p
                + "." + s)

    for case, alg in [("ES256", "RS256"), ("RS256", "ES256"),
                      ("ES384", "ES256"), ("RS256", "PS256"),
                      ("PS256", "RS256"), ("EdDSA", "ES256"),
                      ("ES512", "EdDSA")]:
        doc, _, _, _, toks = pools[case]
        keys = oracle._public_keys(doc)
        assert oracle.verdict(keys, relabel(toks[0], alg)) == (
            False, "signature"), (case, alg)


def test_rfc9068_access_token_has_exactly_its_header_and_claims():
    header, body = claims.make("rfc9068_at", 3000000401, 5, "k1", "PS256")
    assert header == {"alg": "PS256", "typ": "at+jwt", "kid": "k1"}
    assert set(body) == {"iss", "exp", "aud", "sub", "client_id", "iat",
                         "jti", "scope"}
    assert body["exp"] > body["iat"] == claims.IAT
    assert isinstance(body["scope"], str) and " " in body["scope"]
    assert claims.make("rfc9068_at", 3000000401, 5, "k1", "PS256") == (
        header, body)
    assert claims.make("rfc9068_at", 3000000401, 6, "k1",
                       "PS256")[1]["jti"] != body["jti"]


# -- merging the workers' readings ------------------------------------------

def _series(total, count=4):
    return {"count": count, "sum": total, "buckets": {"70": count}}


def _worker_stats(tokens: int) -> dict:
    s = {k: _series(tokens * 1e-6) for k in (
        "prep.native", "keyset.pack", "keyset.launch", "keyset.finish",
        "keyset.decisions", "serve.native.drain", "vcache.lookup",
        "vcache.insert", "serve.native.post", "batcher.handoff",
        "batcher.dispatch", "batcher.dispatch.cpu")}
    return {"counters": {"verify_batch.tokens": tokens,
                         "worker.tokens": tokens,
                         "device.rs.tokens": tokens // 2,
                         "device.es.tokens": tokens // 2,
                         "compile.programs": 0},
            "series": s}


def _worker_trace(busy, window, gap):
    return {"busy_s": busy, "window_s": window, "devices": 1,
            "module_s": {"jit_rs_packed(1)": busy / 4,
                         "jit_es_packed(2)": busy / 2},
            "device_ops": [["while", busy / 2], ["fusion", busy / 4]],
            "idle_gaps": [["batcher.dispatch", gap],
                          ["bench.keyset.dispatch", gap / 2]]}


def test_merged_readings_sum_the_workers_and_read_every_layer():
    import run

    sizes = (8192, 4096, 12288)
    before = fleet.merge_stats([{"counters": {}, "series": {}}
                                for _ in sizes])
    after = fleet.merge_stats([_worker_stats(n) for n in sizes])
    after["counters"]["frontdoor.lookups"] = sum(sizes)
    before["counters"]["frontdoor.lookups"] = 0
    tsum = fleet.merge_traces([_worker_trace(0.5, 5.0, 0.3),
                               _worker_trace(1.0, 5.0, 0.2), {},
                               _worker_trace(2.0, 10.0, 0.1)])
    ctx = run.layer_ctx(before, after, tsum, 5.0)
    for key in ("verify_batch.tokens", "worker.tokens"):
        assert ctx["counters"][key] == sum(sizes)
    assert ctx["counters"]["frontdoor.lookups"] == sum(sizes)
    assert ctx["series"]["keyset.pack"]["count"] == 12
    assert ctx["series"]["keyset.pack"]["sum"] == pytest.approx(
        sum(sizes) * 1e-6)
    # busy and window summed: the idle share is window-weighted
    assert tsum["workers"] == 3 and tsum["devices"] == 3
    idle = spec.reader("device_idle_share")(ctx)
    assert idle == pytest.approx(100 * (1 - 3.5 / 20.0))
    assert tsum["device_ops"][0] == ["while", pytest.approx(1.75)]
    assert [g[1] for g in tsum["idle_gaps"][:3]] == [0.3, 0.2, 0.15]
    for m in spec.benchmark()["per_layer"]:
        assert spec.reader(m["name"])(ctx) is not None, m["name"]
    assert fleet.merge_traces([{}, {}]) == {}


# -- whole layout runs on the CPU -------------------------------------------

def _layout(routing: str) -> dict:
    return {"workers": 2, "router": "frontdoor", "routing": routing}


@pytest.fixture(scope="module")
def fleet_root(tmp_path_factory):
    """A copy of the benchmark, its traffic cut to a test's size, with
    ns16 under a two-worker layout for each routing (cells
    ``fl2-affinity.bulk`` and ``fl2-rr.bulk``, two chips each)."""
    root = tmp_path_factory.mktemp("fleet")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    path = root / "benchmark" / "traffic" / "bulk8k.json"
    t = json.loads(path.read_text())
    t.update(pool_tokens=256, procs=1, conns=2, req_tokens=32,
             tampered_share=0.05, warm_tokens=64,
             warm_sizes={a: [8] for a in t["warm_sizes"]})
    path.write_text(json.dumps(t))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "benchmark" / "configs" / "ns16.json")
                     .read_text())
    for routing in ("affinity", "rr"):
        name = f"fl2-{routing}"
        (root / "benchmark" / "configs" / f"{name}.json").write_text(
            json.dumps(dict(cfg, name=name, layout=_layout(routing))))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"benchmark/configs/{name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": f"{name}.bulk", "config": name,
                                   "traffic": "bulk8k", "chips": 2,
                                   "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "workloads" in m:
                m["workloads"].append(f"{name}.bulk")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def _run(root, workload, factory, seed=2 ** 35 + 3, trace=0):
    import run

    args = run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", "1.5", "--trace", str(trace)])
    return run.measure(args, factory, need_chip=False, root=root)


@pytest.mark.parametrize("routing", ["affinity", "rr"])
def test_layout_run_is_correct(fleet_root, routing):
    """Every answer through the front door and its two workers agrees
    with the reference. The one thing that may still make such a run
    not correct is the front door's own fault (PERF.md, Open
    questions): its Python fallback, taken for a frame when a pool is
    overloaded, answers a reject under a ``RemoteVerifyError: `` head
    that the native relay does not add, so a repeat of that token reads
    differently. Those repeats are counted, and are the only ones."""
    res = _run(fleet_root, f"fl2-{routing}.bulk", faults.cpu_keyset)
    checks = res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert checks["mismatched_answers"][0] == 0
    assert checks["unanswered_requests"][0] == 0
    assert checks["connection_errors"][0] == 0
    rewrapped = res["diagnostics"]["rewrapped_rejects"]
    assert checks["inconsistent_repeats"][0] == rewrapped
    assert res["correct"] == (rewrapped == 0)
    assert res["device"]["count"] == 2
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_layout_broken_timed_path_is_not_correct(fleet_root, fault):
    res = _run(fleet_root, "fl2-affinity.bulk",
               functools.partial(faults.faulty, fault))
    assert not res["correct"], (fault, res["checks"])
    assert res["checks"]["mismatched_answers"][0] > 0 or \
        res["checks"]["inconsistent_repeats"][0] > \
        res["diagnostics"]["rewrapped_rejects"], (fault, res["checks"])


def test_traced_layout_run_sums_the_workers(fleet_root):
    """A traced run's counters are the workers' summed, with the
    router's ``frontdoor.*`` counters beside them. (The CPU records no
    device plane and the CPU keyset no program span, so the readers
    find nothing here; the chip run reads every one.)"""
    import run

    args = run.parse(["--workload", "fl2-affinity.bulk", "--seed", "11",
                      "--seconds", "1.5", "--trace", "1"])
    c = spec.cell(args.workload, fleet_root)
    s = run.Session(args, c, faults.cpu_keyset, need_chip=False)
    try:
        d = s.drive(c["traffic"], 1.5, True)
        ctx = run.layer_ctx(d["before"], d["after"], d["tsum"], 1.5)
        assert ctx["counters"]["frontdoor.lookups"] > 0
        assert ctx["counters"]["worker.tokens"] > 0
        parts = s.sut.ask_all("stats")
        merged = s.sut.stats()
        assert all(p["counters"]["worker.tokens"] > 0 for p in parts)
        for key in parts[0]["counters"]:
            assert merged["counters"][key] == sum(
                p["counters"].get(key, 0) for p in parts), key
        assert merged["counters"]["frontdoor.lookups"] >= sum(
            p["counters"]["worker.tokens"] for p in parts)
    finally:
        s.close()
    assert not any(p.is_alive() for p in s.sut.procs)


def test_layout_needs_a_chip_a_worker(fleet_root):
    c = spec.cell("fl2-rr.bulk", fleet_root)
    c["workload"] = dict(c["workload"], chips=1)
    with pytest.raises(spec.SpecError):
        fleet.Fleet(c, False)


def test_layout_command_refuses_without_a_tpu(fleet_root, tmp_path):
    root = tmp_path / "co"
    shutil.copytree(fleet_root, root)
    os.symlink(os.path.join(ROOT, "cap_tpu"), root / "cap_tpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "fl2-affinity.bulk", "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
