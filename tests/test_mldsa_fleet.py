"""Hybrid ES256 → ML-DSA keyplane migration under load (the headline
post-quantum scenario, ROADMAP open item #2).

A tenant serving ES256 traffic is migrated to ML-DSA-44 through the
keyplane, live, against REAL-ENGINE subprocess workers
(``--keyset jwks:``, no stubs — this is the scenario enterprises will
run this decade):

  epoch 0   workers boot on the tenant's ES256 JWKS
  epoch 2   hybrid push: ES256 + ML-DSA keys (both families verify)
  epoch 3   ML-DSA-only push with a grace window — retired ES kids
            still resolve, so in-flight classical tokens don't flap —
            with ``kill -9`` landing on one worker mid-push

Acceptance (asserted throughout): zero wrong verdicts, zero lost
submissions, fleet convergence on every pushed epoch including after
the SIGKILL respawn, and the rotation-lag SLO green over the run's
telemetry. Everything is dependency-free: ES256 rides the
HostECPublicKey pure-int path, ML-DSA the in-repo FIPS 204 stack.
"""

import hashlib
import json
import signal
import threading
import time

import pytest

from cap_tpu import telemetry
from cap_tpu.fleet import FleetClient, WorkerPool
from cap_tpu.fleet.chaos import kill9
from cap_tpu.jwt.jose import b64url_encode
from cap_tpu.jwt.jwk import parse_jwks, serialize_public_key
from cap_tpu.obs import slo as obs_slo
from cap_tpu.tpu import mldsa
from cap_tpu.tpu.ec import HostECPublicKey, curve, host_ecdsa_sign, scalar_mult

HARD_TIMEOUT_S = 300

# Pinned fixture scalars (test-only, never real credentials).
EC_D = 0x2C9F1B3A8D4E6F5C7B8A9D0E1F2A3B4C5D6E7F8091A2B3C4D5E6F708192A3B4C


@pytest.fixture(autouse=True)
def _hard_timeout():
    def on_alarm(signum, frame):
        raise TimeoutError(
            f"hybrid migration test exceeded hard {HARD_TIMEOUT_S}s "
            "timeout")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(HARD_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _jws(alg: str, kid: str, claims: dict, signer) -> str:
    h = b64url_encode(json.dumps({"alg": alg, "kid": kid},
                                 separators=(",", ":")).encode())
    p = b64url_encode(json.dumps(claims,
                                 separators=(",", ":")).encode())
    return h + "." + p + "." + b64url_encode(signer((h + "." + p).encode()))


def _tamper(tok: str) -> str:
    return tok[:-6] + ("AAAAAA" if not tok.endswith("AAAAAA")
                       else "BBBBBB")


@pytest.fixture(scope="module")
def tenant():
    """The tenant's key material + pre-signed token pools."""
    cp = curve("P-256")
    qx, qy = scalar_mult(cp, EC_D, (cp.gx, cp.gy))
    es_key = HostECPublicKey("P-256", qx, qy)

    def es_sign(si: bytes) -> bytes:
        e = int.from_bytes(hashlib.sha256(si).digest(), "big")
        k = (int.from_bytes(hashlib.sha256(b"nonce" + si).digest(),
                            "big") % (cp.n - 2)) + 1
        r, s = host_ecdsa_sign("P-256", EC_D, e, k)
        return r.to_bytes(32, "big") + s.to_bytes(32, "big")

    pq_priv, pq_pub = mldsa.keygen("ML-DSA-44", bytes([42]) * 32)
    from cap_tpu.tpu import slhdsa

    slh_priv, slh_pub = slhdsa.keygen("SLH-DSA-SHAKE-128f",
                                      bytes([43]) * 32)

    es_jwk = serialize_public_key(es_key, kid="tenant-es")
    pq_jwk = serialize_public_key(pq_pub, kid="tenant-pq")
    slh_jwk = serialize_public_key(slh_pub, kid="tenant-slh")

    es_toks = [_jws("ES256", "tenant-es", {"sub": f"es-{i}"}, es_sign)
               for i in range(4)]
    pq_toks = [_jws("ML-DSA-44", "tenant-pq", {"sub": f"pq-{i}"},
                    pq_priv.sign) for i in range(4)]
    slh_toks = [_jws("SLH-DSA-SHAKE-128f", "tenant-slh",
                     {"sub": f"slh-{i}"}, slh_priv.sign)
                for i in range(4)]
    return {
        "es_jwks": {"keys": [es_jwk]},
        "hybrid_jwks": {"keys": [es_jwk, pq_jwk]},
        "pq_jwks": {"keys": [pq_jwk]},
        "pq_slh_jwks": {"keys": [pq_jwk, slh_jwk]},
        "slh_jwks": {"keys": [slh_jwk]},
        "union_jwks": {"keys": [es_jwk, pq_jwk, slh_jwk]},
        "es_toks": es_toks,
        "pq_toks": pq_toks,
        "slh_toks": slh_toks,
        "es_bad": [_tamper(t) for t in es_toks],
        "pq_bad": [_tamper(t) for t in pq_toks],
        "slh_bad": [_tamper(t) for t in slh_toks],
    }


def _wait_epochs(pool, epoch, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(e == epoch for e in pool.key_epochs().values()):
            return True
        time.sleep(0.1)
    return False


@pytest.mark.chaos
def test_hybrid_migration_es256_to_mldsa_under_load(tenant, tmp_path):
    """The full migration with kill -9 mid-final-push: zero wrong
    verdicts, zero lost submissions, convergence, rotation SLO green."""
    jwks_path = tmp_path / "tenant_es.json"
    jwks_path.write_text(json.dumps(tenant["es_jwks"]))

    rec = telemetry.enable()
    pool = WorkerPool(2, keyset_spec=f"jwks:{jwks_path}", platform="cpu",
                      ping_interval=0.5, max_restarts=20,
                      spawn_timeout=120, max_wait_ms=2.0)
    try:
        assert pool.wait_all_ready(120), "real-engine fleet not ready"
        # The terminal-fallback oracle holds the UNION key set: it can
        # only fire on total fleet failure, where phase-accurate
        # verdicts are unknowable anyway — bad tokens still always
        # reject (parse_jwks is the same code the workers run).
        fallback = _FallbackKeySet(tenant["hybrid_jwks"])
        cl = FleetClient(pool, fallback=fallback, attempt_timeout=5.0,
                         total_deadline=60.0, rr_seed=0)

        ph2_pushed = threading.Event()    # hybrid keys going out
        ph2_converged = threading.Event()
        stop = threading.Event()
        failures = []
        batches = []

        def driver(d):
            i = 0
            while not stop.is_set() and not failures:
                toks = [tenant["es_toks"][i % 4],
                        tenant["es_bad"][i % 4],
                        tenant["pq_toks"][(i + d) % 4],
                        tenant["pq_bad"][(i + d) % 4]]
                submitted_after_conv = ph2_converged.is_set()
                try:
                    res = cl.verify_batch(toks)
                except Exception as e:  # noqa: BLE001
                    failures.append(f"driver {d}: {e!r}")
                    return
                now_pushed = ph2_pushed.is_set()
                if len(res) != len(toks):
                    failures.append(f"driver {d}: lost submissions")
                    return
                es_ok, es_bad, pq_ok, pq_bad = [
                    not isinstance(r, Exception) for r in res]
                if not es_ok:
                    failures.append(
                        f"driver {d}: valid ES256 token rejected")
                if es_bad or pq_bad:
                    failures.append(
                        f"driver {d}: FORGED token accepted")
                if pq_ok and not now_pushed:
                    failures.append(
                        f"driver {d}: ML-DSA accepted before any "
                        "ML-DSA key was pushed")
                if not pq_ok and submitted_after_conv:
                    failures.append(
                        f"driver {d}: valid ML-DSA token rejected "
                        "after fleet convergence")
                if pq_ok and res[2] != {"sub": f"pq-{(i + d) % 4}"}:
                    failures.append(f"driver {d}: wrong ML-DSA claims")
                batches.append(len(toks))
                i += 1

        threads = [threading.Thread(target=driver, args=(d,))
                   for d in range(3)]
        for t in threads:
            t.start()
        time.sleep(1.0)               # ES-only traffic flows first

        # Phase 2: hybrid key set — both families now verify.
        ph2_pushed.set()
        pool.push_keys(tenant["hybrid_jwks"], epoch=2)
        assert _wait_epochs(pool, 2, timeout=60), \
            f"no convergence on hybrid epoch: {pool.key_epochs()}"
        ph2_converged.set()
        time.sleep(1.0)

        # Phase 3: ML-DSA only, with kill -9 landing mid-push. The
        # worker-side grace window keeps retired ES kids resolving, so
        # classical traffic keeps verifying through the cutover.
        victim = pool.pid(0)
        push_started = threading.Event()

        def killer():
            push_started.wait(timeout=10)
            kill9(victim)

        kt = threading.Thread(target=killer)
        kt.start()
        push_started.set()
        acks = pool.push_keys(tenant["pq_jwks"], epoch=3)
        kt.join(timeout=10)
        assert pool.keys_epoch() == 3
        assert 3 in acks.values(), "no worker acked the final push"
        assert _wait_epochs(pool, 3, timeout=120), \
            f"no convergence after kill -9 mid-push: {pool.key_epochs()}"
        assert pool.pid(0) != victim, "victim was not respawned"
        assert pool.epoch_skew() == 0
        time.sleep(1.0)

        stop.set()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive(), "driver wedged"
        assert not failures, failures
        assert sum(batches) > 0
        # Decision counters saw BOTH families flow on the router.
        c = rec.counters()
        assert c.get("decision.router.family.es", 0) > 0
        assert c.get("decision.router.family.mldsa44", 0) > 0
        # Rotation SLO: lag + push-failure budget green over the run.
        results = {r["name"]: r
                   for r in obs_slo.evaluate_once(rec.snapshot())}
        assert results["rotation_lag"]["ok"], results["rotation_lag"]
    finally:
        pool.close()
        telemetry.disable()


class _FallbackKeySet:
    """Terminal-fallback oracle: CPU verify over the union JWKS."""

    def __init__(self, jwks_doc):
        from cap_tpu.jwt.keyset import StaticKeySet

        self._ks = StaticKeySet([j.key for j in parse_jwks(jwks_doc)])

    def verify_batch(self, tokens):
        return self._ks.verify_batch(tokens)


@pytest.mark.chaos
def test_hybrid_migration_mldsa_to_slhdsa_under_load(tenant, tmp_path):
    """The r17 second leg: ES256 → ML-DSA → SLH-DSA, kill -9 landing
    mid-FINAL-push (the SLH-DSA-only cutover). Same invariants as the
    classical→lattice migration above — zero wrong verdicts, zero
    lost submissions, convergence after respawn — now across a second
    family boundary where the replacement engine is the batched
    Keccak hash forest."""
    jwks_path = tmp_path / "tenant_hybrid.json"
    jwks_path.write_text(json.dumps(tenant["hybrid_jwks"]))

    rec = telemetry.enable()
    pool = WorkerPool(2, keyset_spec=f"jwks:{jwks_path}", platform="cpu",
                      ping_interval=0.5, max_restarts=20,
                      spawn_timeout=120, max_wait_ms=2.0)
    try:
        assert pool.wait_all_ready(120), "real-engine fleet not ready"
        fallback = _FallbackKeySet(tenant["union_jwks"])
        # Generous per-attempt budget: a worker's FIRST SLH-DSA batch
        # compiles the hash-forest graph (tens of seconds on this
        # 1-core host) — slow is acceptable, wrong is not.
        cl = FleetClient(pool, fallback=fallback, attempt_timeout=60.0,
                         total_deadline=180.0, rr_seed=0)

        slh_pushed = threading.Event()
        slh_converged = threading.Event()
        stop = threading.Event()
        failures = []
        batches = []

        def driver(d):
            i = 0
            while not stop.is_set() and not failures:
                toks = [tenant["pq_toks"][i % 4],
                        tenant["pq_bad"][i % 4],
                        tenant["slh_toks"][(i + d) % 4],
                        tenant["slh_bad"][(i + d) % 4]]
                after_conv = slh_converged.is_set()
                try:
                    res = cl.verify_batch(toks)
                except Exception as e:  # noqa: BLE001
                    failures.append(f"driver {d}: {e!r}")
                    return
                now_pushed = slh_pushed.is_set()
                if len(res) != len(toks):
                    failures.append(f"driver {d}: lost submissions")
                    return
                pq_ok, pq_bad, slh_ok, slh_bad = [
                    not isinstance(r, Exception) for r in res]
                if not pq_ok:
                    failures.append(
                        f"driver {d}: valid ML-DSA token rejected")
                if pq_bad or slh_bad:
                    failures.append(
                        f"driver {d}: FORGED token accepted")
                if slh_ok and not now_pushed:
                    failures.append(
                        f"driver {d}: SLH-DSA accepted before any "
                        "SLH-DSA key was pushed")
                if not slh_ok and after_conv:
                    failures.append(
                        f"driver {d}: valid SLH-DSA token rejected "
                        "after fleet convergence")
                if slh_ok and res[2] != {"sub": f"slh-{(i + d) % 4}"}:
                    failures.append(f"driver {d}: wrong SLH claims")
                batches.append(len(toks))
                i += 1

        threads = [threading.Thread(target=driver, args=(d,))
                   for d in range(2)]
        for t in threads:
            t.start()
        time.sleep(1.0)               # hybrid ES+ML traffic flows

        # Phase 2: ML-DSA + SLH-DSA hybrid (the second hybrid window).
        slh_pushed.set()
        pool.push_keys(tenant["pq_slh_jwks"], epoch=2)
        assert _wait_epochs(pool, 2, timeout=120), \
            f"no convergence on pq+slh epoch: {pool.key_epochs()}"
        # Warm the SLH engines (compile) before declaring convergence
        # to the drivers — slow-compile rejects would be a test
        # artifact, not a correctness signal.
        warm = cl.verify_batch(tenant["slh_toks"])
        assert all(not isinstance(r, Exception) for r in warm), warm
        slh_converged.set()
        time.sleep(1.0)

        # Phase 3: SLH-DSA only, kill -9 mid-push; grace keeps the
        # retired ML-DSA kid resolving through the cutover.
        victim = pool.pid(0)
        push_started = threading.Event()

        def killer():
            push_started.wait(timeout=10)
            kill9(victim)

        kt = threading.Thread(target=killer)
        kt.start()
        push_started.set()
        acks = pool.push_keys(tenant["slh_jwks"], epoch=3)
        kt.join(timeout=10)
        assert pool.keys_epoch() == 3
        assert 3 in acks.values(), "no worker acked the final push"
        assert _wait_epochs(pool, 3, timeout=180), \
            f"no convergence after kill -9 mid-push: {pool.key_epochs()}"
        assert pool.pid(0) != victim, "victim was not respawned"
        assert pool.epoch_skew() == 0
        time.sleep(1.0)

        stop.set()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive(), "driver wedged"
        assert not failures, failures
        assert sum(batches) > 0
        c = rec.counters()
        assert c.get("decision.router.family.mldsa44", 0) > 0
        assert c.get("decision.router.family.slhdsa128f", 0) > 0
        results = {r["name"]: r
                   for r in obs_slo.evaluate_once(rec.snapshot())}
        assert results["rotation_lag"]["ok"], results["rotation_lag"]
    finally:
        pool.close()
        telemetry.disable()
