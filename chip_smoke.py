#!/usr/bin/env python3
"""On-chip smoke of the served JWT verify path.

Drives the main path once through the entry points a user calls —
JOSE prep → ``TPUBatchKeySet`` → RNS/Pallas engines, then the same
keyset behind a CVB1 ``VerifyWorker`` — and checks every verdict
against the CPU oracle (``StaticKeySet`` / ``Validator``). Each phase
prints one line; the last line is one JSON object naming the device.

    python chip_smoke.py              # one chip: device, headline,
                                      # serve, families
    python chip_smoke.py --chips 4    # four chips: fleet of 4
                                      # one-chip workers, then a
                                      # 4-device mesh

There is no CPU branch: without a TPU it exits non-zero and prints no
result. Compile and batch seconds are printed as information only;
they are not metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

HEADLINE_N = 65536            # the north-star batch (BASELINE.json)
SERVE_REQUESTS = 16
SERVE_REQ_TOKENS = 512
FAMILY_N = 256                # tokens per family (before tampering)
# (alg, unique signatures): host signing of the PQ fixtures is slow
# (SLH-DSA-128s ~4 s/signature), so those families cycle a small pool
# of unique tokens up to FAMILY_N; the device verifies every lane.
FAMILIES = [("RS384", FAMILY_N), ("RS512", FAMILY_N),
            ("PS256", FAMILY_N), ("PS384", FAMILY_N), ("PS512", FAMILY_N),
            ("ES384", FAMILY_N), ("ES512", FAMILY_N), ("EdDSA", FAMILY_N),
            ("ML-DSA-44", 32), ("ML-DSA-65", 32), ("ML-DSA-87", 32),
            ("SLH-DSA-SHAKE-128s", 2), ("SLH-DSA-SHAKE-128f", 8)]
# Fleet requests use the serve phase's request size, so the workers'
# programs are the shapes the one-chip run already put in the cache.
FLEET_CHUNK = SERVE_REQ_TOKENS


class SmokeError(Exception):
    """A phase failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def say(phase: str, **fields) -> None:
    def fmt(v):
        if isinstance(v, float):
            return repr(v)
        if isinstance(v, str) and " " not in v:
            return v
        return json.dumps(v, separators=(",", ":"))
    print(f"{phase}: " + " ".join(f"{k}={fmt(v)}"
                                  for k, v in fields.items()), flush=True)


# ---------------------------------------------------------------------------
# verdict comparison against the CPU oracle
# ---------------------------------------------------------------------------

def oracle_verdicts(fn, tokens):
    """``fn(token)`` per UNIQUE token (claims dict or the exception),
    threaded: OpenSSL releases the GIL."""
    def one(t):
        try:
            return fn(t)
        except Exception as e:  # noqa: BLE001 - the verdict IS the error
            return e
    uniq = list(dict.fromkeys(tokens))
    with ThreadPoolExecutor(8) as ex:
        got = dict(zip(uniq, ex.map(one, uniq, chunksize=64)))
    return [got[t] for t in tokens]


def same(got, want) -> bool:
    """Claims dict equal, or the same error class. A remote reject
    carries the class name as its message prefix."""
    from cap_tpu.serve.client import RemoteVerifyError

    if isinstance(want, Exception):
        if isinstance(got, RemoteVerifyError):
            return str(got).split(":", 1)[0] == type(want).__name__
        return type(got) is type(want)
    return got == want


def compare(got, want) -> list:
    check(len(got) == len(want), f"{len(got)} verdicts for {len(want)}")
    return [i for i, (g, w) in enumerate(zip(got, want)) if not same(g, w)]


def describe(bad, got, want) -> str:
    if not bad:
        return ""
    i = bad[0]
    return (f"{len(bad)} verdicts differ from the oracle; first #{i}: "
            f"got {got[i]!r:.120} want {want[i]!r:.120}")


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def tampered_tokens(tokens, signers):
    """Flipped signature, swapped payload and an expired token for each
    signer family present, plus one ``alg=none`` token."""
    from cap_tpu import testing
    from cap_tpu.jwt.jose import b64url_encode

    out = []
    by_alg = {}
    for t in tokens:
        alg = json.loads(_b64d(t.split(".")[0]))["alg"]
        by_alg.setdefault(alg, []).append(t)
    for alg, toks in sorted(by_alg.items()):
        a, b = toks[0], toks[1 % len(toks)]
        h, p, s = a.split(".")
        i = len(s) // 2
        out.append(f"{h}.{p}.{s[:i]}{'A' if s[i] != 'A' else 'B'}"
                   f"{s[i + 1:]}")
        out.append(f"{h}.{b.split('.')[1]}.{s}")
        priv, _, kid = next(x for x in signers if x[1] == alg)
        out.append(testing.sign_jwt(
            priv, alg, testing.default_claims(now=time.time() - 7200,
                                              ttl=60), kid=kid))
    h = b64url_encode(b'{"alg":"none","typ":"JWT"}')
    out.append(f"{h}.{tokens[0].split('.')[1]}.")
    return out


def _b64d(seg: str) -> bytes:
    import base64

    return base64.urlsafe_b64decode(seg + "=" * (-len(seg) % 4))


def kid_oracle(jwks):
    """The CPU oracle under the keyset's kid semantics: a known kid
    selects its key, an unknown or absent kid tries every key — a
    ``StaticKeySet`` per kid (trial verification, no engine code)."""
    from cap_tpu.jwt.keyset import KeySet, StaticKeySet

    full = StaticKeySet([j.key for j in jwks])
    by_kid = {}
    for j in jwks:
        by_kid.setdefault(j.kid, []).append(j.key)
    per_kid = {k: StaticKeySet(v) for k, v in by_kid.items()}

    class KidOracle(KeySet):
        def verify_signature(self, token):
            try:
                kid = json.loads(_b64d(token.split(".")[0])).get("kid")
            except Exception:  # noqa: BLE001 - malformed: full trial
                kid = None
            return per_kid.get(kid, full).verify_signature(token)

    return KidOracle()


def headline_fixtures(n: int):
    from cap_tpu import testing

    jwks, signers = testing.headline_keys()
    tokens = testing.sign_unique_jwts(signers, n)
    tampered = tampered_tokens(tokens, signers)
    oracle = kid_oracle(jwks)
    batch = tokens + tampered
    want = oracle_verdicts(oracle.verify_signature, batch)
    return jwks, signers, batch, want, oracle


def jwks_file(jwks, tmp: str) -> str:
    from cap_tpu.jwt.jwk import serialize_public_key

    path = os.path.join(tmp, "jwks.json")
    with open(path, "w") as f:
        json.dump({"keys": [serialize_public_key(j.key, kid=j.kid)
                            for j in jwks]}, f)
    return path


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def require_tpu(count: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeError(f"no TPU found: JAX reports platform "
                         f"{devs[0].platform!r}")
    check(len(devs) >= count,
          f"need {count} TPU chips, JAX reports {len(devs)}")
    return devs


def build_native() -> dict:
    """Rebuild the native runtime from the committed sources; returns
    ``{library: built?}`` (a build failure is never a quiet fallback
    here)."""
    from cap_tpu import _build

    t0 = time.time()
    _build.build_native(force=True)
    built = {}
    # libcapruntime.so holds the JOSE prep AND the serve chain;
    # libcapclient.so is the native CVB1 client.
    for name, rel in (("native_runtime", "runtime/native/libcapruntime.so"),
                      ("native_client", "serve/native/libcapclient.so")):
        path = os.path.join(_build._PKG, rel)
        built[name] = (os.path.exists(path)
                       and os.path.getmtime(path) >= t0 - 1)
    built["native_build_s"] = round(time.time() - t0, 1)
    return built


def phase_device(devs, built: dict) -> None:
    from importlib.metadata import version

    import jax

    from cap_tpu import compile_cache
    from cap_tpu.tpu import (
        pallas_edw,
        pallas_keccak,
        pallas_madd,
        pallas_ntt,
        pallas_redc,
        rns,
    )

    paths = {"rns": rns.use_rns(), "pallas_redc": pallas_redc.enabled(),
             "pallas_madd": pallas_madd.enabled(),
             "pallas_edw": pallas_edw.enabled(),
             "pallas_ntt": pallas_ntt.enabled(),
             "pallas_keccak": pallas_keccak.enabled()}
    say("device", platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs), jax=jax.__version__, libtpu=version("libtpu"),
        cache=compile_cache.enable(),
        **{k: "on" if v else "off" for k, v in paths.items()},
        **{k: v if not isinstance(v, bool) else
           ("built" if v else "FAILED") for k, v in built.items()})
    off = [k for k, v in paths.items() if not v]
    check(not off, f"kernel paths off on TPU: {off}")
    failed = [k for k, v in built.items() if v is False]
    check(not failed, f"native build failed: {failed}")


def _fallback_count() -> int:
    from cap_tpu import telemetry

    return telemetry.active().counters().get("cpu_fallback.tokens", 0)


def phase_headline(n: int):
    from cap_tpu.errors import InvalidParameterError
    from cap_tpu import testing
    from cap_tpu.jwt.tpu_keyset import TPUBatchKeySet
    from cap_tpu.jwt.validator import Validator

    t0 = time.perf_counter()
    jwks, signers, batch, want, oracle = headline_fixtures(n)
    fixture_s = time.perf_counter() - t0
    tampered = batch[n:]
    fb0 = _fallback_count()
    ks = TPUBatchKeySet(jwks, cpu_fallback=False)
    t0 = time.perf_counter()
    got = ks.verify_batch(batch)
    first_s = time.perf_counter() - t0
    bad = compare(got, want)
    check(not bad, "verify_batch: " + describe(bad, got, want))
    n_stream = 0
    t0 = time.perf_counter()
    for out in ks.verify_stream([batch, batch]):
        n_stream += 1
        bad = compare(out, want)
        check(not bad, "verify_stream: " + describe(bad, out, want))
    stream_s = time.perf_counter() - t0
    fallback = _fallback_count() - fb0

    # Claims validation of the tampered slice (expiry included).
    vgot = Validator(ks).validate_batch(tampered)
    vwant = oracle_verdicts(Validator(oracle).validate, tampered)
    vbad = compare(vgot, vwant)
    check(not vbad, "validate_batch: " + describe(vbad, vgot, vwant))

    # An unknown kid cannot be routed to a device table (go-jose trial
    # semantics): with cpu_fallback=False the keyset refuses it; with
    # the fallback on, exactly that token is counted on the CPU path.
    priv, alg, _ = signers[0]
    unknown = testing.sign_jwt(priv, alg, testing.default_claims(),
                               kid="rs-unknown")
    refused = ks.verify_batch([unknown])[0]
    check(isinstance(refused, InvalidParameterError),
          f"unknown kid was not refused: {refused!r:.120}")
    fb1 = _fallback_count()
    unk = TPUBatchKeySet(jwks).verify_batch([unknown])[0]
    unk_fb = _fallback_count() - fb1
    check(same(unk, oracle_verdicts(oracle.verify_signature,
                                    [unknown])[0]) and unk_fb == 1,
          f"unknown kid on the fallback keyset: {unk!r:.120} "
          f"cpu_fallback.tokens={unk_fb}")
    n_ok = sum(1 for w in want if not isinstance(w, Exception))
    say("headline", tokens=n, tampered=len(tampered),
        match=f"{len(batch)}/{len(batch)}", accepted=n_ok,
        rejected=len(batch) - n_ok,
        stream_batches=n_stream,
        validate_tampered=f"{len(tampered)}/{len(tampered)}",
        **{"cpu_fallback.tokens": fallback},
        unknown_kid="refused;fallback_keyset_counts_1",
        fixture_s=round(fixture_s, 1), first_batch_s=round(first_s, 2),
        stream_s=round(stream_s, 3),
        info_only_vps=round(n_stream * len(batch) / stream_s))
    check(fallback == 0, f"cpu_fallback.tokens={fallback}")
    return jwks, batch, want


def phase_serve(jwks, batch, want) -> None:
    from cap_tpu.fleet.worker_main import make_keyset
    from cap_tpu.serve.client import VerifyClient
    from cap_tpu.serve.worker import VerifyWorker

    n_plain = SERVE_REQ_TOKENS - 8
    tampered = [i for i, w in enumerate(want) if isinstance(w, Exception)]
    reqs = []
    for r in range(SERVE_REQUESTS):
        idx = list(range(r * n_plain, (r + 1) * n_plain))
        idx += [tampered[(r * 8 + k) % len(tampered)] for k in range(8)]
        reqs.append(idx)
    fb0 = _fallback_count()
    with tempfile.TemporaryDirectory(prefix="cap-smoke-") as tmp:
        ks = make_keyset(f"jwks:{jwks_file(jwks, tmp)}")
        worker = VerifyWorker(ks, target_batch=SERVE_REQ_TOKENS,
                              max_batch=SERVE_REQ_TOKENS,
                              serve_native=True)
        try:
            check(worker.serve_chain == "native",
                  f"serve chain is {worker.serve_chain}, not native")
            t0 = time.perf_counter()
            with VerifyClient(port=worker.address[1],
                              timeout=900.0) as client:
                outs = list(client.verify_stream(
                    [[batch[i] for i in idx] for idx in reqs], depth=4))
            serve_s = time.perf_counter() - t0
        finally:
            worker.close()
    n_bad = 0
    for idx, out in zip(reqs, outs):
        bad = compare(out, [want[i] for i in idx])
        n_bad += len(bad)
        check(not bad, "serve: " + describe(
            bad, out, [want[i] for i in idx]))
    fallback = _fallback_count() - fb0
    total = sum(len(r) for r in reqs)
    say("serve", requests=len(reqs), tokens=total,
        match=f"{total - n_bad}/{total}", chain=worker.serve_chain,
        **{"cpu_fallback.tokens": fallback},
        wall_s=round(serve_s, 2))
    check(fallback == 0, f"serve cpu_fallback.tokens={fallback}")


def family_fixtures():
    from cap_tpu import testing
    from cap_tpu.jwt.jwk import JWK

    jwks, tokens = [], []
    for alg, n_unique in FAMILIES:
        priv, pub = testing.generate_keys(alg)
        kid = f"fam-{alg}"
        jwks.append(JWK(pub, kid=kid))
        signers = [(priv, alg, kid)]
        uniq = testing.sign_unique_jwts(signers, n_unique)
        toks = [uniq[i % n_unique] for i in range(FAMILY_N)]
        tokens.append((alg, toks, tampered_tokens(uniq, signers)[:2]))
    return jwks, tokens


def phase_families() -> None:
    from cap_tpu.jwt.tpu_keyset import TPUBatchKeySet

    t0 = time.perf_counter()
    jwks, fam = family_fixtures()
    batch = [t for _, toks, bad in fam for t in toks + bad]
    oracle = kid_oracle(jwks)
    want = oracle_verdicts(oracle.verify_signature, batch)
    fixture_s = time.perf_counter() - t0
    fb0 = _fallback_count()
    ks = TPUBatchKeySet(jwks, cpu_fallback=False)
    t0 = time.perf_counter()
    got = ks.verify_batch(batch)
    first_s = time.perf_counter() - t0
    fallback = _fallback_count() - fb0
    bad = set(compare(got, want))
    per, off = {}, 0
    for alg, toks, tam in fam:
        m = len(toks) + len(tam)
        ok = m - sum(1 for i in range(off, off + m) if i in bad)
        n_acc = sum(1 for i in range(off, off + m)
                    if not isinstance(want[i], Exception))
        check(n_acc == len(toks), f"{alg}: oracle accepted {n_acc} of "
              f"{len(toks)} clean tokens")
        per[alg] = f"{ok}/{m}"
        off += m
    say("families", **per, **{"cpu_fallback.tokens": fallback},
        fixture_s=round(fixture_s, 1), first_batch_s=round(first_s, 2))
    check(not bad, "families: " + describe(sorted(bad), got, want))
    check(fallback == 0, f"families cpu_fallback.tokens={fallback}")


def phase_fleet(jwks, batch, want) -> None:
    """Four one-chip workers behind FleetClient; the parent never
    touches JAX while they hold the chips."""
    from cap_tpu.fleet import FleetClient, WorkerPool

    check("jax" not in sys.modules, "parent imported jax before the fleet")
    chunks = [batch[i:i + FLEET_CHUNK]
              for i in range(0, len(batch), FLEET_CHUNK)]
    with tempfile.TemporaryDirectory(prefix="cap-smoke-") as tmp:
        pool = WorkerPool(4, keyset_spec=f"jwks:{jwks_file(jwks, tmp)}",
                          platform="tpu", target_batch=FLEET_CHUNK,
                          max_batch=FLEET_CHUNK,
                          spawn_timeout=900.0, ping_interval=1.0,
                          ping_timeout=30.0, hung_after=30,
                          max_restarts=0)
        try:
            check(pool.wait_all_ready(900.0), "fleet did not come up: "
                  f"{pool.device_report()}")
            report = pool.device_report()
            fc = FleetClient(pool, attempt_timeout=900.0,
                             total_deadline=1800.0, max_rounds=1)
            t0 = time.perf_counter()
            with ThreadPoolExecutor(4) as ex:
                outs = list(ex.map(fc.verify_batch, chunks))
            fleet_s = time.perf_counter() - t0
            fc.close()
        finally:
            pool.close()
    got = [r for out in outs for r in out]
    bad = compare(got, want)
    ready = [f"{w}:platform={p},device={d},chip={c}"
             for w, (p, d, c) in sorted(report.items())]
    say("fleet", workers=len(report), ready=ready,
        match=f"{len(got) - len(bad)}/{len(got)}",
        wall_s=round(fleet_s, 2))
    check(not bad, "fleet: " + describe(bad, got, want))
    check(all(p == "tpu" and d is not None and "," not in d
              for p, d, _ in report.values()),
          f"a worker is not on exactly one TPU device: {report}")
    check(len({c for _, _, c in report.values()}) == 4,
          f"workers do not report four distinct chips: {report}")


def phase_mesh(jwks, batch, want, n_clean: int) -> None:
    from cap_tpu.jwt.tpu_keyset import TPUBatchKeySet, resident_dispatchers
    from cap_tpu.parallel.mesh import make_mesh

    ks = TPUBatchKeySet(jwks, mesh=make_mesh(4), cpu_fallback=False)
    got = ks.verify_batch(batch)
    bad = compare(got, want)
    check(not bad, "mesh: " + describe(bad, got, want))
    # The placed packed records (the same shard_batch placement the
    # verify path uses), read back per device.
    records = []
    resident_dispatchers(ks, batch[:n_clean], records_out=records)
    rows = [sorted((s.device.id, s.data.shape[0])
                   for s in rec.addressable_shards) for rec in records]
    say("mesh", devices=4, match=f"{len(got)}/{len(got)}",
        records=[rec.shape[0] for rec in records],
        shard_rows=[[r for _, r in rec] for rec in rows],
        shard_devices=[[d for d, _ in rec] for rec in rows])
    for rec, r in zip(records, rows):
        check(len(r) == 4 and len({d for d, _ in r}) == 4
              and all(x == rec.shape[0] // 4 for _, x in r),
              f"record of {rec.shape[0]} rows is not split n/4: {r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        if args.chips == 1:
            devs = require_tpu(1)
            phase_device(devs, build_native())
            from cap_tpu import telemetry

            telemetry.enable()
            jwks, batch, want = phase_headline(HEADLINE_N)
            phase_serve(jwks, batch, want)
            phase_families()
        else:
            # The parent stays off JAX until the fleet is gone; fail
            # fast where the environment already rules the chip out.
            plat = os.environ.get("JAX_PLATFORMS")
            check(not plat or "tpu" in plat,
                  f"no TPU found: JAX_PLATFORMS={plat}")
            built = build_native()
            jwks, _, batch, want, _ = headline_fixtures(HEADLINE_N)
            phase_fleet(jwks, batch, want)
            devs = require_tpu(4)
            phase_device(devs, built)
            phase_mesh(jwks, batch, want, HEADLINE_N)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    import jax

    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
