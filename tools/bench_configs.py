#!/usr/bin/env python3
"""The BASELINE.md config ladder (configs ①-⑤), one JSON line each.

① 1k RS256, single 2048-bit key, StaticKeySet   (CPU reference path)
② RS256/384/512 mix, 2048+4096-bit, 8-key JWKS  (batched RSA + gather)
③ ES256/ES384 on P-256/P-384 JWKS               (batched ECDSA)
④ PS256 + EdDSA mix, rotating kids              (PSS + Ed25519)
⑤ end-to-end Provider.verify_id_token_batch over OIDC discovery JWKS
   (the full RP stack sharing the accelerated KeySet path)

CAP_CFG_BATCH scales the per-config batch (default 16384; config ①
fixed at 1000 per the ladder, ⑤ at min(batch, 100k)).
"""

import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from cap_tpu import compile_cache

compile_cache.enable()

from cap_tpu import testing as T
from cap_tpu.jwt import StaticKeySet
from cap_tpu.jwt.jwk import JWK
from cap_tpu.jwt.tpu_keyset import TPUBatchKeySet

BATCH = int(os.environ.get("CAP_CFG_BATCH", 1 << 14))
REPS = int(os.environ.get("CAP_CFG_REPS", 3))


def tile(unique, n):
    return (unique * (n // len(unique) + 1))[:n]


def sign_unique(signers, n):
    """n UNIQUE tokens (distinct sub/jti), signed across threads."""
    return T.sign_unique_jwts(signers, n)


def rate(fn, n):
    fn()
    vals = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        fn()
        vals.append(n / (time.perf_counter() - t0))
    return statistics.median(vals)


def rate_stream(ks, toks, window: int = 4):
    """Steady-state pipelined rate: median completion interval over
    ``window`` back-to-back batches (2-deep), pipeline fill dropped —
    the same methodology as bench.py's headline. Returns
    (rate, effective_h2d_mbps): the device configs here are WIRE-bound
    when the host link is slow, so each number carries the link
    throughput it was measured at (docs/PERF.md)."""
    from cap_tpu import telemetry

    ks.verify_batch(toks)                      # warm compile
    rec = telemetry.enable()
    done = []
    for out in ks.verify_stream(toks for _ in range(window + 1)):
        done.append(time.perf_counter())
        assert not any(isinstance(r, Exception) for r in out)
    telemetry.disable()
    h2d = rec.counters().get("h2d.bytes", 0) / (window + 1)
    intervals = [b - a for a, b in zip(done, done[1:])]
    med = statistics.median(intervals)
    return len(toks) / med, (h2d / med) / (1 << 20)


def emit(name, value, n, eff_mbps=None):
    rec = {"metric": name, "value": round(value, 1),
           "unit": "verifies/sec", "batch": n}
    if eff_mbps is not None:
        rec["wire_effective_mbps"] = round(eff_mbps, 2)
    print(json.dumps(rec), flush=True)


def config1():
    n = 1000
    priv, pub = T.generate_keys("RS256", rsa_bits=2048)
    ks = StaticKeySet([pub])
    toks = tile([T.sign_jwt(priv, "RS256", T.default_claims(ttl=86400))
                 for _ in range(32)], n)

    def run():
        for t in toks:
            ks.verify_signature(t)

    emit("cfg1_rs256_static_cpu", rate(run, n), n)


def config2():
    n = BATCH
    jwks, signers = [], []
    for i, (alg, bits) in enumerate(
            [("RS256", 2048)] * 3 + [("RS384", 2048)] * 2
            + [("RS512", 4096)] * 2 + [("RS256", 4096)]):
        priv, pub = T.generate_keys(alg, rsa_bits=bits)
        jwks.append(JWK(pub, kid=f"k{i}"))
        signers.append((priv, alg, f"k{i}"))
    toks = sign_unique(signers, n)
    ks = TPUBatchKeySet(jwks)
    # rate_stream warms compile and asserts every batch verifies
    r, eff = rate_stream(ks, toks)
    emit("cfg2_rs_mix_8key_jwks", r, n, eff)


def config3():
    n = BATCH
    jwks, signers = [], []
    for i in range(4):
        priv, pub = T.generate_keys("ES256")
        jwks.append(JWK(pub, kid=f"p256-{i}"))
        signers.append((priv, "ES256", f"p256-{i}"))
    for i in range(4):
        priv, pub = T.generate_keys("ES384")
        jwks.append(JWK(pub, kid=f"p384-{i}"))
        signers.append((priv, "ES384", f"p384-{i}"))
    toks = sign_unique(signers, n)
    ks = TPUBatchKeySet(jwks)
    # rate_stream warms compile and asserts every batch verifies
    r, eff = rate_stream(ks, toks)
    emit("cfg3_es256_es384", r, n, eff)


def config4():
    n = BATCH
    jwks, signers = [], []
    for i in range(4):
        priv, pub = T.generate_keys("PS256", rsa_bits=2048)
        jwks.append(JWK(pub, kid=f"ps-{i}"))
        signers.append((priv, "PS256", f"ps-{i}"))
    for i in range(4):
        priv, pub = T.generate_keys("EdDSA")
        jwks.append(JWK(pub, kid=f"ed-{i}"))
        signers.append((priv, "EdDSA", f"ed-{i}"))
    toks = sign_unique(signers, n)
    ks = TPUBatchKeySet(jwks)
    # rate_stream warms compile and asserts every batch verifies
    r, eff = rate_stream(ks, toks)
    emit("cfg4_ps256_eddsa", r, n, eff)


def config5():
    from cap_tpu.oidc import Config, Provider, Request
    from cap_tpu.oidc.testing import TestProvider

    n = min(BATCH, 100_000)
    idp = TestProvider().start()
    try:
        cfg = Config(issuer=idp.issuer(), client_id=idp.client_id,
                     client_secret=idp.client_secret,
                     supported_signing_algs=["ES256"],
                     allowed_redirect_urls=["http://127.0.0.1:1/cb"],
                     provider_ca=idp.ca_cert())
        # accelerated KeySet shared by the whole RP stack, built from
        # the IdP's signing key (the discovery JWKS equivalent)
        priv, pub, alg, kid = idp.signing_keys()
        ks = TPUBatchKeySet([JWK(pub, kid=kid)])
        p = Provider(cfg, keyset=ks)
        req = Request(3600.0, "http://127.0.0.1:1/cb")
        claims = T.default_claims(issuer=idp.issuer(), ttl=3600.0,
                                  aud=[idp.client_id])
        claims["nonce"] = req.nonce()
        toks = tile([T.sign_jwt(priv, alg, claims, kid=kid)
                     for _ in range(128)], n)

        def run():
            out = p.verify_id_token_batch(toks, req)
            bad = sum(1 for r in out if isinstance(r, Exception))
            assert bad == 0, bad

        emit("cfg5_oidc_verify_id_token_e2e", rate(run, n), n)

        def run_raw():
            # the serve-style mode: registered-claims validation off
            # the native tape, accepted tokens return payload bytes
            out = p.verify_id_token_batch(toks, req, raw=True)
            bad = sum(1 for r in out if isinstance(r, Exception))
            assert bad == 0, bad

        emit("cfg5_oidc_verify_id_token_e2e_raw", rate(run_raw, n), n)
    finally:
        idp.stop()


def main():
    only = os.environ.get("CAP_CFG_ONLY", "")
    wanted = {int(c) for c in only.split(",") if c} if only else None
    for i, fn in enumerate((config1, config2, config3, config4,
                            config5), start=1):
        if wanted is not None and i not in wanted:
            continue
        try:
            fn()
        except Exception as e:  # noqa: BLE001 - report per config
            print(json.dumps({"metric": fn.__name__, "error":
                              f"{type(e).__name__}: {e}"}), flush=True)


if __name__ == "__main__":
    main()
