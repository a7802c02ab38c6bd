"""Test infrastructure: key generation, JWT signing, CA generation.

Analog of the reference's exported test helpers (oidc/testing.go:29-112:
TestGenerateKeys, TestSignJWT, TestGenerateCA), usable both by this
repo's tests and by users of the framework. Signing exists ONLY to
produce fixtures — the framework's job is verification.
"""

from __future__ import annotations

import datetime
import json
import os
from contextlib import contextmanager
from typing import Any, Dict, Optional, Tuple

from cryptography import x509
from cryptography.hazmat.primitives import hashes, serialization
from cryptography.hazmat.primitives.asymmetric import ec, ed25519, padding, rsa
from cryptography.hazmat.primitives.asymmetric.utils import decode_dss_signature
from cryptography.x509.oid import NameOID

from .jwt import algs
from .jwt.jose import b64url_encode

_EC_CURVE = {
    algs.ES256: (ec.SECP256R1, 32),
    algs.ES384: (ec.SECP384R1, 48),
    algs.ES512: (ec.SECP521R1, 66),
}
_HASH = {
    "sha256": hashes.SHA256,
    "sha384": hashes.SHA384,
    "sha512": hashes.SHA512,
}


def generate_keys(alg: str = algs.ES256, rsa_bits: int = 2048):
    """Generate a (private, public) key pair suitable for ``alg``.

    ML-DSA and SLH-DSA pairs come from the repo's own fixture signers
    (``cap_tpu.tpu.mldsa`` / ``slhdsa`` keygen from a random seed).
    """
    if alg in algs.MLDSA_ALGORITHMS:
        from .tpu import mldsa

        return mldsa.keygen(alg, os.urandom(32))
    if alg in algs.SLHDSA_ALGORITHMS:
        from .tpu import slhdsa

        return slhdsa.keygen(alg, os.urandom(32))
    if alg in (algs.RS256, algs.RS384, algs.RS512,
               algs.PS256, algs.PS384, algs.PS512):
        priv = rsa.generate_private_key(public_exponent=65537, key_size=rsa_bits)
    elif alg in _EC_CURVE:
        priv = ec.generate_private_key(_EC_CURVE[alg][0]())
    elif alg == algs.EdDSA:
        priv = ed25519.Ed25519PrivateKey.generate()
    else:
        raise ValueError(f"unsupported alg {alg!r}")
    return priv, priv.public_key()


def sign_jwt(priv, alg: str, claims: Dict[str, Any],
             kid: Optional[str] = None,
             extra_headers: Optional[Dict[str, Any]] = None) -> str:
    """Sign ``claims`` into a compact JWS with the given private key."""
    header: Dict[str, Any] = {"alg": alg, "typ": "JWT"}
    if kid:
        header["kid"] = kid
    if extra_headers:
        header.update(extra_headers)
    signing_input = (
        b64url_encode(json.dumps(header, separators=(",", ":")).encode())
        + "."
        + b64url_encode(json.dumps(claims, separators=(",", ":")).encode())
    ).encode("ascii")

    hash_cls = _HASH.get(algs.HASH_FOR_ALG.get(alg, ""))
    if alg in (algs.RS256, algs.RS384, algs.RS512):
        sig = priv.sign(signing_input, padding.PKCS1v15(), hash_cls())
    elif alg in (algs.PS256, algs.PS384, algs.PS512):
        sig = priv.sign(
            signing_input,
            padding.PSS(mgf=padding.MGF1(hash_cls()),
                        salt_length=hash_cls.digest_size),
            hash_cls(),
        )
    elif alg in _EC_CURVE:
        coord = _EC_CURVE[alg][1]
        der = priv.sign(signing_input, ec.ECDSA(hash_cls()))
        r, s = decode_dss_signature(der)
        sig = r.to_bytes(coord, "big") + s.to_bytes(coord, "big")
    elif alg == algs.EdDSA or alg in algs.PQ_ALGORITHMS:
        sig = priv.sign(signing_input)
    else:
        raise ValueError(f"unsupported alg {alg!r}")
    return signing_input.decode("ascii") + "." + b64url_encode(sig)


def default_claims(issuer: str = "https://example.com/", sub: str = "alice",
                   aud=("client-id",), now: Optional[float] = None,
                   ttl: float = 300.0, **extra) -> Dict[str, Any]:
    """A standard valid claims set for test JWTs."""
    import time

    t = now if now is not None else time.time()
    claims: Dict[str, Any] = {
        "iss": issuer,
        "sub": sub,
        "aud": list(aud),
        "iat": int(t),
        "nbf": int(t),
        "exp": int(t + ttl),
    }
    claims.update(extra)
    return claims


def sign_unique_jwts(signers, n: int, ttl: float = 86400.0):
    """n UNIQUE test JWTs: distinct sub/jti per token → distinct payload
    bytes AND signatures (the honest-bench workload; VERDICT r2 #3).

    signers: [(private_key, alg, kid), ...] cycled round-robin; signing
    runs across threads (OpenSSL releases the GIL).
    """
    from concurrent.futures import ThreadPoolExecutor

    base = default_claims(ttl=ttl)

    def sign(j: int) -> str:
        priv, alg, kid = signers[j % len(signers)]
        claims = dict(base, sub=f"user-{j:08d}", jti=f"tok-{j:012d}")
        return sign_jwt(priv, alg, claims, kid=kid)

    with ThreadPoolExecutor(min(16, os.cpu_count() or 4)) as ex:
        return list(ex.map(sign, range(n), chunksize=256))


def headline_keys():
    """The BASELINE.json north-star key set: a 16-key JWKS (8×RSA-2048
    + 8×P-256, kids ``rs-i``/``es-i``) and its signers
    ``[(private_key, alg, kid), ...]``."""
    from .jwt.jwk import JWK

    jwks, signers = [], []
    for alg, prefix, bits in ((algs.RS256, "rs", 2048),
                              (algs.ES256, "es", 0)):
        for i in range(8):
            priv, pub = generate_keys(alg, rsa_bits=bits)
            jwks.append(JWK(pub, kid=f"{prefix}-{i}"))
            signers.append((priv, alg, f"{prefix}-{i}"))
    return jwks, signers


def headline_fixtures(n_unique: int):
    """The north-star workload: :func:`headline_keys`' JWKS and
    n_unique UNIQUE mixed RS256/ES256 tokens.

    Shared by bench.py, tools/bench_serve.py and chip_smoke.py so the
    offline and serving benchmarks can never desynchronize their key
    mix.
    """
    jwks, signers = headline_keys()
    return jwks, sign_unique_jwts(signers, n_unique)


def to_json_form(token: str, flattened: bool = True,
                 unprotected: Optional[Dict[str, Any]] = None) -> str:
    """Re-serialize a compact JWS as its RFC 7515 §7.2 JSON form.

    ``flattened`` picks §7.2.2 (flattened) vs §7.2.1 (general, one
    signature); ``unprotected`` becomes the per-signature unprotected
    header. Fixture helper for the JSON-serialization parity tests.
    """
    h, p, s = token.split(".")
    sig_obj: Dict[str, Any] = {"protected": h, "signature": s}
    if unprotected is not None:
        sig_obj["header"] = unprotected
    if flattened:
        return json.dumps({"payload": p, **sig_obj})
    return json.dumps({"payload": p, "signatures": [sig_obj]})


def x5c_jwk(priv, pub, kid: Optional[str] = None,
            include_params: bool = False) -> Dict[str, Any]:
    """A JWK whose key material rides an ``x5c`` self-signed cert.

    With ``include_params=False`` (the default) the n/e, x/y, or OKP x
    members are stripped so the chain is the ONLY key material — the
    go-jose-accepted shape the x5c parity tests pin. The cert is signed
    with ``priv`` itself (self-signed leaf).
    """
    import base64

    from .jwt.jwk import serialize_public_key

    name = x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, "cap-tpu-x5c")])
    now = datetime.datetime.now(datetime.timezone.utc)
    builder = (
        x509.CertificateBuilder()
        .subject_name(name)
        .issuer_name(name)
        .public_key(pub)
        .serial_number(x509.random_serial_number())
        .not_valid_before(now - datetime.timedelta(minutes=5))
        .not_valid_after(now + datetime.timedelta(days=1))
    )
    sign_hash = (None if isinstance(priv, ed25519.Ed25519PrivateKey)
                 else hashes.SHA256())
    cert = builder.sign(priv, sign_hash)
    der = cert.public_bytes(serialization.Encoding.DER)
    jwk = serialize_public_key(pub, kid=kid)
    jwk["x5c"] = [base64.b64encode(der).decode("ascii")]
    if not include_params:
        for field in ("n", "e", "x", "y"):
            jwk.pop(field, None)
    return jwk


def generate_ca(common_name: str = "cap-tpu-test-ca") -> Tuple[str, Any, str]:
    """Generate a self-signed CA; returns (cert_pem, private_key, key_pem)."""
    key = ec.generate_private_key(ec.SECP256R1())
    name = x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, common_name)])
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (
        x509.CertificateBuilder()
        .subject_name(name)
        .issuer_name(name)
        .public_key(key.public_key())
        .serial_number(x509.random_serial_number())
        .not_valid_before(now - datetime.timedelta(minutes=5))
        .not_valid_after(now + datetime.timedelta(days=1))
        .add_extension(x509.BasicConstraints(ca=True, path_length=None),
                       critical=True)
        .add_extension(
            x509.SubjectAlternativeName([
                x509.DNSName("localhost"),
                x509.IPAddress(__import__("ipaddress").ip_address("127.0.0.1")),
            ]),
            critical=False,
        )
        .sign(key, hashes.SHA256())
    )
    cert_pem = cert.public_bytes(serialization.Encoding.PEM).decode()
    key_pem = key.private_bytes(
        serialization.Encoding.PEM,
        serialization.PrivateFormat.PKCS8,
        serialization.NoEncryption(),
    ).decode()
    return cert_pem, key, key_pem


@contextmanager
def jwks_test_server(state: Dict[str, Any]):
    """Serve ``{"keys": state["keys"]}`` over loopback HTTP.

    The JWKS analog of :class:`TestProvider` for tests that need ONLY a
    rotating key endpoint (remote/discovery keysets): mutate
    ``state["keys"]`` between requests to rotate; every GET increments
    ``state["fetches"]``. Yields ``(url, server)`` — the server handle
    lets failure tests shut the endpoint down mid-test.
    """
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    state.setdefault("fetches", 0)

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            state["fetches"] += 1
            body = json.dumps({"keys": state["keys"]}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        yield f"http://127.0.0.1:{srv.server_address[1]}/jwks", srv
    finally:
        srv.shutdown()
        srv.server_close()  # release the listening fd (idempotent)
