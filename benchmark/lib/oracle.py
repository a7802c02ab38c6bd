"""The plain reference: JWS signature verification with go-jose
semantics, written from RFC 7515/7518 and RFC 8037 on ``cryptography``
alone.

It imports nothing of the program and takes nothing the program made:
it reads the public keys from the JWKS document the benchmark wrote,
and shares no signing code with the fixtures. A token's verdict is
``(True, claims)`` or ``(False, reason)``:

- three compact segments, a JSON-object header with an ``alg`` of
  RS256/384/512, PS256/384/512, ES256/384/512 or EdDSA;
- a known ``kid`` selects its key; an absent or unknown ``kid`` tries
  every key (go-jose trial verification);
- an alg verifies only on its own key type: RS* is RSASSA-PKCS1-v1_5
  (RFC 7518 section 3.3) and PS* RSASSA-PSS with MGF1 over the same
  hash and a salt as long as the hash (section 3.5), both on RSA keys;
  ES* is ECDSA over a fixed-length R||S on its own curve: ES256 on
  P-256 (64 bytes), ES384 on P-384 (96), ES512 on P-521 (132)
  (section 3.4); EdDSA is Ed25519 on an OKP key (RFC 8037 3.1);
- an accepted token's claims are its payload, a JSON object.
"""

from __future__ import annotations

import json

from .fixtures import b64u_dec

_KEYS: dict = {}
# alg -> (key type, hash, curve), as RFC 7518 3.3-3.5 and RFC 8037 3.1
# pair them
_ALGS = {
    "RS256": ("RSA", "SHA256", None), "RS384": ("RSA", "SHA384", None),
    "RS512": ("RSA", "SHA512", None),
    "PS256": ("RSA", "SHA256", None), "PS384": ("RSA", "SHA384", None),
    "PS512": ("RSA", "SHA512", None),
    "ES256": ("EC", "SHA256", "P-256"), "ES384": ("EC", "SHA384", "P-384"),
    "ES512": ("EC", "SHA512", "P-521"),
    "EdDSA": ("OKP", None, "Ed25519"),
}
_EC_SIZE = {"P-256": 32, "P-384": 48, "P-521": 66}


def _public_keys(jwks: dict):
    """{kid: (kty, crv, public key)} of a JWKS document."""
    from cryptography.hazmat.primitives.asymmetric import ec, ed25519, rsa

    key = json.dumps(jwks, sort_keys=True)
    got = _KEYS.get(key)
    if got is None:
        got = {}
        curves = {"P-256": ec.SECP256R1, "P-384": ec.SECP384R1,
                  "P-521": ec.SECP521R1}
        for j in jwks["keys"]:
            crv = j.get("crv")
            if j["kty"] == "RSA":
                n = int.from_bytes(b64u_dec(j["n"]), "big")
                e = int.from_bytes(b64u_dec(j["e"]), "big")
                pub = rsa.RSAPublicNumbers(e, n).public_key()
            elif j["kty"] == "OKP":
                pub = ed25519.Ed25519PublicKey.from_public_bytes(
                    b64u_dec(j["x"]))
            else:
                pub = ec.EllipticCurvePublicNumbers(
                    int.from_bytes(b64u_dec(j["x"]), "big"),
                    int.from_bytes(b64u_dec(j["y"]), "big"),
                    curves[crv]()).public_key()
            got[j["kid"]] = (j["kty"], crv, pub)
        _KEYS.clear()
        _KEYS[key] = got
    return got


def _check(kty: str, crv, pub, alg: str, si: bytes, sig: bytes) -> bool:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec, padding
    from cryptography.hazmat.primitives.asymmetric.utils import (
        encode_dss_signature,
    )

    want_kty, hash_name, want_crv = _ALGS[alg]
    if kty != want_kty or (want_crv is not None and crv != want_crv):
        return False
    try:
        if kty == "OKP":
            pub.verify(sig, si)
            return True
        h = getattr(hashes, hash_name)()
        if alg.startswith("RS"):
            pub.verify(sig, si, padding.PKCS1v15(), h)
        elif alg.startswith("PS"):
            pub.verify(sig, si, padding.PSS(
                mgf=padding.MGF1(getattr(hashes, hash_name)()),
                salt_length=h.digest_size), h)
        else:
            size = _EC_SIZE[crv]
            if len(sig) != 2 * size:
                return False
            r = int.from_bytes(sig[:size], "big")
            s = int.from_bytes(sig[size:], "big")
            pub.verify(encode_dss_signature(r, s), si, ec.ECDSA(h))
        return True
    except (InvalidSignature, ValueError):
        pass
    return False


def verdict(keys, token: str):
    parts = token.split(".")
    if len(parts) != 3:
        return False, "malformed"
    h, p, s = parts
    try:
        header = json.loads(b64u_dec(h))
        sig = b64u_dec(s)
    except (ValueError, UnicodeDecodeError):
        return False, "malformed"
    if not isinstance(header, dict):
        return False, "malformed"
    alg = header.get("alg")
    if not isinstance(alg, str) or alg not in _ALGS:
        return False, "unsupported_alg"
    kid = header.get("kid")
    cands = [keys[kid]] if kid in keys else list(keys.values())
    si = f"{h}.{p}".encode("ascii")
    if not any(_check(kty, crv, pub, alg, si, sig)
               for kty, crv, pub in cands):
        return False, "signature"
    try:
        claims = json.loads(b64u_dec(p))
    except (ValueError, UnicodeDecodeError):
        return False, "malformed"
    if not isinstance(claims, dict):
        return False, "malformed"
    return True, claims


def verdicts(jwks: dict, tokens):
    """Reference verdicts of ``tokens`` (runs in a pool worker)."""
    keys = _public_keys(jwks)
    return [verdict(keys, t) for t in tokens]


def reference(pool, jwks: dict, tokens, chunk: int = 1024):
    jobs = [(jwks, tokens[i:i + chunk]) for i in range(0, len(tokens), chunk)]
    parts = (pool.starmap(verdicts, jobs) if pool is not None
             else [verdicts(*j) for j in jobs])
    return [v for part in parts for v in part]


# How a router that re-raises a worker's reject writes it: the worker's
# "<ErrorClass>: <message>" under a head of its own.
RELAY_HEAD = b"RemoteVerifyError: "


def agrees(answer, want) -> bool:
    """One served answer ``(status, payload bytes)`` against the
    reference's verdict: an accept carries exactly the reference's
    claims; a reject is a verify error, not an admission pushback, also
    where a router relayed it under ``RELAY_HEAD``."""
    status, payload = answer
    ok, detail = want
    if status == 0:
        if not ok:
            return False
        try:
            return json.loads(payload) == detail
        except ValueError:
            return False
    while payload.startswith(RELAY_HEAD):
        payload = payload[len(RELAY_HEAD):]
    return (not ok) and status == 1 and not payload.startswith(
        b"ThrottledError")
