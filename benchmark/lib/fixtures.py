"""Keys and tokens made from the run's seed.

Key material is a function of ``(seed, kid)``: RSA primes and EC
scalars come from a seeded generator (one purpose per key type and
curve), an Ed25519 key from 32 seeded bytes. Every signature is
deterministic for a seed: RSA PKCS#1 v1.5 by its nature, ECDSA with
RFC 6979 nonces, Ed25519 by design, and RSASSA-PSS through the
EMSA-PSS encoder below with its salt drawn from the seed. So one seed
gives the same JWKS and the same token bytes in every run. Signing runs
in a process pool over the host's cores. Nothing here imports the
program: the JWKS is written as RFC 7517 JSON for the program to parse.
"""

from __future__ import annotations

import base64
import hashlib
import json
import random

from . import claims as _claims

_SMALL_PRIMES = [p for p in range(3, 2000)
                 if all(p % d for d in range(2, int(p ** 0.5) + 1))]
# P-256 group order (SEC 2, 2.4.2)
P256_N = 0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551

# JWK "crv" -> (group order, the purpose its scalars are drawn under,
# coordinate bytes). The orders are SEC 2's (2.4.2, 2.5.1, 2.6.1).
# P-256 keeps the purpose "p256", so its keys did not change when the
# other curves came.
CURVES = {
    "P-256": (P256_N, "p256", 32),
    "P-384": (int("FFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFF"
                  "C7634D81F4372DDF581A0DB248B0A77AECEC196ACCC52973", 16),
              "p384", 48),
    "P-521": (int("01FF" + "FFFFFFFF" * 7 + "FFFFFFFA51868783BF2F966B7FCC0148"
                  "F709A5D03BB5C9B8899C47AEBB6FB71E91386409", 16),
              "p521", 66),
}

# alg -> (key type, hash, curve for ES*) per RFC 7518 3.3-3.5 and
# RFC 8037 3.1
ALGS = {
    "RS256": ("RSA", "SHA256", None), "RS384": ("RSA", "SHA384", None),
    "RS512": ("RSA", "SHA512", None),
    "PS256": ("RSA", "SHA256", None), "PS384": ("RSA", "SHA384", None),
    "PS512": ("RSA", "SHA512", None),
    "ES256": ("EC", "SHA256", "P-256"), "ES384": ("EC", "SHA384", "P-384"),
    "ES512": ("EC", "SHA512", "P-521"),
    "EdDSA": ("OKP", None, "Ed25519"),
}


def rng(seed: int, *purpose) -> random.Random:
    """An independent generator per (seed, purpose)."""
    h = hashlib.sha256(repr((int(seed),) + purpose).encode()).digest()
    return random.Random(int.from_bytes(h, "big"))


def b64u(data: bytes) -> str:
    return base64.urlsafe_b64encode(data).rstrip(b"=").decode("ascii")


def b64u_dec(seg: str) -> bytes:
    return base64.urlsafe_b64decode(seg + "=" * (-len(seg) % 4))


def _is_probable_prime(n: int, r: random.Random, rounds: int = 24) -> bool:
    for p in _SMALL_PRIMES:
        if n % p == 0:
            return n == p
    d, s = n - 1, 0
    while d % 2 == 0:
        d //= 2
        s += 1
    for _ in range(rounds):
        a = r.randrange(2, n - 2)
        x = pow(a, d, n)
        if x in (1, n - 1):
            continue
        for _ in range(s - 1):
            x = x * x % n
            if x == n - 1:
                break
        else:
            return False
    return True


def _prime(r: random.Random, bits: int, e: int) -> int:
    while True:
        c = r.getrandbits(bits) | (3 << (bits - 2)) | 1
        if (c - 1) % e and _is_probable_prime(c, r):
            return c


def rsa_numbers(seed: int, name: str, bits: int = 2048, e: int = 65537):
    """(p, q, e) of an RSA key of exactly ``bits`` bits, from the seed."""
    r = rng(seed, "rsa", name)
    while True:
        p, q = _prime(r, bits // 2, e), _prime(r, bits // 2, e)
        if p != q and (p * q).bit_length() == bits:
            return p, q, e


def rsa_jobs(keys, seed: int):
    """The ``rsa_numbers`` arguments of each RSA key of a config's
    ``keys``, in order (``bits`` defaults to 2048)."""
    return [(seed, k["kid"], k.get("bits", 2048)) for k in keys
            if k["kty"] == "RSA"]


def ec_scalar(seed: int, name: str, crv: str = "P-256") -> int:
    n, purpose, _ = CURVES[crv]
    return rng(seed, purpose, name).randrange(1, n)


def ed25519_seed(seed: int, name: str) -> bytes:
    return rng(seed, "ed25519", name).randbytes(32)


def _curve(crv: str):
    from cryptography.hazmat.primitives.asymmetric import ec

    return {"P-256": ec.SECP256R1, "P-384": ec.SECP384R1,
            "P-521": ec.SECP521R1}[crv]()


def private_key(spec):
    """A ``cryptography`` private key from ("RSA", (p, q, e)),
    ("EC", d) on P-256, ("EC", (crv, d)) or ("OKP", 32-byte seed)."""
    from cryptography.hazmat.primitives.asymmetric import ec, ed25519, rsa

    kty, num = spec
    if kty == "RSA":
        p, q, e = num
        n, phi = p * q, (p - 1) * (q - 1)
        d = pow(e, -1, phi)
        return rsa.RSAPrivateNumbers(
            p, q, d, d % (p - 1), d % (q - 1), pow(q, -1, p),
            rsa.RSAPublicNumbers(e, n)).private_key()
    if kty == "OKP":
        return ed25519.Ed25519PrivateKey.from_private_bytes(num)
    crv, d = num if isinstance(num, tuple) else ("P-256", num)
    return ec.derive_private_key(d, _curve(crv))


class KeyConfigError(ValueError):
    """A config's key entry names a key type, curve or alg that do not
    go together."""


def _key_spec(k: dict, seed: int, rsa_num: dict):
    kty, alg = k["kty"], k["alg"]
    want = ALGS.get(alg)
    if want is None:
        raise KeyConfigError(f"{k['kid']}: unsupported alg {alg!r}")
    if want[0] != kty:
        raise KeyConfigError(f"{k['kid']}: {alg} needs a {want[0]} key, "
                             f"not {kty}")
    if kty == "RSA":
        return "RSA", rsa_num[k["kid"]]
    if kty == "OKP":
        if k.get("crv") != "Ed25519":
            raise KeyConfigError(f"{k['kid']}: OKP keys are Ed25519 here")
        return "OKP", ed25519_seed(seed, k["kid"])
    crv = k.get("crv", "P-256")
    if crv != want[2]:
        raise KeyConfigError(f"{k['kid']}: {alg} signs on {want[2]}, "
                             f"not {crv}")
    d = ec_scalar(seed, k["kid"], crv)
    return "EC", (d if crv == "P-256" else (crv, d))


def key_specs(keys, seed: int, rsa_num: dict):
    """``keys`` is the config's list of {"kid", "kty", "alg"} (with
    ``bits`` or ``crv`` where given); ``rsa_num`` maps each RSA kid to
    its ``rsa_numbers``. Returns {kid: (alg, spec)}, a spec as
    ``private_key`` takes it."""
    return {k["kid"]: (k["alg"], _key_spec(k, seed, rsa_num)) for k in keys}


def _int_b64u(v: int, size: int | None = None) -> str:
    return b64u(v.to_bytes(size or (v.bit_length() + 7) // 8, "big"))


def public_jwk(kid: str, alg: str, spec) -> dict:
    kty, num = spec
    if kty == "RSA":
        p, q, e = num
        return {"kty": "RSA", "kid": kid, "alg": alg, "use": "sig",
                "n": _int_b64u(p * q), "e": _int_b64u(e)}
    if kty == "OKP":
        from cryptography.hazmat.primitives import serialization

        raw = private_key(spec).public_key().public_bytes(
            serialization.Encoding.Raw, serialization.PublicFormat.Raw)
        return {"kty": "OKP", "kid": kid, "alg": alg, "use": "sig",
                "crv": "Ed25519", "x": b64u(raw)}
    crv = num[0] if isinstance(num, tuple) else "P-256"
    size = CURVES[crv][2]
    pub = private_key(spec).public_key().public_numbers()
    return {"kty": "EC", "kid": kid, "alg": alg, "use": "sig",
            "crv": crv, "x": _int_b64u(pub.x, size),
            "y": _int_b64u(pub.y, size)}


def jwks_document(key_specs) -> dict:
    return {"keys": [public_jwk(kid, alg, spec)
                     for kid, (alg, spec) in key_specs.items()]}


# -- signing (runs in pool workers) ----------------------------------------

_SIGNERS: dict = {}


def _signer(spec):
    """The private key of ``spec``, made once per process (keyed by the
    spec itself, so two seeds' keys of one kid never mix)."""
    key = _SIGNERS.get(spec)
    if key is None:
        key = _SIGNERS[spec] = private_key(spec)
    return key


def _mgf1(seed: bytes, length: int, hash_name: str) -> bytes:
    """MGF1 (RFC 8017 B.2.1)."""
    out, c = b"", 0
    while len(out) < length:
        out += hashlib.new(hash_name, seed + c.to_bytes(4, "big")).digest()
        c += 1
    return out[:length]


def emsa_pss_encode(message: bytes, em_bits: int, hash_name: str,
                    salt: bytes) -> bytes:
    """EMSA-PSS-ENCODE (RFC 8017 9.1.1) with MGF1 over the same hash."""
    m_hash = hashlib.new(hash_name, message).digest()
    h_len, s_len = len(m_hash), len(salt)
    em_len = (em_bits + 7) // 8
    if em_len < h_len + s_len + 2:
        raise ValueError("encoding error: key too short for the hash")
    h = hashlib.new(hash_name, b"\x00" * 8 + m_hash + salt).digest()
    db = b"\x00" * (em_len - s_len - h_len - 2) + b"\x01" + salt
    masked = bytes(a ^ b for a, b in zip(
        db, _mgf1(h, em_len - h_len - 1, hash_name)))
    masked = bytes([masked[0] & (0xFF >> (8 * em_len - em_bits))]) \
        + masked[1:]
    return masked + h + b"\xbc"


def pss_sign(key, hash_name: str, signing_input: bytes,
             salt: bytes) -> bytes:
    """RSASSA-PSS-SIGN (RFC 8017 8.1.1) with the private exponent:
    ``cryptography`` draws its salt at random, so the encoding is done
    here and only the exponentiation (by CRT) is the key's."""
    pn = key.private_numbers()
    n = pn.public_numbers.n
    k = (n.bit_length() + 7) // 8
    m = int.from_bytes(emsa_pss_encode(signing_input, n.bit_length() - 1,
                                       hash_name, salt), "big")
    s1, s2 = pow(m, pn.dmp1, pn.p), pow(m, pn.dmq1, pn.q)
    s = s2 + pn.q * (pn.iqmp * (s1 - s2) % pn.p)
    return s.to_bytes(k, "big")


def sign(key, alg: str, signing_input: bytes, salt: bytes = b"") -> bytes:
    """The JWS signature of ``alg``; ``salt`` is PSS's (as long as the
    hash, RFC 7518 3.5)."""
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec, padding
    from cryptography.hazmat.primitives.asymmetric.utils import (
        decode_dss_signature,
    )

    if alg not in ALGS:
        raise ValueError(f"unsupported alg {alg!r}")
    _, hash_name, crv = ALGS[alg]
    if alg.startswith("RS"):
        return key.sign(signing_input, padding.PKCS1v15(),
                        getattr(hashes, hash_name)())
    if alg.startswith("PS"):
        return pss_sign(key, hash_name.lower(), signing_input, salt)
    if alg == "EdDSA":
        return key.sign(signing_input)
    der = key.sign(signing_input, ec.ECDSA(getattr(hashes, hash_name)(),
                                           deterministic_signing=True))
    r, s = decode_dss_signature(der)
    size = CURVES[crv][2]
    return r.to_bytes(size, "big") + s.to_bytes(size, "big")


def encode_jws(header: dict, claims: dict) -> str:
    return (b64u(json.dumps(header, separators=(",", ":")).encode()) + "."
            + b64u(json.dumps(claims, separators=(",", ":")).encode()))


def sign_range(key_specs, kid_order, profile, seed, lo, hi):
    """Tokens lo..hi-1 of the pool: token j is signed by
    ``kid_order[j % len(kid_order)]``; a PSS token's salt is drawn from
    ``rng(seed, "pss", j)``."""
    out = []
    for j in range(lo, hi):
        kid = kid_order[j % len(kid_order)]
        alg, spec = key_specs[kid]
        header, body = _claims.make(profile, seed, j, kid, alg)
        si = encode_jws(header, body)
        salt = (rng(seed, "pss", j).randbytes(
            hashlib.new(ALGS[alg][1].lower()).digest_size)
            if alg.startswith("PS") else b"")
        sig = sign(_signer(spec), alg, si.encode("ascii"), salt)
        out.append(si + "." + b64u(sig))
    return out


# -- tampered tokens --------------------------------------------------------

TAMPER_KINDS = ("flipped_signature", "swapped_payload", "alg_none")
_NONE_HEADER = b64u(b'{"alg":"none","typ":"JWT"}')


def tamper(tokens, i: int, kind: str, other: int) -> str:
    """chip_smoke.tampered_tokens' three forgeries of token ``i``:
    one signature character changed, the payload of token ``other``
    under token i's signature, or ``alg=none`` with an empty
    signature."""
    h, p, s = tokens[i].split(".")
    if kind == "flipped_signature":
        k = len(s) // 2
        return f"{h}.{p}.{s[:k]}{'A' if s[k] != 'A' else 'B'}{s[k + 1:]}"
    if kind == "swapped_payload":
        return f"{h}.{tokens[other].split('.')[1]}.{s}"
    if kind == "alg_none":
        return f"{_NONE_HEADER}.{p}."
    raise ValueError(kind)


def with_tampered(tokens, seed: int, share: float):
    """Replace ``round(share * n)`` seeded positions of the pool with
    forgeries of other pool tokens (kinds in rotation); returns the new
    pool and the tampered positions."""
    n = len(tokens)
    k = int(round(share * n))
    r = rng(seed, "tamper")
    pos = sorted(r.sample(range(n), k))
    out = list(tokens)
    for m, i in enumerate(pos):
        src = (i + 1 + r.randrange(n - 1)) % n
        other = (src + 1 + r.randrange(n - 1)) % n
        out[i] = tamper(tokens, src, TAMPER_KINDS[m % 3], other)
    return out, pos
