"""Native capruntime ↔ Python parser conformance.

The C++ batch tokenizer must agree with cap_tpu.jwt.jose.parse_compact
on every token — identical verdicts (parsed vs error class), identical
extracted fields, identical digests — across valid tokens, all malformed
classes, and adversarial headers.
"""

import hashlib
import json

import pytest

pytest.importorskip("cryptography", reason=(
    "module-wide fixtures need the cryptography package: "
    "clean skip instead of a collection ERROR on crypto-less hosts"))


from cap_tpu import testing as captest
from cap_tpu.jwt import algs
from cap_tpu.jwt.jose import b64url_encode, parse_compact
from cap_tpu.runtime import prep

native = pytest.importorskip("cap_tpu.runtime.native_binding")


def _h(d: dict) -> str:
    return b64url_encode(json.dumps(d).encode())


VALID_TOKENS = []
for alg in sorted(algs.SUPPORTED_ALGORITHMS):
    priv, _ = captest.generate_keys(alg)
    VALID_TOKENS.append(captest.sign_jwt(
        priv, alg, captest.default_claims(sub=f"u-{alg}"), kid=f"kid-{alg}"))

MALFORMED = [
    "", "a", "a.b", "a.b.c.d", "..", "a..c",
    "!!!.e30.c2ln", "e30.!!!.c2ln", "e30.e30.!!!",
    "aaaaa.e30.c2ln",                       # header len % 4 == 1
    _h({"alg": "RS256"}) + "." + _h({}) + ".",   # unsigned
    b64url_encode(b"[1]") + ".e30.c2ln",    # header not an object
    b64url_encode(b"{}") + ".e30.c2ln",     # no alg
    b64url_encode(b'{"alg":42}') + ".e30.c2ln",  # alg not a string
    b64url_encode(b'{"alg":"RS256"') + ".e30.c2ln",  # truncated JSON
    b64url_encode(b'{"alg":"RS256"} x') + ".e30.c2ln",  # trailing junk
    b64url_encode(b'not json') + ".e30.c2ln",
]

TRICKY_VALID = [
    # duplicate alg keys: last wins (Python json semantics)
    b64url_encode(b'{"alg":"RS256","alg":"ES256"}') + "." + _h({"a": 1}) + ".c2ln",
    # nested objects/arrays around alg; unicode escapes in kid
    b64url_encode(
        b'{"x":{"alg":"PS256"},"alg":"RS384","arr":[1,{"kid":"no"},null],'
        b'"kid":"k\\u00e9y","n":1.5e3,"b":true}') + "." + _h({}) + ".c2ln",
    # kid non-string -> treated as absent
    b64url_encode(b'{"alg":"EdDSA","kid":123}') + "." + _h({}) + ".c2ln",
    # unknown alg string (parses fine; alg check happens later)
    b64url_encode(b'{"alg":"HS256"}') + "." + _h({}) + ".c2ln",
]


def test_valid_tokens_match_python():
    results = native.prepare_batch(VALID_TOKENS)
    for tok, res in zip(VALID_TOKENS, results):
        ref = parse_compact(tok)
        assert not isinstance(res, Exception), res
        assert res.alg == ref.alg
        assert res.kid == ref.kid
        assert res.signature == ref.signature
        assert res.payload == ref.payload
        assert res.signing_input == ref.signing_input
        if ref.alg != "EdDSA" and ref.alg in algs.HASH_FOR_ALG:
            hname = algs.HASH_FOR_ALG[ref.alg]
            assert res.digest() == hashlib.new(
                hname, ref.signing_input).digest()
        assert res.claims()["sub"] == ref.claims()["sub"]


def test_malformed_match_python():
    results = native.prepare_batch(MALFORMED)
    for tok, res in zip(MALFORMED, results):
        try:
            parse_compact(tok)
            pytest.fail(f"python accepted {tok!r}")
        except Exception as ref_exc:
            assert isinstance(res, Exception), f"native accepted {tok!r}"
            assert type(res) is type(ref_exc), (
                f"{tok!r}: native {type(res).__name__} "
                f"vs python {type(ref_exc).__name__}")


def test_tricky_headers_match_python():
    results = native.prepare_batch(TRICKY_VALID)
    for tok, res in zip(TRICKY_VALID, results):
        ref = parse_compact(tok)
        assert not isinstance(res, Exception), (tok, res)
        assert res.alg == ref.alg
        assert res.kid == ref.kid


def test_kid_edge_cases_match_python():
    # empty kid, NUL-embedded kid, overlong kid, unicode-escaped kid
    cases = [
        b64url_encode(b'{"alg":"RS256","kid":""}') + "." + _h({}) + ".c2ln",
        b64url_encode(b'{"alg":"RS256","kid":"a\\u0000b"}') + "." + _h({}) + ".c2ln",
        b64url_encode(('{"alg":"RS256","kid":"' + "K" * 300 + '"}')
                      .encode()) + "." + _h({}) + ".c2ln",
        b64url_encode(b'{"alg":"RS256","kid":"k\\u00e9y"}') + "." + _h({}) + ".c2ln",
    ]
    results = native.prepare_batch(cases)
    pb = native.prepare_batch_arrays(cases)
    import numpy as np

    for i, (tok, res) in enumerate(zip(cases, results)):
        ref = parse_compact(tok)
        assert not isinstance(res, Exception)
        assert res.kid == ref.kid, (i, res.kid, ref.kid)
        assert pb.kid(i) == ref.kid, i
    # kid_rows resolves NUL-embedded kids byte-exactly and routes
    # empty-kid ("" is a present kid) separately from absent
    rows = pb.kid_rows(np.arange(4), {"a\x00b": 3, "": 9, "kéy": 1})
    assert rows[1] == 3 and rows[0] == 9 and rows[3] == 1
    assert rows[2] == -2  # overlong → slow path


def test_mixed_batch_order_preserved():
    batch = [VALID_TOKENS[0], MALFORMED[0], VALID_TOKENS[1], MALFORMED[10]]
    results = native.prepare_batch(batch)
    assert not isinstance(results[0], Exception)
    assert isinstance(results[1], Exception)
    assert not isinstance(results[2], Exception)
    assert isinstance(results[3], Exception)


def test_prep_uses_native_when_built():
    res = prep.prepare_batch(VALID_TOKENS[:2])
    assert all(not isinstance(r, Exception) for r in res)


def test_sha_batch():
    chunks = [b"", b"abc", b"x" * 1000, bytes(range(256)) * 7]
    for bits, name in [(256, "sha256"), (384, "sha384"), (512, "sha512")]:
        got = native.sha_batch(chunks, bits)
        expect = [hashlib.new(name, c).digest() for c in chunks]
        assert got == expect


def test_fuzz_parity_random_mutations():
    import random

    rng = random.Random(7)
    base = VALID_TOKENS[0]
    cases = []
    for _ in range(300):
        chars = list(base)
        for _ in range(rng.randrange(1, 4)):
            pos = rng.randrange(len(chars))
            chars[pos] = rng.choice("AZaz09._-!=")
        cases.append("".join(chars))
    results = native.prepare_batch(cases)
    for tok, res in zip(cases, results):
        try:
            ref = parse_compact(tok)
            ok_ref = True
        except Exception as e:
            ok_ref, ref_exc = False, e
        if ok_ref:
            assert not isinstance(res, Exception), tok
            assert res.alg == ref.alg and res.signature == ref.signature
        else:
            assert isinstance(res, Exception), tok
            assert type(res) is type(ref_exc), tok


# ---------------------------------------------------------------------------
# _capclaims: batch claims-JSON parsing parity vs json.loads
# ---------------------------------------------------------------------------

def _claims_ext():
    ext = native._claims_ext
    if ext is None:
        pytest.skip("_capclaims extension not built")
    return ext


def _run_claims_batch(payloads):
    import numpy as np

    ext = _claims_ext()
    blob = np.frombuffer(b"".join(payloads), np.uint8)
    lens = np.asarray([len(p) for p in payloads], np.int64)
    offs = np.zeros(len(payloads), np.int64)
    np.cumsum(lens[:-1], out=offs[1:])
    out, n_bad = ext.parse_batch(blob, offs, lens)
    assert n_bad == sum(1 for v in out if not isinstance(v, dict))
    return out


CLAIMS_EDGE = [
    b"", b"{", b"[1,2", b'{"a":}', b"nul", b'{"a":1}garbage', b"123",
    b'"just a string"', b"[]", b"{}", b'{"a": NaN}', b'{"a": Infinity}',
    b'{"a": -Infinity}', b'{"\\ud800": 1}', b'{"x": "\\ud83d\\ude00"}',
    b'{"a":1e999}', b'{"a":-0.0}', b'{"a":0.1e+5}', b'{"dup":1,"dup":2}',
    b'{"a":' + b"[" * 100 + b"]" * 100 + b"}",
    b'{"big":' + b"9" * 4500 + b"}", b"\xff\xfe", b'{"a":"\xc3\x28"}',
    b'{"a":01}', b'{"a":+1}', b'{"a":.5}', b'{"a":1.}', b'{"a":"\x01"}',
    b'  {"ws": 1}  ', b'{"t":true,"f":false,"n":null}',
    b'{"neg":-9223372036854775808,"pos":9223372036854775807}',
    b'{"over":9223372036854775808,"under":-9223372036854775809}',
    b'{"u":"\\u0041\\u00e9\\u4e2d\\uffff"}', b'{"s":"\\/\\\\\\"\\b\\f\\n\\r\\t"}',
    b'{"e":{}}', b'{"e":[[],{}]}', b'{"a":2.2250738585072014e-308}',
]


def _same_typed(a, b):
    if type(a) is not type(b):
        return False
    if isinstance(a, dict):
        return set(a) == set(b) and all(_same_typed(a[k], b[k]) for k in a)
    if isinstance(a, list):
        return len(a) == len(b) and all(
            _same_typed(x, y) for x, y in zip(a, b))
    return a == b


def test_claims_ext_edge_parity():
    out = _run_claims_batch(CLAIMS_EDGE)
    for p, got in zip(CLAIMS_EDGE, out):
        try:
            want = json.loads(p)
            want_state = "dict" if isinstance(want, dict) else "notobj"
        except Exception:  # noqa: BLE001
            want, want_state = None, "bad"
        if isinstance(got, int):
            if got == 3:
                continue  # fallback: Python re-parses — always correct
            assert (got == 1 and want_state == "bad") or \
                (got == 2 and want_state == "notobj"), (p, got, want_state)
        else:
            assert want_state == "dict", (p, got)
            assert got == want and _same_typed(got, want), p


def test_claims_ext_fuzz_parity():
    import random

    rng = random.Random(20260730)

    def rnd_val(d=0):
        r = rng.random()
        if d > 3 or r < 0.3:
            return rng.choice([
                None, True, False, 12345, -7, 0, 3.14159, 1.5e300,
                -2.5e-10, 10 ** 25, -(10 ** 30), "plain", "unié中文",
                'esc"q\\u\n\t', "", "x" * 257])
        if r < 0.55:
            return [rnd_val(d + 1) for _ in range(rng.randint(0, 4))]
        if r < 0.65:
            return rng.randint(-(10 ** 40), 10 ** 40)
        return {f"k{rng.randint(0, 20)}": rnd_val(d + 1)
                for _ in range(rng.randint(0, 5))}

    payloads = []
    for i in range(2000):
        obj = {"iss": "https://idp.example.com", "sub": f"user-{i}",
               "aud": ["a", "b"], "exp": 1790000000 + i,
               "extra": rnd_val()}
        payloads.append(json.dumps(
            obj, ensure_ascii=rng.random() < 0.5).encode())
    out = _run_claims_batch(payloads)
    for p, got in zip(payloads, out):
        want = json.loads(p)
        if isinstance(got, int):
            assert got == 3, (p, got)  # only fallback allowed on valid input
        else:
            assert got == want and _same_typed(got, want), p


def test_claims_ext_degenerate_batches_overflow_caches():
    """Intern-table caps (256 keys / value-table entries / 64-byte value
    threshold) must only change speed, never results: an all-unique
    batch overflows every cache and still parses byte-identically."""
    payloads = []
    # > 256 distinct keys across the batch (key-cache cap), > 4096
    # distinct short values (value-table cap), values straddling the
    # 64-byte cache threshold, and > 5 keys per object (presize path).
    for i in range(1200):
        obj = {
            f"uk{i}a": f"val-{i}-alpha", f"uk{i}b": f"val-{i}-beta",
            f"uk{i}c": i, f"uk{i}d": f"v{i}" * 3, f"uk{i}e": True,
            f"uk{i}f": "x" * 63, f"uk{i}g": "y" * 64, f"uk{i}h": "z" * 65,
            "shared": "common-value",
        }
        payloads.append(json.dumps(obj, separators=(",", ":")).encode())
    out = _run_claims_batch(payloads)
    for p, got in zip(payloads, out):
        want = json.loads(p)
        assert got == want and _same_typed(got, want), p


def test_prefetch_claims_uses_ext_with_identical_results():
    """PreparedBatch.prefetch_claims: ext path == pure-json path."""
    priv, _ = captest.generate_keys(algs.ES256)
    tokens = [captest.sign_jwt(priv, algs.ES256,
                               captest.default_claims(sub=f"s-{i}"))
              for i in range(50)]
    # one weird-but-valid payload and one non-object payload via raw JWS
    h = b64url_encode(json.dumps({"alg": "ES256"}).encode())
    inf_payload = b'{"inf": Infinity}'
    tokens.append(f"{h}.{b64url_encode(b'[1,2,3]')}.c2ln")
    tokens.append(f"{h}.{b64url_encode(inf_payload)}.c2ln")

    pb1 = native.prepare_batch_arrays(tokens)
    pb1.prefetch_claims(range(pb1.n))
    saved = native._claims_ext
    try:
        native._claims_ext = None
        pb2 = native.prepare_batch_arrays(tokens)
        pb2.prefetch_claims(range(pb2.n))
    finally:
        native._claims_ext = saved
    for i in range(pb1.n):
        a, b = pb1._claims_cache[i], pb2._claims_cache[i]
        if isinstance(a, Exception):
            assert type(a) is type(b) and str(a) == str(b), i
        else:
            assert a == b and _same_typed(a, b), i
