"""TPUBatchKeySet parity vs the CPU oracle, successes AND rejections.

This is the bit-exact-parity contract from BASELINE.md: for every token
in a mixed batch, the TPU path must produce the same verdict as the
reference-semantics CPU path (StaticKeySet / verify_parsed).
"""

import json

import numpy as np
import pytest

pytest.importorskip("cryptography", reason=(
    "module-wide fixtures need the cryptography package: "
    "clean skip instead of a collection ERROR on crypto-less hosts"))


from cap_tpu import testing as captest
from cap_tpu.errors import InvalidSignatureError, MalformedTokenError
from cap_tpu.jwt import StaticKeySet
from cap_tpu.jwt.jose import b64url_encode
from cap_tpu.jwt.jwk import JWK
from cap_tpu.jwt.tpu_keyset import TPUBatchKeySet


@pytest.fixture(scope="module")
def rsa_jwks():
    """8-key JWKS: mixed 2048/3072/4096-bit RSA keys (config ② shape)."""
    pairs = []
    for i, bits in enumerate([2048, 2048, 2048, 3072, 3072, 4096, 4096, 2048]):
        priv, pub = captest.generate_keys("RS256", rsa_bits=bits)
        pairs.append((f"kid-{i}", priv, pub))
    return pairs


@pytest.fixture(scope="module")
def tpu_keyset(rsa_jwks):
    return TPUBatchKeySet([JWK(pub, kid=kid) for kid, _, pub in rsa_jwks])


def _tokens(rsa_jwks, alg, n, start=0):
    toks = []
    for j in range(n):
        kid, priv, _ = rsa_jwks[(start + j) % len(rsa_jwks)]
        toks.append(captest.sign_jwt(
            priv, alg, captest.default_claims(sub=f"user-{j}"), kid=kid))
    return toks


@pytest.mark.parametrize("alg", ["RS256", "RS384", "RS512"])
def test_rs_batch_verifies(alg, rsa_jwks, tpu_keyset):
    toks = _tokens(rsa_jwks, alg, 12)
    res = tpu_keyset.verify_batch(toks)
    for j, r in enumerate(res):
        assert isinstance(r, dict), f"token {j}: {r}"
        assert r["sub"] == f"user-{j}"


@pytest.mark.parametrize("alg", ["PS256", "PS384", "PS512"])
def test_ps_batch_verifies(alg, rsa_jwks, tpu_keyset):
    toks = _tokens(rsa_jwks, alg, 9)
    res = tpu_keyset.verify_batch(toks)
    assert all(isinstance(r, dict) for r in res)


def test_mixed_batch_parity_with_cpu(rsa_jwks, tpu_keyset):
    """Mixed good/tampered/garbage batch: verdicts must match CPU oracle."""
    good = _tokens(rsa_jwks, "RS256", 6) + _tokens(rsa_jwks, "PS256", 3)
    # tampered payload (sig of another payload)
    h, p, s = good[0].split(".")
    evil = b64url_encode(json.dumps({"sub": "evil"}).encode())
    tampered = f"{h}.{evil}.{s}"
    # truncated signature
    shortsig = good[1][: len(good[1]) - 40]
    # garbage token
    garbage = "not.a.jwt"
    # wrong kid (kid-0 key didn't sign this)
    kid0, priv7, _ = rsa_jwks[0]
    _, priv_other, _ = rsa_jwks[1]
    wrongkid = captest.sign_jwt(priv_other, "RS256",
                                captest.default_claims(), kid=kid0)
    batch = good + [tampered, shortsig, garbage, wrongkid]

    cpu = StaticKeySet([pub for _, _, pub in rsa_jwks])
    cpu_verdicts = []
    for t in batch:
        try:
            cpu.verify_signature(t)
            cpu_verdicts.append(True)
        except Exception:
            cpu_verdicts.append(False)

    tpu_res = tpu_keyset.verify_batch(batch)
    tpu_verdicts = [isinstance(r, dict) for r in tpu_res]
    # Note: wrongkid verifies on CPU StaticKeySet (trial over all keys)
    # but the kid-routed TPU path rejects it — kid routing is stricter,
    # matching the remote-JWKS (kid-matched) reference path. Compare the
    # kid-faithful subset exactly:
    assert tpu_verdicts[:-1] == cpu_verdicts[:-1]
    assert tpu_verdicts[-1] is False
    assert isinstance(tpu_res[-4], InvalidSignatureError)   # tampered
    assert isinstance(tpu_res[-2], MalformedTokenError)     # garbage


def test_no_kid_falls_back_to_trial(rsa_jwks, tpu_keyset):
    _, priv, _ = rsa_jwks[2]
    tok = captest.sign_jwt(priv, "RS256", captest.default_claims())  # no kid
    res = tpu_keyset.verify_batch([tok])
    assert isinstance(res[0], dict)


def test_unknown_kid_rejected(rsa_jwks, tpu_keyset):
    _, priv, _ = rsa_jwks[0]
    tok = captest.sign_jwt(priv, "RS256", captest.default_claims(),
                           kid="nonexistent")
    # kid not in table → trial-verifies over all keys (CPU) and succeeds,
    # same as the static reference path; a *wrong-key* kid is the reject case.
    res = tpu_keyset.verify_batch([tok])
    assert isinstance(res[0], dict)


def test_single_token_path(rsa_jwks, tpu_keyset):
    kid, priv, _ = rsa_jwks[0]
    tok = captest.sign_jwt(priv, "RS256", captest.default_claims(), kid=kid)
    assert tpu_keyset.verify_signature(tok)["sub"] == "alice"


def test_bitflip_sweep_parity(rsa_jwks, tpu_keyset):
    """Flip bits across the signature; every corruption must reject."""
    kid, priv, _ = rsa_jwks[0]
    tok = captest.sign_jwt(priv, "RS256", captest.default_claims(), kid=kid)
    h, p, s = tok.split(".")
    corrupted = []
    raw = bytearray(__import__("cap_tpu.jwt.jose", fromlist=["b64url_decode"])
                    .b64url_decode(s))
    for bit in range(0, len(raw) * 8, 191):
        mut = bytearray(raw)
        mut[bit // 8] ^= 1 << (bit % 8)
        corrupted.append(f"{h}.{p}.{b64url_encode(bytes(mut))}")
    res = tpu_keyset.verify_batch(corrupted)
    assert all(isinstance(r, InvalidSignatureError) for r in res)


def test_mixed_rsa_key_sizes_one_batch(rsa_jwks, tpu_keyset):
    """2048+4096-bit keys in one device dispatch (shared padded K)."""
    toks = _tokens(rsa_jwks, "RS512", 16)
    res = tpu_keyset.verify_batch(toks)
    assert all(isinstance(r, dict) for r in res)


def test_es_falls_back_to_cpu_until_ec_engine(tpu_keyset, rsa_jwks):
    es_priv, es_pub = captest.generate_keys("ES256")
    ks = TPUBatchKeySet(
        [JWK(pub, kid=kid) for kid, _, pub in rsa_jwks] + [JWK(es_pub, kid="es")]
    )
    tok = captest.sign_jwt(es_priv, "ES256", captest.default_claims(), kid="es")
    res = ks.verify_batch([tok])
    assert isinstance(res[0], dict)


def test_remote_keyset_rotation():
    """TPURemoteKeySet: unknown kid triggers ONE refetch + table rebuild;
    bad signatures against known kids never refetch (no amplification)."""
    from cap_tpu.jwt.jwk import serialize_public_key
    from cap_tpu.jwt.tpu_keyset import TPURemoteKeySet

    priv1, pub1 = captest.generate_keys("ES256")
    priv2, pub2 = captest.generate_keys("ES256")
    state = {"keys": [serialize_public_key(pub1, kid="gen1")]}

    with captest.jwks_test_server(state) as (url, _srv):
        ks = TPURemoteKeySet(url, min_refresh_interval=0.0)
        claims = captest.default_claims()
        tok1 = captest.sign_jwt(priv1, "ES256", claims, kid="gen1")
        out = ks.verify_batch([tok1] * 4)
        assert all(isinstance(r, dict) for r in out)
        fetches_before = state["fetches"]

        # forged token with a KNOWN kid: must fail with NO refetch
        forged = tok1[:-8] + ("AAAAAAAA" if not tok1.endswith("AAAAAAAA")
                              else "BBBBBBBB")
        out = ks.verify_batch([forged])
        assert isinstance(out[0], Exception)
        assert state["fetches"] == fetches_before

        # rotate: new signing key, new kid → one refetch, then verifies.
        # tok1 still verifies in THIS batch (it matched the cached key
        # before the refetch — same semantics as the reference's cached
        # RemoteKeySet).
        state["keys"] = [serialize_public_key(pub2, kid="gen2")]
        tok2 = captest.sign_jwt(priv2, "ES256", claims, kid="gen2")
        out = ks.verify_batch([tok2, tok1])
        assert isinstance(out[0], dict)
        assert isinstance(out[1], dict)
        assert state["fetches"] == fetches_before + 1

        # next batch: gen1 is gone from the rebuilt table → unknown kid
        # → one more refetch, still rejected (IdP dropped the key)
        out = ks.verify_batch([tok1])
        assert isinstance(out[0], Exception)
        assert state["fetches"] == fetches_before + 2

        # attacker-style random unknown kids: the refresh cooldown and
        # the unchanged-content check bound fetches and table rebuilds
        ks2 = TPURemoteKeySet(url, min_refresh_interval=1000.0)
        ks2.verify_batch([tok2])               # builds table, 1 fetch
        fetches = state["fetches"]
        table_obj = ks2._ks
        forged2 = captest.sign_jwt(priv1, "ES256", claims, kid="evil-1")
        forged3 = captest.sign_jwt(priv1, "ES256", claims, kid="evil-2")
        out = ks2.verify_batch([forged2])
        assert isinstance(out[0], Exception)
        out = ks2.verify_batch([forged3])
        assert isinstance(out[0], Exception)
        assert state["fetches"] <= fetches + 1   # cooldown caps fetches
        assert ks2._ks is table_obj              # content unchanged →
        #                                          no table rebuild


def test_remote_keyset_raw_mode_rotation():
    """TPURemoteKeySet.verify_batch_raw: accepted tokens yield payload
    BYTES equal to the dict path's claims, and kid rotation still
    triggers exactly one refetch with per-token verdicts preserved."""
    import json as jsonlib

    from cap_tpu.jwt.jwk import serialize_public_key
    from cap_tpu.jwt.tpu_keyset import TPURemoteKeySet

    priv1, pub1 = captest.generate_keys("ES256")
    priv2, pub2 = captest.generate_keys("ES256")
    state = {"keys": [serialize_public_key(pub1, kid="gen1")]}

    with captest.jwks_test_server(state) as (url, _srv):
        ks = TPURemoteKeySet(url, min_refresh_interval=0.0)
        claims = captest.default_claims()
        tok1 = captest.sign_jwt(priv1, "ES256", claims, kid="gen1")
        forged = tok1[:-8] + ("AAAAAAAA" if not tok1.endswith("AAAAAAAA")
                              else "BBBBBBBB")
        raws = ks.verify_batch_raw([tok1, forged])
        want = ks.verify_batch([tok1])
        assert isinstance(raws[0], bytes)
        assert jsonlib.loads(raws[0]) == want[0]
        assert isinstance(raws[1], InvalidSignatureError)

        # rotation mid-stream, raw path: one refetch, bytes come back
        state["keys"] = [serialize_public_key(pub2, kid="gen2")]
        tok2 = captest.sign_jwt(priv2, "ES256", claims, kid="gen2")
        fetches_before = state["fetches"]
        raws = ks.verify_batch_raw([tok2])
        assert isinstance(raws[0], bytes)
        assert jsonlib.loads(raws[0])["iss"] == claims["iss"]
        assert state["fetches"] == fetches_before + 1


def test_remote_keyset_refetch_failure_keeps_verdicts():
    """A failed rotation refetch (IdP down) must NOT discard the batch's
    verdicts: known-key results stay dicts, the unknown-kid token keeps
    its per-token InvalidSignatureError (ADVICE r1, medium)."""
    from cap_tpu.jwt.jwk import serialize_public_key
    from cap_tpu.jwt.tpu_keyset import TPURemoteKeySet

    priv1, pub1 = captest.generate_keys("ES256")
    evil_priv, _ = captest.generate_keys("ES256")  # NOT in the JWKS
    state = {"keys": [serialize_public_key(pub1, kid="gen1")]}

    with captest.jwks_test_server(state) as (url, srv):
        ks = TPURemoteKeySet(url, min_refresh_interval=0.0)
        claims = captest.default_claims()
        good = captest.sign_jwt(priv1, "ES256", claims, kid="gen1")
        assert isinstance(ks.verify_batch([good])[0], dict)

        # IdP goes away; a batch with one attacker token (unknown kid)
        # plus legitimate tokens must still return per-token verdicts.
        srv.shutdown()
        srv.server_close()
        evil = captest.sign_jwt(evil_priv, "ES256", claims,
                                kid="no-such-kid")
        out = ks.verify_batch([good, evil, good])
        assert isinstance(out[0], dict)
        assert isinstance(out[1], InvalidSignatureError)
        assert isinstance(out[2], dict)


def test_resident_dispatchers_headline_mix():
    """The resident engine benchmark (bench.py resident_mixed_vps)
    dispatches the REAL packed programs on device-resident records:
    accept-bit sums must equal the token count per family bucket, and
    repeated dispatches must keep returning it (the slope-timing loop
    relies on that)."""
    from cap_tpu.jwt.tpu_keyset import resident_dispatchers

    jwks, toks = captest.headline_fixtures(256)
    ks = TPUBatchKeySet(jwks)
    n, fns = resident_dispatchers(ks, toks)
    assert n == len(toks)
    assert len(fns) == 2              # one RS256 bucket + one ES256
    per_fn = {int(fn()) for _, fn in fns}
    assert per_fn == {sum(m for m, _ in fns) // 2}
    total = sum(int(fn()) for _, fn in fns)
    assert total == n


def test_resident_dispatchers_rejects_unroutable():
    """A token that would fall back to the CPU oracle must raise — the
    resident number can never silently measure a subset."""
    from cap_tpu.errors import InvalidParameterError
    from cap_tpu.jwt.tpu_keyset import resident_dispatchers

    jwks, toks = captest.headline_fixtures(16)
    ks = TPUBatchKeySet(jwks)
    priv, _ = captest.generate_keys("ES256")
    stranger = captest.sign_jwt(priv, "ES256", captest.default_claims(),
                                kid="not-in-jwks")
    with pytest.raises(InvalidParameterError):
        resident_dispatchers(ks, toks + [stranger])


def test_wire_adaptive_chunk_sizing():
    """_chunk_tokens targets a TIME budget against the observed wire
    rate: slow link -> smaller chunks (bounded per-chunk latency), fast
    link -> the 8 MB clamp; a real batch updates the estimate."""
    jwks, toks = captest.headline_fixtures(64)
    ks = TPUBatchKeySet(jwks)
    rec_width = 292                   # RS-2048 record bytes

    default = ks._chunk_tokens(rec_width)      # no estimate: ~5 MB
    ks._wire_bps = 6 * (1 << 20)               # 6 MB/s trough
    slow = ks._chunk_tokens(rec_width)
    # 6 MB/s * 250 ms = 1.5 MB -> ~4k tokens of 292 B (pow-2)
    assert slow * rec_width <= int(1.5 * (1 << 20))
    assert slow < default
    ks._wire_bps = 100 * (1 << 20)             # fat co-located link
    fast = ks._chunk_tokens(rec_width)
    assert fast * rec_width <= (8 << 20)       # clamp
    assert fast >= default

    ks._wire_bps = None
    out = ks.verify_batch(toks)
    assert all(isinstance(r, dict) for r in out)
    from cap_tpu.runtime import prep
    if prep._load_native() is not None:
        # the object fallback never dispatches device work, so the
        # estimate only updates on the native batch path
        assert ks._wire_bps is not None and ks._wire_bps > 0


def _sign_raw_payload(priv, alg, payload: bytes, kid: str) -> str:
    """Compact JWS over an ARBITRARY payload (sign_jwt forces a claims
    dict; parity tests need e.g. a JSON array payload)."""
    import json as jsonlib

    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec as cec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        decode_dss_signature,
    )

    from cap_tpu.jwt.jose import b64url_encode

    header = jsonlib.dumps({"alg": alg, "typ": "JWT", "kid": kid},
                           separators=(",", ":")).encode()
    si = (b64url_encode(header) + "." + b64url_encode(payload)).encode()
    der = priv.sign(si, cec.ECDSA(hashes.SHA256()))
    r, s = decode_dss_signature(der)
    sig = r.to_bytes(32, "big") + s.to_bytes(32, "big")
    return si.decode() + "." + b64url_encode(sig)


def test_verify_batch_raw_parity():
    """Raw mode returns the SIGNED payload bytes for every token the
    dict mode accepts, the same error classes for every token it
    rejects — including a VALID signature over a non-object payload —
    and json.loads(raw) == the dict-mode claims."""
    import json as jsonlib

    from cap_tpu.errors import MalformedTokenError

    jwks, toks = captest.headline_fixtures(48)
    es_priv, es_pub = captest.generate_keys("ES256")
    ks = TPUBatchKeySet(jwks + [JWK(es_pub, kid="raw-es")])
    arr_payload = _sign_raw_payload(es_priv, "ES256", b"[1,2,3]",
                                    "raw-es")
    bad_json = _sign_raw_payload(es_priv, "ES256", b"{not json",
                                 "raw-es")
    # BOM-prefixed object: the strict phase-1 scan flags it, but
    # json.loads accepts — BOTH modes must accept (json.loads is
    # authoritative; the native mask is only a fast filter)
    bom = _sign_raw_payload(es_priv, "ES256", b'\xef\xbb\xbf{"b":1}',
                            "raw-es")
    tampered = toks[0][:-8] + ("AAAAAAAA"
                               if not toks[0].endswith("AAAAAAAA")
                               else "BBBBBBBB")
    batch = toks + [bom, arr_payload, bad_json, tampered, "garbage"]

    dicts = ks.verify_batch(batch)
    raws = ks.verify_batch_raw(batch)
    assert len(dicts) == len(raws)
    for i, (d, r) in enumerate(zip(dicts, raws)):
        if isinstance(d, Exception):
            assert isinstance(r, Exception), f"tok {i}"
            assert type(r) is type(d), f"tok {i}: {r!r} vs {d!r}"
        else:
            assert isinstance(r, bytes), f"tok {i}"
            assert jsonlib.loads(r) == d, f"tok {i}"
    # crafted tokens: valid signatures, divergent payloads
    assert dicts[-5] == {"b": 1}                        # BOM accept
    assert isinstance(raws[-5], bytes)
    assert isinstance(dicts[-4], MalformedTokenError)   # [1,2,3]
    assert isinstance(raws[-4], MalformedTokenError)
    assert isinstance(dicts[-3], MalformedTokenError)   # {not json
    assert isinstance(raws[-3], MalformedTokenError)


def test_payload_object_ok_matches_json_loads():
    """The phase-1-only validity mask agrees with json.loads on
    object/non-object/malformed/exotic payloads."""
    import json as jsonlib

    from cap_tpu.runtime import prep

    if prep._load_native() is None:
        pytest.skip("native runtime not built")
    from cap_tpu.runtime.native_binding import prepare_batch_arrays

    es_priv, es_pub = captest.generate_keys("ES256")
    payloads = [
        b'{"a":1}', b"[1,2]", b"42", b'"str"', b"{broken",
        b'{"nested":{"deep":[1,{"x":null}]}}',
        b'{"u":"\\ud83d\\ude00"}',           # surrogate pair: fallback
        b'{"big":123456789012345678901234567890123456789012}',
        "{\"k\":\"café\"}".encode(),
        b'  {"ws": 1}  ',
    ]
    toks = [_sign_raw_payload(es_priv, "ES256", p, "k") for p in payloads]
    pb = prepare_batch_arrays(toks)
    assert (pb.status == 0).all()
    got = pb.payload_object_ok(np.arange(len(toks)))
    for i, p in enumerate(payloads):
        try:
            want = isinstance(jsonlib.loads(p), dict)
        except ValueError:
            want = False
        # The mask is ONE-SIDED: True must imply json.loads accepts
        # (callers re-check the Falses with json.loads, which accepts
        # some payloads the strict scan flags, e.g. BOM prefixes).
        if got[i]:
            assert want, f"payload {i}: {p!r}"
    # and for these plain-UTF-8 payloads the mask is exact
    assert [bool(g) for g in got] == [
        True, False, False, False, False, True, True, True, True, True]


def _per_token_finish(chunk, okv, fold):
    """The verdict fold one token at a time, as it was before the
    vectorized fold: the reference the fold is held to. It marks no
    token answered or bulk, so the collect's scan for missing verdicts
    and its reject list then cover every token."""
    pb, results, raw_ok = fold.pb, fold.results, fold.raw_ok
    cache = getattr(pb, "_claims_cache", None)
    if cache is None:
        cache = {}
    claims = pb.claims
    msg = ("no known key successfully validated the token "
           "signature")
    for j, good in zip(np.asarray(chunk).tolist(),
                       np.asarray(okv).tolist()):
        if good:
            if raw_ok is not None:
                if raw_ok[j]:
                    results[j] = pb.payload_bytes(j)
                else:
                    try:
                        claims(j)
                        results[j] = pb.payload_bytes(j)
                    except MalformedTokenError as e:
                        results[j] = e
                continue
            hit = cache.get(j)
            if hit is None:
                try:
                    hit = claims(j)
                except MalformedTokenError as e:
                    hit = e
            results[j] = hit
        else:
            results[j] = InvalidSignatureError(msg)


def _per_token_families(pb, ok):
    from cap_tpu.obs import decision
    from cap_tpu.runtime.native_binding import ALG_NAMES

    fam_for = [decision.family_for_alg(a) for a in ALG_NAMES]
    out = []
    for j in range(pb.n):
        if not ok[j]:
            out.append("unknown")
        elif int(pb.alg_id[j]) >= 0:
            out.append(fam_for[int(pb.alg_id[j])])
        else:
            out.append(decision.family_for_alg(pb.alg(j)))
    return out


def _fold_mix():
    """One batch with every kind of answer the fold gives, and the
    index of each crafted token by name: RS256 and ES256 accepts,
    flipped signatures, a swapped payload, alg=none, a malformed
    segment, verified signatures over a non-object, a non-JSON and a
    BOM-prefixed payload, JSON-form tokens (one compacted onto the
    device path, one whose kid rides only in the unprotected header,
    verified on the object path, and one that does not parse), a kid
    no key row holds (the CPU fallback), and an ES384 and an HS256
    token, which no device table takes (the scan for missing
    verdicts)."""
    jwks, signers = captest.headline_keys()
    toks = captest.sign_unique_jwts(signers, 40)
    es_priv, es_pub = captest.generate_keys("ES256")
    ks = TPUBatchKeySet(jwks + [JWK(es_pub, kid="raw-es")])

    def flip(tok):
        h, p, s = tok.split(".")
        return f"{h}.{p}.{s[:5]}{'A' if s[5] != 'A' else 'B'}{s[6:]}"

    h0, _, s0 = toks[0].split(".")
    priv, alg, kid = signers[0]
    p384, _ = captest.generate_keys("ES384")
    crafted = {
        "flipped_rs": flip(toks[1]),
        "flipped_es": flip(toks[8]),
        "swapped": f"{h0}.{toks[2].split('.')[1]}.{s0}",
        "alg_none": (b64url_encode(b'{"alg":"none"}') + "."
                     + toks[4].split(".")[1] + "."),
        "malformed": f"{h0}.@@@.{s0}",
        "array": _sign_raw_payload(es_priv, "ES256", b"[1,2,3]",
                                   "raw-es"),
        "not_json": _sign_raw_payload(es_priv, "ES256", b"{not json",
                                      "raw-es"),
        "bom": _sign_raw_payload(es_priv, "ES256",
                                 b'\xef\xbb\xbf{"b":1}', "raw-es"),
        "json_compacted": captest.to_json_form(
            toks[6], unprotected={"x5u": "u"}),
        "json_special": captest.to_json_form(
            captest.sign_jwt(priv, alg, captest.default_claims()),
            unprotected={"kid": kid}),
        "json_broken": '{"payload": "e30", "signatures": 5}',
        "flipped_late": flip(toks[11]),
        "unknown_kid": captest.sign_jwt(
            priv, alg, captest.default_claims(), kid="not-in-the-jwks"),
        "es384": captest.sign_jwt(p384, "ES384", captest.default_claims(),
                                  kid="es384-x"),
        "hs256": (b64url_encode(b'{"alg":"HS256","kid":"h"}') + "."
                  + b64url_encode(b'{"a":1}') + "."
                  + b64url_encode(b"x" * 32)),
    }
    at = {name: len(toks) + i for i, name in enumerate(crafted)}
    return ks, toks + list(crafted.values()), at


def _same_answer(a, b):
    if isinstance(a, Exception):
        return (type(a) is type(b) and str(a) == str(b)
                and a.args == b.args)
    return type(a) is type(b) and a == b


def test_vectorized_fold_matches_per_token_reference(monkeypatch):
    """Raw and dict mode: every answer of the vectorized verdict fold
    equals the per-token reference's, byte for byte and error class
    and message for message; so do the CPU fallback's count and every
    tpu-surface decision counter and ring entry. ES lanes flagged
    degenerate are re-verified on the CPU and enter the fold the
    same way."""
    import jax.numpy as jnp

    from cap_tpu import telemetry
    from cap_tpu.jwt import tpu_keyset as tk
    from cap_tpu.obs import decision
    from cap_tpu.tpu import ec as tpuec

    ks, batch, at = _fold_mix()
    real_es = tpuec.verify_es_packed_pending
    deg_calls = []

    def es_with_degenerate_lanes(*a, **kw):
        ok_dev, deg_dev = real_es(*a, **kw)
        return ok_dev, jnp.asarray(deg_dev).at[::3].set(True)

    real_cpu = tpuec._cpu_verify_one

    def spy(*a):
        deg_calls.append(a[1])
        return real_cpu(*a)

    monkeypatch.setattr(tpuec, "verify_es_packed_pending",
                        es_with_degenerate_lanes)
    monkeypatch.setattr(tpuec, "_cpu_verify_one", spy)

    def run(reference: bool):
        with monkeypatch.context() as m:
            if reference:
                real_record = decision.record_batch

                def walk(*a, rejects=None, **kw):
                    return real_record(*a, **kw)

                m.setattr(TPUBatchKeySet, "_finish_arrays",
                          staticmethod(_per_token_finish))
                m.setattr(tk, "_token_families", _per_token_families)
                m.setattr(decision, "record_batch", walk)
            out = {}
            for mode in ("raw", "dict"):
                with telemetry.recording() as rec:
                    res = (ks.verify_batch_raw(batch) if mode == "raw"
                           else ks.verify_batch(batch))
                    c = rec.counters()
                    ring = [{k: v for k, v in e.items() if k != "lat"}
                            for e in rec.decisions()]
                out[mode] = (res, {k: v for k, v in c.items()
                                   if k.startswith("decision.")
                                   or k == "cpu_fallback.tokens"}, ring)
            return out

    want, got = run(True), run(False)
    assert deg_calls, "no ES lane was flagged degenerate"
    for mode in ("raw", "dict"):
        (w, wc, wr), (g, gc, gr) = want[mode], got[mode]
        assert len(g) == len(w) == len(batch)
        bad = [i for i, (a, b) in enumerate(zip(w, g))
               if not _same_answer(a, b)]
        assert not bad, (mode, [(w[i], g[i]) for i in bad])
        assert gc == wc, mode
        assert gr == wr, mode
    raw, dicts = got["raw"][0], got["dict"][0]
    # unknown_kid, es384, hs256
    assert got["raw"][1]["cpu_fallback.tokens"] == 3
    # the crafted tokens answer as the kinds they were made to be
    for name in ("flipped_rs", "flipped_es", "swapped", "flipped_late",
                 "es384", "hs256"):
        assert isinstance(raw[at[name]], InvalidSignatureError), name
    for name in ("alg_none", "malformed", "array", "not_json",
                 "json_broken"):
        assert isinstance(raw[at[name]], Exception), name
    for name in ("malformed", "array", "not_json", "json_broken"):
        assert isinstance(raw[at[name]], MalformedTokenError), name
    assert raw[at["bom"]] == b'\xef\xbb\xbf{"b":1}'
    for name in ("bom", "json_compacted", "json_special", "unknown_kid"):
        assert isinstance(raw[at[name]], bytes), name
        assert isinstance(dicts[at[name]], dict), name
    assert sum(isinstance(r, bytes) for r in raw) == 40 + 4


def test_finish_per_token_counts_the_tokens_answered_one_by_one():
    """``keyset.finish.per_token``: in raw mode the device-path rejects
    and the verified signatures whose payload the phase-1 check
    flagged; in dict mode every token the fold answers. Prep errors
    and JSON-form specials never reach the fold."""
    from cap_tpu import telemetry

    ks, batch, _ = _fold_mix()
    with telemetry.recording() as rec:
        ks.verify_batch_raw(batch)
        raw = rec.counters().get("keyset.finish.per_token", 0)
    with telemetry.recording() as rec:
        ks.verify_batch(batch)
        dicts = rec.counters().get("keyset.finish.per_token", 0)
    # 3 flipped + 1 swapped signature; [1,2,3], {not json, BOM
    assert raw == 4 + 3
    # the 40 signed tokens, the 7 crafted ones that pass prep and the
    # compacted JSON form
    assert dicts == 40 + 7 + 1
