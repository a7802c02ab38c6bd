"""ctypes binding for the native capruntime (jose_native.cpp).

Loads cap_tpu/runtime/native/libcapruntime.so (built via ``make native``)
and exposes ``prepare_batch(tokens)`` returning, per token, either a
:class:`NativeParsed` (duck-compatible with jose.ParsedJWS for the batch
path: alg / kid / signature / signing_input / payload / claims() /
digest()) or the same taxonomy exception the Python parser raises.

Raises OSError at import when the library is missing — runtime.prep
catches that and falls back to pure Python.
"""

from __future__ import annotations

import ctypes
import json
import os
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..errors import MalformedTokenError, TokenNotSignedError

_LIB_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "native", "libcapruntime.so")
# Build artifacts are not committed (ADVICE r1): build on first use.
# Unconditional — build_native() is a cheap no-op when everything is
# fresh (per-target mtime checks) and rebuilds STALE libraries too; a
# missing-only gate would leave an old libcapruntime.so without the
# record packer and never compile _capclaims.so at all.
from .._build import build_native

build_native()
_lib = ctypes.CDLL(_LIB_PATH)

ALG_NAMES = ["RS256", "RS384", "RS512", "ES256", "ES384", "ES512",
             "PS256", "PS384", "PS512", "EdDSA"]

(_OK, _ERR_SEGMENTS, _ERR_B64, _ERR_HEADER_JSON, _ERR_NO_ALG, _ERR_UNSIGNED,
 _ERR_CRIT) = range(7)


class _TokOut(ctypes.Structure):
    _fields_ = [
        ("status", ctypes.c_int32),
        ("alg_id", ctypes.c_int32),
        ("sig_off", ctypes.c_int64),
        ("sig_len", ctypes.c_int64),
        ("payload_off", ctypes.c_int64),
        ("payload_len", ctypes.c_int64),
        ("signing_input_len", ctypes.c_int64),
        ("kid", ctypes.c_uint8 * 160),
        ("alg_raw", ctypes.c_uint8 * 32),
        ("digest", ctypes.c_uint8 * 64),
        ("digest_len", ctypes.c_int32),
        ("kid_len", ctypes.c_int32),
        ("alg_len", ctypes.c_int32),
        ("pad", ctypes.c_int32),
    ]


assert ctypes.sizeof(_TokOut) == _lib.cap_tokout_size(), \
    "TokOut ABI mismatch between binding and libcapruntime"

_lib.cap_prepare_batch.argtypes = [
    ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
    ctypes.POINTER(_TokOut), ctypes.POINTER(ctypes.c_uint8),
    ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
]
_lib.cap_sha_batch.argtypes = [
    ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int64),
    ctypes.c_int64, ctypes.c_int32, ctypes.POINTER(ctypes.c_uint8),
    ctypes.c_int32,
]
def _load_claims_ext():
    """Import the _capclaims extension module (None when unbuilt)."""
    import importlib.machinery
    import importlib.util

    from .._build import EXT_NAME

    path = os.path.join(os.path.dirname(_LIB_PATH), EXT_NAME)
    if not os.path.exists(path):
        return None
    try:
        loader = importlib.machinery.ExtensionFileLoader("_capclaims", path)
        spec = importlib.util.spec_from_loader("_capclaims", loader)
        mod = importlib.util.module_from_spec(spec)
        loader.exec_module(mod)
        return mod
    except Exception:  # noqa: BLE001 - stale/foreign .so → Python parse
        return None


_claims_ext = _load_claims_ext()

try:
    _lib.cap_pack_sig_records.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int32,
    ]
    _HAS_PACK_RECORDS = True
except AttributeError:       # stale .so from before the packer
    _HAS_PACK_RECORDS = False

try:
    _lib.cap_pss_check_batch.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_int32,
    ]
    _HAS_PSS_CHECK = True
except AttributeError:           # stale .so from before the PSS check
    _HAS_PSS_CHECK = False


class NativeParsed:
    """Parsed JWS view over native-decoded buffers (unverified)."""

    __slots__ = ("alg", "kid", "signature", "payload", "signing_input",
                 "_digest", "_digest_len", "header")

    def __init__(self, alg: str, kid: Optional[str], signature: bytes,
                 payload: bytes, signing_input: bytes,
                 digest: bytes):
        self.alg = alg
        self.kid = kid
        self.signature = signature
        self.payload = payload
        self.signing_input = signing_input
        self._digest = digest
        # only alg/kid are extracted natively; enough for the batch path
        self.header: Dict[str, Any] = (
            {"alg": alg, "kid": kid} if kid is not None else {"alg": alg})

    def claims(self) -> Dict[str, Any]:
        try:
            claims = json.loads(self.payload)
        except (ValueError, UnicodeDecodeError) as e:
            raise MalformedTokenError(f"payload is not valid JSON: {e}") from e
        if not isinstance(claims, dict):
            raise MalformedTokenError("payload is not a JSON object")
        return claims

    def digest(self) -> bytes:
        """Precomputed SHA-2 of the signing input (empty for EdDSA)."""
        return self._digest


def prepare_batch(tokens: Sequence[str],
                  n_threads: int = 0) -> List[Any]:
    n = len(tokens)
    if n == 0:
        return []
    try:
        encoded = [t.encode("ascii") for t in tokens]
    except UnicodeEncodeError:
        # non-ascii tokens: delegate entirely to the Python parser
        from .prep import _prepare_python

        return _prepare_python(tokens)
    blob = b"".join(encoded)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum([len(e) for e in encoded], out=offsets[1:])
    # per-token scratch: decoded payload+sig always fit in token length+8
    scratch_sizes = np.asarray([len(e) + 8 for e in encoded], np.int64)
    scratch_offsets = np.zeros(n + 1, np.int64)
    np.cumsum(scratch_sizes, out=scratch_offsets[1:])
    scratch = np.empty(int(scratch_offsets[-1]), np.uint8)
    outs = (_TokOut * n)()

    _lib.cap_prepare_batch(
        blob,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n, outs,
        scratch.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        scratch_offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n_threads,
    )

    scratch_bytes = scratch.tobytes()
    results: List[Any] = []
    for i in range(n):
        o = outs[i]
        if o.status == _OK:
            base = int(scratch_offsets[i])
            tok_base = int(offsets[i])
            payload = scratch_bytes[base: base + o.payload_len]
            sig = scratch_bytes[base + o.sig_off:
                                base + o.sig_off + o.sig_len]
            signing_input = blob[tok_base: tok_base + o.signing_input_len]
            if o.kid_len == -1:
                kid = None
            elif o.kid_len == -2:
                from ..jwt.jose import parse_compact as _pc

                try:
                    kid = _pc(tokens[i]).kid
                except Exception:  # noqa: BLE001
                    kid = None
            else:
                kid = bytes(bytearray(o.kid[: o.kid_len])).decode(
                    "utf-8", "surrogateescape")
            alg = (ALG_NAMES[o.alg_id] if o.alg_id >= 0
                   else bytes(bytearray(o.alg_raw[: o.alg_len])).decode(
                       "utf-8", "surrogateescape"))
            results.append(NativeParsed(
                alg, kid, sig, payload, signing_input,
                bytes(o.digest[: o.digest_len])))
        elif o.status == _ERR_UNSIGNED:
            results.append(TokenNotSignedError("token must be signed"))
        elif o.status == _ERR_SEGMENTS:
            results.append(MalformedTokenError(
                "compact JWS must have 3 segments"))
        elif o.status == _ERR_NO_ALG:
            results.append(MalformedTokenError(
                "protected header missing alg parameter"))
        elif o.status == _ERR_HEADER_JSON:
            results.append(MalformedTokenError(
                "protected header is not a JSON object"))
        elif o.status == _ERR_CRIT:
            results.append(MalformedTokenError("unsupported crit header"))
        else:
            results.append(MalformedTokenError(
                "invalid base64url segment"))
    return results


def _loads_claims(raw: bytes):
    """ONE json.loads-payload-to-claims helper: dict or the
    MalformedTokenError whose class/wording every parse path shares
    (prefetch fallbacks, the raw OIDC mode, _parse_one)."""
    try:
        c = json.loads(raw)
        return c if isinstance(c, dict) else \
            MalformedTokenError("payload is not a JSON object")
    except (ValueError, UnicodeDecodeError) as e:
        return MalformedTokenError(f"payload is not valid JSON: {e}")


def registered_claims_from_payloads(payloads: Sequence[bytes]):
    """[payload bytes] → per-payload claims for VALIDATION only.

    Each entry is a dict (the native extension's registered-claims
    SUBSET — iss/sub/aud/exp/nbf/iat/nonce/azp/auth_time — or the
    json.loads full dict on its conservative fallbacks) or a
    MalformedTokenError. The OIDC raw mode reads only registered
    claims, so the subset is indistinguishable from the full parse
    there while skipping the full dict build per token.
    """
    full = _loads_claims
    if _claims_ext is None or not hasattr(_claims_ext,
                                          "registered_batch"):
        return [full(p) for p in payloads]
    scratch = b"".join(payloads)
    lens = np.asarray([len(p) for p in payloads], np.int64)
    offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int64) \
        if len(payloads) else np.zeros(0, np.int64)
    parsed, n_bad = _claims_ext.registered_batch(
        scratch, np.ascontiguousarray(offs), np.ascontiguousarray(lens))
    if n_bad == 0:
        return parsed
    return [v if type(v) is dict else full(payloads[i])
            for i, v in enumerate(parsed)]


def exp_nbf_from_payloads(payloads: Sequence[bytes]):
    """[raw payload bytes] → ``(exp, nbf, status)`` numpy arrays, or
    None when the extension is absent.

    ``exp``/``nbf`` are float64 (NaN = absent) read by the native tape
    scan from each payload's top level; ``status`` is 0 where they
    were read and nonzero where the caller must read that payload
    itself (the rules are in claims_ext.cpp's ``exp_nbf_batch``).
    The scan runs with the GIL released and makes no Python object
    per payload."""
    if _claims_ext is None or not hasattr(_claims_ext, "exp_nbf_batch"):
        return None
    n = len(payloads)
    lens = np.fromiter(map(len, payloads), np.int64, count=n)
    offs = np.zeros(n, np.int64)
    np.cumsum(lens[:-1], out=offs[1:])
    exp, nbf, status = _claims_ext.exp_nbf_batch(b"".join(payloads),
                                                 offs, lens)
    return (np.frombuffer(exp, np.float64), np.frombuffer(nbf, np.float64),
            np.frombuffer(status, np.uint8))


def _copy_claims(v):
    """Independent copy of a parsed-JSON value (containers only)."""
    if isinstance(v, dict):
        return {k: _copy_claims(x) for k, x in v.items()}
    if isinstance(v, list):
        return [_copy_claims(x) for x in v]
    return v


class PreparedBatch:
    """Structure-of-arrays view of a prepared token batch.

    The zero-copy fast path for ``TPUBatchKeySet``: statuses, alg ids,
    kid bytes, signatures, and digests stay as numpy arrays so
    bucketing, key-row lookup, and limb packing are vectorized;
    per-token Python objects are only created lazily (claims of
    verified tokens, error objects for failures).
    """

    __slots__ = ("n", "status", "alg_id", "kid_mat", "kid_len", "sig_off",
                 "sig_len", "payload_off", "payload_len", "si_len", "digest",
                 "digest_len", "scratch", "blob", "tok_off", "alg_raw",
                 "alg_len", "_claims_cache")

    def __init__(self, n, status, alg_id, kid_mat, kid_len, sig_off, sig_len,
                 payload_off, payload_len, si_len, digest, digest_len,
                 scratch, blob, tok_off, alg_raw, alg_len):
        self.n = n
        self.status = status
        self.alg_id = alg_id
        self.kid_mat = kid_mat
        self.kid_len = kid_len          # -1 absent, -2 overlong
        self.sig_off = sig_off          # absolute into scratch
        self.sig_len = sig_len
        self.payload_off = payload_off  # absolute into scratch
        self.payload_len = payload_len
        self.si_len = si_len
        self.digest = digest            # [n, 64] uint8
        self.digest_len = digest_len
        self.scratch = scratch          # uint8 array (decoded payload+sig)
        self.blob = blob                # bytes (raw concatenated tokens)
        self.tok_off = tok_off
        self.alg_raw = alg_raw          # [n, 32] uint8 (for unknown algs)
        self.alg_len = alg_len

    # -- vectorized helpers -----------------------------------------------

    def sig_matrix(self, idx: np.ndarray, width: int) -> np.ndarray:
        """[len(idx), width] uint8: left-aligned raw signature bytes,
        zero-padded at the tail (pair with sig_len)."""
        cols = np.arange(width)[None, :]
        offs = self.sig_off[idx][:, None] + cols
        lens = self.sig_len[idx][:, None]
        safe = np.minimum(offs, len(self.scratch) - 1)
        mat = self.scratch[safe]
        return np.where(cols < lens, mat, 0).astype(np.uint8)

    def kid_rows(self, idx: np.ndarray, kid_to_row: dict) -> np.ndarray:
        """Vectorized kid → key-row resolution. Returns row per token;
        -1 = no kid; -2 = unknown/unresolvable kid.

        One np.unique over (kid bytes ‖ kid length) views, then a dict
        lookup per *unique* kid — O(m log m + uniques), independent of
        JWKS size (byte-exact: embedded NULs fine; overlong kids were
        flagged by the native layer and resolve to -2 → exact slow path).
        """
        m = len(idx)
        lens = self.kid_len[idx]
        rows = np.full(m, -2, np.int32)
        rows[lens == -1] = -1
        present = lens >= 0
        if not present.any():
            return rows
        keyed = np.zeros((m, 164), np.uint8)
        keyed[present, :160] = self.kid_mat[idx[present]]
        keyed[present, 160:] = lens[present, None].astype(np.int32).view(
            np.uint8).reshape(-1, 4)
        view = np.ascontiguousarray(keyed).view(
            np.dtype((np.void, 164))).ravel()
        uniq, inverse = np.unique(view, return_inverse=True)
        uniq_rows = np.full(len(uniq), -2, np.int32)
        for u in range(len(uniq)):
            raw = uniq[u].tobytes()
            klen = int(np.frombuffer(raw[160:], np.int32)[0])
            if klen < 0:
                continue
            kid = raw[:klen].decode("utf-8", "surrogateescape")
            uniq_rows[u] = kid_to_row.get(kid, -2)
        resolved = uniq_rows[inverse]
        rows[present] = resolved[present]
        return rows

    def pack_sig_records(self, idx: np.ndarray, expect_size: np.ndarray,
                         extra_valid: np.ndarray, key_rows: np.ndarray,
                         width: int, h_len: int,
                         pad: int) -> Optional[np.ndarray]:
        """One-pass native build of a packed [pad, width+h_len+2] u8
        record chunk: right-aligned signature ‖ digest ‖ flag ‖ key row.

        Row flags are 1 iff extra_valid[r] and sig_len == expect_size
        (the CPU oracle's length rejections). Returns None when the
        loaded library predates the packer (caller uses the numpy
        path). GIL-free and multithreaded — this replaces several
        full-matrix numpy passes on the batch hot path.
        """
        if not _HAS_PACK_RECORDS:
            return None
        m = len(idx)
        idx = np.ascontiguousarray(idx, np.int64)
        expect = np.ascontiguousarray(expect_size, np.int64)
        valid = np.ascontiguousarray(extra_valid, np.uint8)
        rows = np.ascontiguousarray(key_rows, np.uint8)
        out = np.empty((pad, width + h_len + 2), np.uint8)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i64p = ctypes.POINTER(ctypes.c_int64)
        _lib.cap_pack_sig_records(
            self.scratch.ctypes.data_as(u8p), len(self.scratch),
            self.sig_off.ctypes.data_as(i64p),
            self.sig_len.ctypes.data_as(i64p),
            self.digest.ctypes.data_as(u8p), self.digest.shape[1],
            idx.ctypes.data_as(i64p), expect.ctypes.data_as(i64p),
            valid.ctypes.data_as(u8p), rows.ctypes.data_as(u8p),
            m, pad, width, h_len, out.ctypes.data_as(u8p), 0)
        return out

    # -- lazy per-token materialization -----------------------------------

    def payload_bytes(self, i: int) -> bytes:
        o, l = int(self.payload_off[i]), int(self.payload_len[i])
        return self.scratch[o: o + l].tobytes()

    def payloads(self, idx) -> List[bytes]:
        """The payload bytes of the tokens ``idx``, in order: one
        ``tobytes()`` of ``scratch``, then one slicing pass (no
        per-token numpy slice or int conversion)."""
        buf = self.scratch.tobytes()
        starts = self.payload_off[idx]
        return [buf[o:e] for o, e in zip(
            starts.tolist(), (starts + self.payload_len[idx]).tolist())]

    def payload_object_ok(self, indices) -> np.ndarray:
        """[len(indices)] bool: the payload parses as a JSON OBJECT.

        Phase-1 only (no dicts built): the raw-claims serve path passes
        the signed payload bytes through verbatim, but a verified
        signature over a non-object payload must still reject exactly
        like the claims() path. Status 3 (outside the strict native
        parser's envelope) falls back to json.loads so the decision is
        byte-identical to the Python path; without the extension every
        token takes that fallback.
        """
        idx = np.ascontiguousarray(indices, np.int64)
        out = np.zeros(len(idx), bool)
        if _claims_ext is not None and hasattr(_claims_ext,
                                               "validate_batch"):
            offs = np.ascontiguousarray(self.payload_off[idx], np.int64)
            lens = np.ascontiguousarray(self.payload_len[idx], np.int64)
            st = np.frombuffer(
                _claims_ext.validate_batch(self.scratch, offs, lens),
                np.uint8)
            out[:] = st == 0
            for k in np.nonzero(st == 3)[0]:
                out[k] = self._payload_is_object(int(idx[k]))
            return out
        for k, i in enumerate(idx):
            out[k] = self._payload_is_object(int(i))
        return out

    def _payload_is_object(self, i: int) -> bool:
        try:
            return isinstance(json.loads(self.payload_bytes(i)), dict)
        except (ValueError, UnicodeDecodeError):
            return False

    def claims(self, i: int) -> Dict[str, Any]:
        cache = getattr(self, "_claims_cache", None)
        if cache is not None:
            hit = cache.get(i)
            if hit is not None:
                if isinstance(hit, MalformedTokenError):
                    raise hit
                return hit
        try:
            claims = json.loads(self.payload_bytes(i))
        except (ValueError, UnicodeDecodeError) as e:
            raise MalformedTokenError(f"payload is not valid JSON: {e}") from e
        if not isinstance(claims, dict):
            raise MalformedTokenError("payload is not a JSON object")
        return claims

    def prefetch_claims(self, indices) -> None:
        """Pre-parse claim payloads into a per-index cache.

        Called between device dispatch and the materializing sync so
        the host-side JSON parsing overlaps the device wait instead of
        serializing after it. The _capclaims extension does the heavy
        scan GIL-free across threads (~2 µs/token); payloads outside
        its envelope re-parse with json.loads — byte-for-byte identical
        results either way (tests/test_native_runtime.py fuzz parity).
        Without the extension, identical payload bytes (replay-heavy
        workloads) parse once and fan out as independent copies.
        """
        try:
            cache = self._claims_cache
        except AttributeError:
            cache = {}
            self._claims_cache = cache
        scratch = self.scratch
        off, ln = self.payload_off, self.payload_len
        if not cache and isinstance(indices, np.ndarray):
            idx = indices.astype(np.int64, copy=False)
        else:
            idx = np.asarray([i for i in indices
                              if int(i) not in cache], np.int64)
        if len(idx) == 0:
            return
        if _claims_ext is not None:
            offs = np.ascontiguousarray(off[idx], np.int64)
            lens = np.ascontiguousarray(ln[idx], np.int64)
            res = _claims_ext.parse_batch(scratch, offs, lens)
            if isinstance(res, tuple):
                parsed, n_bad = res
            else:
                # pre-(list, n_bad) extension build still loaded (a
                # failed rebuild keeps the old .so): no fast-path count,
                # take the per-token branch below.
                parsed, n_bad = res, -1
            idx_list = idx.tolist()
            if n_bad == 0:
                # All dicts: one C-level bulk insert, no per-token
                # Python iteration (measurable at 64k tokens on a
                # one-core host).
                cache.update(zip(idx_list, parsed))
                return
            for j, v in zip(idx_list, parsed):
                if type(v) is dict:
                    cache[j] = v
                else:
                    # malformed / not-an-object / outside-envelope:
                    # re-parse with json.loads so messages and edge
                    # semantics are byte-identical to the Python path
                    # (the int status is only a fast-path filter).
                    cache[j] = self._parse_one(int(off[j]), int(ln[j]))
            return
        protos: Dict[bytes, Any] = {}
        for i in idx:
            i = int(i)
            raw = scratch[off[i]: off[i] + ln[i]].tobytes()
            proto = protos.get(raw)
            if proto is None:
                proto = self._parse_one(int(off[i]), int(ln[i]))
                protos[raw] = proto
            cache[i] = _copy_claims(proto) \
                if isinstance(proto, dict) else proto

    def _parse_one(self, off: int, ln: int) -> Any:
        """json.loads one payload → dict or MalformedTokenError."""
        return _loads_claims(self.scratch[off: off + ln].tobytes())

    def signature(self, i: int) -> bytes:
        o, l = int(self.sig_off[i]), int(self.sig_len[i])
        return self.scratch[o: o + l].tobytes()

    def signing_input(self, i: int) -> bytes:
        o = int(self.tok_off[i])
        return self.blob[o: o + int(self.si_len[i])]

    def token(self, i: int) -> str:
        o, e = int(self.tok_off[i]), int(self.tok_off[i + 1])
        return self.blob[o:e].decode("ascii")

    def alg(self, i: int) -> str:
        aid = int(self.alg_id[i])
        if aid >= 0:
            return ALG_NAMES[aid]
        n = int(self.alg_len[i])
        return self.alg_raw[i, :n].tobytes().decode("utf-8", "surrogateescape")

    def kid(self, i: int) -> Optional[str]:
        n = int(self.kid_len[i])
        if n == -1:
            return None
        if n == -2:
            # overlong kid (>160B): not captured natively; re-parse the
            # original token in Python for the exact value
            from ..jwt.jose import parse_compact

            try:
                return parse_compact(self.token(i)).kid
            except Exception:  # noqa: BLE001
                return None
        return self.kid_mat[i, :n].tobytes().decode("utf-8", "surrogateescape")

    def error(self, i: int) -> Exception:
        s = int(self.status[i])
        if s == _ERR_UNSIGNED:
            return TokenNotSignedError("token must be signed")
        if s == _ERR_SEGMENTS:
            return MalformedTokenError("compact JWS must have 3 segments")
        if s == _ERR_NO_ALG:
            return MalformedTokenError(
                "protected header missing alg parameter")
        if s == _ERR_HEADER_JSON:
            return MalformedTokenError(
                "protected header is not a JSON object")
        if s == _ERR_CRIT:
            return MalformedTokenError("unsupported crit header")
        return MalformedTokenError("invalid base64url segment")

    def parsed(self, i: int) -> "NativeParsed":
        """Materialize one token as a NativeParsed (slow-path interop)."""
        return NativeParsed(
            self.alg(i), self.kid(i), self.signature(i),
            self.payload_bytes(i), self.signing_input(i),
            bytes(self.digest[i, : self.digest_len[i]]))


_TOKOUT_DTYPE = np.dtype([
    ("status", np.int32), ("alg_id", np.int32),
    ("sig_off", np.int64), ("sig_len", np.int64),
    ("payload_off", np.int64), ("payload_len", np.int64),
    ("signing_input_len", np.int64),
    ("kid", np.uint8, 160), ("alg_raw", np.uint8, 32),
    ("digest", np.uint8, 64), ("digest_len", np.int32),
    ("kid_len", np.int32), ("alg_len", np.int32), ("pad", np.int32),
])
assert _TOKOUT_DTYPE.itemsize == ctypes.sizeof(_TokOut)


def prepare_batch_arrays(tokens: Sequence[str],
                         n_threads: int = 0) -> PreparedBatch:
    """Prepare a batch into structure-of-arrays form (the fast path)."""
    n = len(tokens)
    blob_str = "".join(tokens)
    blob = blob_str.encode("ascii", "replace")  # non-ascii → malformed anyway
    lengths = np.fromiter((len(t) for t in tokens), np.int64, count=n)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(lengths, out=offsets[1:])
    scratch_offsets = np.zeros(n + 1, np.int64)
    np.cumsum(lengths + 8, out=scratch_offsets[1:])
    scratch = np.empty(int(scratch_offsets[-1]) + 1, np.uint8)
    outs = np.zeros(n, dtype=_TOKOUT_DTYPE)

    _lib.cap_prepare_batch(
        blob,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
        outs.ctypes.data_as(ctypes.POINTER(_TokOut)),
        scratch.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        scratch_offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n_threads,
    )
    base = scratch_offsets[:n]
    return PreparedBatch(
        n=n,
        status=outs["status"],
        alg_id=outs["alg_id"],
        kid_mat=outs["kid"],
        kid_len=outs["kid_len"],
        sig_off=base + outs["sig_off"],
        # contiguous copies: the native record packer reads these
        # through raw pointers (structured-array field views stride by
        # the full record and would be misread)
        sig_len=np.ascontiguousarray(outs["sig_len"]),
        payload_off=base + outs["payload_off"],
        payload_len=outs["payload_len"],
        si_len=outs["signing_input_len"],
        digest=np.ascontiguousarray(outs["digest"]),
        digest_len=outs["digest_len"],
        scratch=scratch,
        blob=blob,
        tok_off=offsets,
        alg_raw=outs["alg_raw"],
        alg_len=outs["alg_len"],
    )


def sha_batch(chunks: Sequence[bytes], bits: int,
              n_threads: int = 0) -> List[bytes]:
    """Batched SHA-256/384/512 over byte chunks via the native library."""
    n = len(chunks)
    if n == 0:
        return []
    blob = b"".join(chunks)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum([len(c) for c in chunks], out=offsets[1:])
    out_len = bits // 8
    out = np.empty(n * out_len, np.uint8)
    data = np.frombuffer(blob, np.uint8)
    _lib.cap_sha_batch(
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n, bits,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n_threads,
    )
    raw = out.tobytes()
    return [raw[i * out_len:(i + 1) * out_len] for i in range(n)]


def pss_check_batch(em_mat: np.ndarray, mhash_mat: np.ndarray,
                    em_bits: np.ndarray, bits: int, valid: np.ndarray,
                    n_threads: int = 0) -> Optional[np.ndarray]:
    """Batched EMSA-PSS-VERIFY (salt auto-recovered) in native C++.

    em_mat: [n, stride] right-aligned big-endian EM bytes;
    mhash_mat: [n, ≥bits/8] digests; em_bits: [n] modBits-1;
    valid: [n] precondition mask. Returns [n] bool, or None when the
    loaded library predates cap_pss_check_batch (caller falls back to
    the Python check).
    """
    if not _HAS_PSS_CHECK:
        return None
    em_mat = np.ascontiguousarray(em_mat, np.uint8)
    mhash_mat = np.ascontiguousarray(mhash_mat, np.uint8)
    em_bits = np.ascontiguousarray(em_bits, np.int64)
    valid_u8 = np.ascontiguousarray(valid, np.uint8)
    n = em_mat.shape[0]
    out = np.zeros(n, np.uint8)
    _lib.cap_pss_check_batch(
        em_mat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n, em_mat.shape[1],
        mhash_mat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        mhash_mat.shape[1],
        em_bits.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        bits,
        valid_u8.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n_threads,
    )
    return out.astype(bool)
