"""Batched Ed25519 (EdDSA) verification as JAX/XLA programs.

Replaces crypto/ed25519.Verify — the reference's EdDSA hot loop
(jwt/keyset.go:126-139 → go-jose → Go stdlib) — with TPU-shaped batch
arithmetic over the limb machinery in ``bignum``:

- field arithmetic mod p = 2^255-19 in Montgomery form (16×16-bit
  limbs), batch-last [K, N] like the RSA/ECDSA engines;
- extended twisted-Edwards coordinates with the a = -1 unified
  formulas, which are COMPLETE for edwards25519 (d is non-square,
  -1 is a square mod p) — unlike the Weierstrass ladder in ``ec``,
  there are no degenerate cases and no CPU re-verification;
- [S]B + [k](-A) by interleaved fixed-window recoding (w = 4): all
  d·2^{4i} multiples are precomputed host-side as affine triples
  (B per process, -A per key in the device-resident table), so the
  ladder is 2·64 complete mixed additions with ZERO doublings;
- the verification equation is checked the way Go does it
  (encoding comparison): compute R' = [S]B + [k](-A), normalize to
  affine with one batched Fermat inversion, re-encode, and compare
  the 32-byte encoding against the R half of the signature — which
  automatically rejects non-canonical R encodings;
- k = SHA-512(R ‖ A ‖ M) mod L is computed host-side (variable-length
  messages; hashing is cheap and branchy), S < L is enforced
  on-device (rejects the malleable S+L forgeries, as Go's
  Scalar.SetCanonicalBytes does);
- per-key precomputation: -A and B-A rows in affine triple form
  (y-x, y+x, 2dxy), gathered per token (the key-gather axis,
  SURVEY.md §2.6); keys whose 32 bytes do not decode to a curve
  point always verify False (Go returns false at decode).

Everything is shape-static; one compilation per batch-size bucket.
"""

from __future__ import annotations

import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import limbs as L

# edwards25519 domain parameters (RFC 8032 §5.1).
P = (1 << 255) - 19
L_ORDER = (1 << 252) + 27742317777372353535851937790883648493
D_CONST = (-121665 * pow(121666, -1, P)) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)
K = 16                       # 256 bits of 16-bit limbs
NBITS = 253                  # max bit length of S and k (both < 2^253)
N_WINDOWS = (NBITS + 3) // 4  # 4-bit interleaved-window positions

_BY = 4 * pow(5, -1, P) % P


def decode_point(data: bytes) -> Optional[Tuple[int, int]]:
    """RFC 8032 §5.1.3 point decompression; None if not on the curve."""
    if len(data) != 32:
        return None
    y = int.from_bytes(data, "little")
    sign = y >> 255
    y &= (1 << 255) - 1
    if y >= P:
        return None
    y2 = y * y % P
    u = (y2 - 1) % P
    v = (D_CONST * y2 + 1) % P
    # candidate root x = (u/v)^((p+3)/8) = u·v³·(u·v⁷)^((p-5)/8)
    v3 = v * v % P * v % P
    x = u * v3 % P * pow(u * v3 % P * v3 % P * v % P, (P - 5) // 8, P) % P
    vx2 = v * x % P * x % P
    if vx2 == u:
        pass
    elif vx2 == (-u) % P:
        x = x * SQRT_M1 % P
    else:
        return None
    if x == 0 and sign:
        return None
    if x & 1 != sign:
        x = P - x
    return x, y


def _edw_add(p1: Tuple[int, int], p2: Tuple[int, int]) -> Tuple[int, int]:
    """Host affine Edwards addition (complete; table precompute only)."""
    x1, y1 = p1
    x2, y2 = p2
    dxy = D_CONST * x1 % P * x2 % P * y1 % P * y2 % P
    x3 = (x1 * y2 + y1 * x2) * pow(1 + dxy, -1, P) % P
    y3 = (y1 * y2 + x1 * x2) * pow(1 - dxy, -1, P) % P
    return x3, y3


_B_POINT = decode_point(_BY.to_bytes(32, "little"))  # sign bit 0 → even x
assert _B_POINT is not None

_IDENTITY = (0, 1)


def _window_triple_rows(pt: Tuple[int, int]) -> np.ndarray:
    """4-bit window table of one point as Montgomery affine triples.

    Returns [3, N_WINDOWS·16, K] uint32: row i·16 + d holds the
    (y-x, y+x, 2dxy) triple of d·2^{4i}·pt, with d = 0 the identity
    (the complete formulas absorb identity addends, so the ladder
    needs no skip mask). Low-order pt (adversarial keys) may produce
    identity rows elsewhere too — equally harmless.
    """
    cc = consts()
    rows = np.empty((3, N_WINDOWS * 16, K), np.uint32)
    base = pt
    for i in range(N_WINDOWS):
        acc = _IDENTITY
        for d in range(16):
            if d:
                acc = _edw_add(acc, base)
            for t, v in enumerate(_triple_limbs(acc, cc.pone_int)):
                rows[t, i * 16 + d] = v
        for _ in range(4):
            base = _edw_add(base, base)
    return rows


_B_TABLE = None


def b_table():
    """Cached device window table for the basepoint B: 3× [NW·16, K]."""
    global _B_TABLE
    if _B_TABLE is None:
        rows = _window_triple_rows(_B_POINT)
        _B_TABLE = tuple(jnp.asarray(rows[t]) for t in range(3))
    return _B_TABLE


class _FieldConsts:
    """Cached [K, 1] device constants for the edwards25519 field."""

    def __init__(self):
        from .bignum import mont_params

        pprime, pr2, pone = mont_params(P, K)
        self.pone_int = pone
        host = dict(
            p=L.int_to_limbs(P, K),
            pp=L.int_to_limbs(pprime, K),
            pr2=L.int_to_limbs(pr2, K),
            pone=L.int_to_limbs(pone, K),
            pm2=L.int_to_limbs(P - 2, K),     # Fermat exponent
            l=L.int_to_limbs(L_ORDER, K),
        )
        self.dev = tuple(jnp.asarray(v)[:, None] for v in (
            host["p"], host["pp"], host["pr2"], host["pone"], host["pm2"],
            host["l"]))


def _triple_limbs(pt: Tuple[int, int], r_mod_p: int) -> List[np.ndarray]:
    """Affine point → Montgomery-form (y-x, y+x, 2dxy) limb rows."""
    x, y = pt
    vals = ((y - x) % P, (y + x) % P, 2 * D_CONST * x % P * y % P)
    return [L.int_to_limbs(v * r_mod_p % P, K) for v in vals]


_CONSTS: Optional[_FieldConsts] = None


def consts() -> _FieldConsts:
    global _CONSTS
    if _CONSTS is None:
        _CONSTS = _FieldConsts()
    return _CONSTS


class Ed25519KeyTable:
    """Device-resident table of Ed25519 public keys.

    Per key, the full 4-bit interleaved-window table of -A (d·2^{4i}
    multiples as affine triples (y-x, y+x, 2dxy), field-Montgomery
    form) — the ladder then needs no doublings, only gathers + complete
    mixed adds. Undecodable keys get identity tables and an ``invalid``
    flag (their tokens verify False, matching Go's decode-failure
    behavior).
    """

    def __init__(self, keys: Sequence):
        from cryptography.hazmat.primitives.serialization import (
            Encoding,
            PublicFormat,
        )

        self.keys = list(keys)  # cryptography Ed25519PublicKey
        nk = len(self.keys)
        self.key_bytes: List[bytes] = [
            k.public_bytes(Encoding.Raw, PublicFormat.Raw)
            for k in self.keys]

        rows = N_WINDOWS * 16
        na = np.empty((3, nk * rows, K), np.uint32)
        invalid = np.zeros(nk, bool)
        for i, raw in enumerate(self.key_bytes):
            a = decode_point(raw)
            if a is None:
                invalid[i] = True
                neg_a = _IDENTITY
            else:
                neg_a = ((P - a[0]) % P, a[1])
            na[:, i * rows:(i + 1) * rows] = _window_triple_rows(neg_a)
        self.tna = tuple(jnp.asarray(na[t]) for t in range(3))
        self.invalid = invalid
        self._rns = None

    def rns(self):
        """Lazily-built RNS-form window tables (accelerator path)."""
        if self._rns is None:
            from . import ed25519_rns

            decoded = [decode_point(raw) for raw in self.key_bytes]
            self._rns = ed25519_rns.Ed25519RNSKeyTable(decoded)
        return self._rns


# ---------------------------------------------------------------------------
# Device kernel (all field values in Montgomery form unless noted)
# ---------------------------------------------------------------------------

def _edw_double(X, Y, Z, T, p, pp):
    """Extended-coordinate doubling, a = -1 (dbl-2008-hwcd). 4M+4S."""
    from . import bignum as B

    a = B.mont_mul(X, X, p, pp)
    b = B.mont_mul(Y, Y, p, pp)
    zz = B.mont_mul(Z, Z, p, pp)
    c = B.add_mod(zz, zz, p)
    d = B.sub_mod(jnp.zeros_like(a), a, p)          # a = -1 → D = -X²
    xy = B.add_mod(X, Y, p)
    e = B.sub_mod(B.sub_mod(B.mont_mul(xy, xy, p, pp), a, p), b, p)
    g = B.add_mod(d, b, p)
    f = B.sub_mod(g, c, p)
    h = B.sub_mod(d, b, p)
    return (B.mont_mul(e, f, p, pp), B.mont_mul(g, h, p, pp),
            B.mont_mul(f, g, p, pp), B.mont_mul(e, h, p, pp))


def _edw_madd(X, Y, Z, T, ym, yp, t2, p, pp):
    """Mixed extended + affine-triple addition, a = -1 (madd-2008-hwcd-3).

    7M. COMPLETE for edwards25519 — valid for every input pair,
    including doubling, inverses, and the identity on either side.
    """
    from . import bignum as B

    a = B.mont_mul(B.sub_mod(Y, X, p), ym, p, pp)
    b = B.mont_mul(B.add_mod(Y, X, p), yp, p, pp)
    c = B.mont_mul(T, t2, p, pp)
    d = B.add_mod(Z, Z, p)
    e = B.sub_mod(b, a, p)
    f = B.sub_mod(d, c, p)
    g = B.add_mod(d, c, p)
    h = B.add_mod(b, a, p)
    return (B.mont_mul(e, f, p, pp), B.mont_mul(g, h, p, pp),
            B.mont_mul(f, g, p, pp), B.mont_mul(e, h, p, pp))


@jax.jit
def _ed25519_core(s, kk, yr, sign_r, bad_key, key_idx,
                  ta_ym, ta_yp, ta_t2, tb_ym, tb_yp, tb_t2,
                  p, pp, pr2, pone, pm2, l_):
    """Batched Ed25519 verify core.

    s, kk: [K, N] plain scalar limbs (S half of the signature;
    k = H(R‖A‖M) mod L); N a power of two (batch-inverse tree).
    yr: [K, N] limbs of the R encoding's y value (sign bit cleared);
    sign_r: [N] its sign bit. bad_key: [N] bool. key_idx: [N] int32.
    ta_*: [nk·NW·16, K] per-key window tables of -A; tb_*: [NW·16, K]
    the basepoint window table. Remaining args: [K, 1] field constants
    (broadcast on-device — transferred once, not per batch).
    Returns ok [N].
    """
    from . import bignum as B

    shape = s.shape
    p1, pp1, pr21, pone1, pm21 = p, pp, pr2, pone, pm2
    (p, pp, pone, l_) = (
        jnp.broadcast_to(a, shape) for a in (p, pp, pone, l_))

    # 1. S must be canonical: S < L (Go: Scalar.SetCanonicalBytes).
    s_ok = ~B.compare_ge(s, l_)

    # 2. Interleaved-window ladder: R' = Σ d1_i·(2^{4i}B) +
    #    d2_i·(2^{4i}(-A)). Digit 0 rows hold the identity and the
    #    formulas are complete, so every iteration adds unconditionally.
    k = shape[0]

    def nibbles(u):
        return jnp.stack(
            [(u >> (4 * j)) & 15 for j in range(4)], axis=1
        ).reshape(4 * k, shape[1]).astype(jnp.int32)

    dig1 = nibbles(s)        # [S]B digits
    dig2 = nibbles(kk)       # [k](-A) digits
    key_base = key_idx.astype(jnp.int32) * (N_WINDOWS * 16)

    zeros = jnp.zeros_like(s)
    X0, Y0, Z0, T0 = zeros, pone, pone, zeros

    def add_from_table(pt, tab_ym, tab_yp, tab_t2, idx):
        X, Y, Z, T = pt
        ym = jnp.take(tab_ym, idx, axis=0).T
        yp = jnp.take(tab_yp, idx, axis=0).T
        t2 = jnp.take(tab_t2, idx, axis=0).T
        return _edw_madd(X, Y, Z, T, ym, yp, t2, p, pp)

    def ladder_body(i, carry):
        d1 = lax.dynamic_slice_in_dim(dig1, i, 1, axis=0)[0]
        d2 = lax.dynamic_slice_in_dim(dig2, i, 1, axis=0)[0]
        carry = add_from_table(carry, tb_ym, tb_yp, tb_t2, i * 16 + d1)
        carry = add_from_table(carry, ta_ym, ta_yp, ta_t2,
                               key_base + i * 16 + d2)
        return carry

    X, Y, Z, T = lax.fori_loop(0, N_WINDOWS, ladder_body,
                               (X0, Y0, Z0, T0))

    # 3. Affine normalize: batch product-tree inversion of Z (Z ≠ 0
    #    always — Edwards completeness), then leave the Montgomery
    #    domain and re-encode.
    zinv = B.batch_mont_inverse(Z, p1, pp1, pr21, pone1, pm21, nbits=255)
    one = jnp.zeros_like(s).at[0].set(1)
    x = B.mont_mul(B.mont_mul(X, zinv, p, pp), one, p, pp)
    y = B.mont_mul(B.mont_mul(Y, zinv, p, pp), one, p, pp)

    # 4. Encoding comparison (Go: bytes.Equal(R, R'.Bytes())): the y
    #    limbs must match R's y field exactly and x's parity must match
    #    R's sign bit. Non-canonical yr (≥ p) can never equal y < p.
    enc_ok = jnp.all(y == yr, axis=0) & ((x[0] & 1) == sign_r)

    return s_ok & enc_ok & ~bad_key


# ---------------------------------------------------------------------------
# Host interface
# ---------------------------------------------------------------------------

def _le_bytes_to_limbs(mat: np.ndarray) -> np.ndarray:
    """[N, 32] little-endian byte rows → [K, N] limb-first array."""
    lo = mat[:, 0::2].astype(np.uint32)
    hi = mat[:, 1::2].astype(np.uint32)
    return (lo | (hi << 8)).T.copy()


def verify_ed25519_batch_pending(table: Ed25519KeyTable,
                                 sigs: Sequence[bytes],
                                 msgs: Sequence[bytes],
                                 key_idx: np.ndarray):
    """Dispatch the EdDSA device work; return a finalize() → [N] bool.

    sigs: raw 64-byte JOSE signatures (R ‖ S); msgs: signing inputs;
    key_idx: [N] table rows. k = SHA-512(R ‖ A ‖ M) mod L is computed
    here (host), everything else on device, asynchronously.
    """
    n_tok = len(sigs)
    len_ok = np.fromiter((len(sg) == 64 for sg in sigs), bool, n_tok)

    sig_mat = np.zeros((n_tok, 64), np.uint8)
    k_ints: List[int] = []
    for j, sg in enumerate(sigs):
        if len_ok[j]:
            sig_mat[j] = np.frombuffer(sg, np.uint8)
            h = hashlib.sha512(
                sg[:32] + table.key_bytes[int(key_idx[j])] + msgs[j]
            ).digest()
            k_ints.append(int.from_bytes(h, "little") % L_ORDER)
        else:
            k_ints.append(0)

    s_limbs = _le_bytes_to_limbs(sig_mat[:, 32:])
    r_mat = sig_mat[:, :32].copy()
    sign_r = (r_mat[:, 31] >> 7).astype(np.uint32)
    r_mat[:, 31] &= 0x7F
    yr_limbs = _le_bytes_to_limbs(r_mat)
    k_limbs = L.ints_to_limbs(k_ints, K)
    key_rows = np.asarray(key_idx, np.int32)
    bad = table.invalid[key_rows]

    # Pad the batch to a power of two ≥ 128 for the inverse tree /
    # bucket-shape stability. Padding rows compute on key row 0 and are
    # discarded below.
    n_pad = 128
    while n_pad < n_tok:
        n_pad *= 2
    if n_pad != n_tok:
        fill = n_pad - n_tok
        s_limbs = np.pad(s_limbs, ((0, 0), (0, fill)))
        k_limbs = np.pad(k_limbs, ((0, 0), (0, fill)))
        yr_limbs = np.pad(yr_limbs, ((0, 0), (0, fill)))
        sign_r = np.pad(sign_r, (0, fill))
        key_rows = np.pad(key_rows, (0, fill))
        bad = np.pad(bad, (0, fill))

    from .rns import use_rns

    if use_rns():
        from . import ed25519_rns

        rtab = table.rns()
        ok_dev = ed25519_rns._ed25519_rns_core(
            jnp.asarray(s_limbs), jnp.asarray(k_limbs),
            jnp.asarray(yr_limbs), jnp.asarray(sign_r), jnp.asarray(bad),
            jnp.asarray(key_rows),
            *rtab.tna, *ed25519_rns.b_table_rns(),
            *consts().dev)
    else:
        ok_dev = _ed25519_core(
            jnp.asarray(s_limbs), jnp.asarray(k_limbs),
            jnp.asarray(yr_limbs), jnp.asarray(sign_r), jnp.asarray(bad),
            jnp.asarray(key_rows),
            *table.tna, *b_table(),
            *consts().dev)
    return lambda: np.asarray(ok_dev)[:n_tok] & len_ok


def verify_ed25519_batch(table: Ed25519KeyTable, sigs: Sequence[bytes],
                         msgs: Sequence[bytes],
                         key_idx: np.ndarray) -> np.ndarray:
    """[N] bool verdicts for one EdDSA bucket (synchronous wrapper)."""
    return verify_ed25519_batch_pending(table, sigs, msgs, key_idx)()


# ---------------------------------------------------------------------------
# Packed single-transfer dispatch (see rsa.py's packed section)
# ---------------------------------------------------------------------------

ED_REC_EXTRA = 2          # trailing bytes per record: flags, key row


def ed_packed_records(table: Ed25519KeyTable, sigs: Sequence[bytes],
                      msgs: Sequence[bytes],
                      key_idx: np.ndarray) -> np.ndarray:
    """Host: packed [N, 64 + 32 + 2] u8 records for one EdDSA chunk.

    Row layout: signature R‖S (64) ‖ k = SHA-512(R‖A‖M) mod L as 32
    little-endian bytes ‖ validity flag u8 (length ok AND key decodes)
    ‖ key row u8. The k hash is inherently host-side (variable-length
    message); everything downstream of it runs on device.
    """
    n = len(sigs)
    rec = np.zeros((n, 64 + 32 + ED_REC_EXTRA), np.uint8)
    chunks: List[bytes] = []
    live: List[int] = []
    for j, sg in enumerate(sigs):
        row = int(key_idx[j])
        rec[j, 97] = row
        if len(sg) == 64:
            rec[j, :64] = np.frombuffer(sg, np.uint8)
            rec[j, 96] = not table.invalid[row]
            chunks.append(sg[:32] + table.key_bytes[row] + msgs[j])
            live.append(j)
    if not live:
        return rec
    # k = SHA-512(R ‖ A ‖ M): multithreaded C++ when built
    digests = _sha512_batch(chunks)
    for j, h in zip(live, digests):
        kk = int.from_bytes(h, "little") % L_ORDER
        rec[j, 64:96] = np.frombuffer(kk.to_bytes(32, "little"),
                                      np.uint8)
    return rec


def _sha512_batch(chunks: Sequence[bytes]) -> List[bytes]:
    from ..runtime import prep

    native = prep._load_native()
    if native is not None:
        return native.sha_batch(chunks, 512)
    return [hashlib.sha512(c).digest() for c in chunks]


def _le_bytes_to_limbs_dev(mat):
    """Device: [N, 2K] u8 little-endian → [K, N] u32 limbs."""
    m = mat.astype(jnp.uint32)
    return (m[:, 0::2] | (m[:, 1::2] << 8)).T


def _ed_packed_unpack(packed):
    sig = packed[:, :64]
    flags = packed[:, 96] != 0
    idx = packed[:, 97].astype(jnp.int32)
    sign_r = (sig[:, 31] >> 7).astype(jnp.uint32)
    r_clr = sig[:, :32].at[:, 31].set(sig[:, 31] & 0x7F)
    yr = _le_bytes_to_limbs_dev(r_clr)
    s = _le_bytes_to_limbs_dev(sig[:, 32:64])
    kk = _le_bytes_to_limbs_dev(packed[:, 64:96])
    bad = jnp.zeros(packed.shape[0], bool)   # folded into flags on host
    return s, kk, yr, sign_r, bad, idx, flags


def _ed_packed_rns_impl(packed, ta, tb, cdev):
    from . import ed25519_rns

    s, kk, yr, sign_r, bad, idx, flags = _ed_packed_unpack(packed)
    p, pp, pr2, pone, pm2, l_ = cdev
    ok = ed25519_rns._ed25519_rns_core(
        s, kk, yr, sign_r, bad, idx, *ta, *tb, p, pp, pr2, pone, pm2, l_)
    return ok & flags


def _ed_packed_limb_impl(packed, ta, tb, cdev):
    s, kk, yr, sign_r, bad, idx, flags = _ed_packed_unpack(packed)
    p, pp, pr2, pone, pm2, l_ = cdev
    ok = _ed25519_core(
        s, kk, yr, sign_r, bad, idx, *ta, *tb, p, pp, pr2, pone, pm2, l_)
    return ok & flags


_ed_packed_jits: Dict[str, object] = {}


def _ed_packed_jit(name: str, impl):
    fn = _ed_packed_jits.get(name)
    if fn is None:
        fn = jax.jit(impl)
        _ed_packed_jits[name] = fn
    return fn


def verify_ed_packed_pending(table: Ed25519KeyTable, rec: np.ndarray,
                             mesh=None):
    """Dispatch one packed EdDSA chunk; returns the device [N] bool.

    With a mesh the record shards along the batch axis and each device
    verifies its own rows; tables replicate (SURVEY.md §2.6).
    """
    from .rns import use_rns

    if use_rns():
        from . import ed25519_rns

        name, impl = "rns", _ed_packed_rns_impl
        tables = (tuple(table.rns().tna), tuple(ed25519_rns.b_table_rns()),
                  tuple(consts().dev))
    else:
        name, impl = "limb", _ed_packed_limb_impl
        tables = (tuple(table.tna), tuple(b_table()), tuple(consts().dev))
    if mesh is not None:
        from ..parallel.place import run_batch_sharded

        return run_batch_sharded(impl, mesh, rec, tables, {})
    return _ed_packed_jit(name, impl)(jax.device_put(rec), *tables)
