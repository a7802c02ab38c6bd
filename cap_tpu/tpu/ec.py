"""Batched ECDSA verification (P-256/P-384/P-521) as JAX/XLA programs.

Replaces crypto/ecdsa.Verify — the reference's ES* hot loop
(jwt/keyset.go:126-139 → go-jose → Go stdlib) — with TPU-shaped batch
arithmetic over the limb machinery in ``bignum``:

- per-curve Montgomery constants for BOTH the field (mod p) and the
  scalar group (mod n), broadcast across the batch;
- w = s⁻¹ mod n via Montgomery's simultaneous-inversion product tree
  (``bignum.batch_mont_inverse``): ~3 multiplies per token instead of
  a 2·nbits-multiply Fermat ladder per token;
- u1·G + u2·Q by interleaved fixed-window recoding (w = 4): scalars
  split into 4-bit digits d_i, and the sum becomes
  Σ d1_i·(2^{4i}G) + Σ d2_i·(2^{4i}Q) — every 2^{4i}-multiple is
  PRECOMPUTED host-side (G per curve; Q per key, into the
  device-resident key table — the key-gather axis, SURVEY.md §2.6),
  so the device ladder is just 2·⌈nbits/4⌉ mixed additions with
  per-token table gathers and ZERO doublings;
- mixed Jacobian/affine addition — complete for the inputs the ladder
  produces, EXCEPT the same-x exceptional cases (addend ==
  ±accumulator), which are flagged per token and re-verified on the
  CPU oracle (unreachable for honest signatures, adversarially
  constructible — parity must hold there too);
- the final check is projective: accept iff X ≡ r·Z² or, when
  r + n < p, X ≡ (r+n)·Z² (mod p) — no field inversion anywhere.

Everything is shape-static; one compilation per (curve, batch-size)
bucket.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import limbs as L


def ladder_mode() -> str:
    """Window-add law for the ES* ladders: ``jacobian`` (default) or
    ``affine``.

    ``affine`` replaces the 11-mul mixed Jacobian/affine window madd
    with a 2M+1S affine add whose per-lane division is amortized by ONE
    batched product-tree inversion mod p across all lanes per window
    step (the round-5 verdict's A/B ask). Selectable per keyset
    (``TPUBatchKeySet(ec_ladder=...)``) or globally via
    ``CAP_TPU_EC_LADDER=affine``; docs/PERF.md records the measured
    A/B and why the default stays Jacobian.
    """
    v = os.environ.get("CAP_TPU_EC_LADDER", "").strip().lower()
    return "affine" if v == "affine" else "jacobian"


def resolve_ladder(ladder: Optional[str]) -> str:
    if ladder is None:
        return ladder_mode()
    if ladder not in ("jacobian", "affine"):
        raise ValueError(f"unknown EC ladder mode {ladder!r}")
    return ladder

# NIST curve domain parameters (FIPS 186-4 / SEC 2).
_CURVE_INTS = {
    "P-256": dict(
        p=0xFFFFFFFF00000001000000000000000000000000FFFFFFFFFFFFFFFFFFFFFFFF,
        n=0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551,
        gx=0x6B17D1F2E12C4247F8BCE6E563A440F277037D812DEB33A0F4A13945D898C296,
        gy=0x4FE342E2FE1A7F9B8EE7EB4A7C0F9E162BCE33576B315ECECBB6406837BF51F5,
        coord_bytes=32,
    ),
    "P-384": dict(
        p=(1 << 384) - (1 << 128) - (1 << 96) + (1 << 32) - 1,
        n=0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFC7634D81F4372DDF581A0DB248B0A77AECEC196ACCC52973,  # noqa: E501
        gx=0xAA87CA22BE8B05378EB1C71EF320AD746E1D3B628BA79B9859F741E082542A385502F25DBF55296C3A545E3872760AB7,  # noqa: E501
        gy=0x3617DE4A96262C6F5D9E98BF9292DC29F8F41DBD289A147CE9DA3113B5F0B8C00A60B1CE1D7E819D7A431D7C90EA0E5F,  # noqa: E501
        coord_bytes=48,
    ),
    "P-521": dict(
        p=(1 << 521) - 1,
        n=int("01fffffffffffffffffffffffffffffffffffffffffffffffffffffff"
              "ffffffffffa51868783bf2f966b7fcc0148f709a5d03bb5c9b8899c47"
              "aebb6fb71e91386409", 16),
        gx=0x00C6858E06B70404E9CD9E3ECB662395B4429C648139053FB521F828AF606B4D3DBAA14B5E77EFE75928FE1DC127A2FFA8DE3348B3C1856A429BF97E7E31C2E5BD66,  # noqa: E501
        gy=0x011839296A789A3BC0045C8A5FB42C7D1BD998F54449579B446817AFBD17273E662C97EE72995EF42640C550B9013FAD0761353C7086A272C24088BE94769FD16650,  # noqa: E501
        coord_bytes=66,
    ),
}


class CurveParams:
    """Host-side per-curve constants (ints + packed limb arrays)."""

    def __init__(self, name: str):
        from .bignum import mont_params

        c = _CURVE_INTS[name]
        self.name = name
        self.p: int = c["p"]
        self.n: int = c["n"]
        self.gx: int = c["gx"]
        self.gy: int = c["gy"]
        self.coord_bytes: int = c["coord_bytes"]
        self.nbits: int = self.n.bit_length()
        self.k: int = L.nlimbs_for_bits(self.p.bit_length())

        k = self.k
        self.p_limbs = L.int_to_limbs(self.p, k)
        self.n_limbs = L.int_to_limbs(self.n, k)
        pprime, pr2, pone = mont_params(self.p, k)
        nprime, nr2, none_ = mont_params(self.n, k)
        self.pprime_limbs = L.int_to_limbs(pprime, k)
        self.pr2_limbs = L.int_to_limbs(pr2, k)
        self.pone_limbs = L.int_to_limbs(pone, k)
        self.nprime_limbs = L.int_to_limbs(nprime, k)
        self.nr2_limbs = L.int_to_limbs(nr2, k)
        self.none_limbs = L.int_to_limbs(none_, k)
        self.nm2_limbs = L.int_to_limbs(self.n - 2, k)   # Fermat exponent
        # Field-side Fermat exponent p−2: the affine ladder's batched
        # inversion tree inverts its root mod p (the Jacobian ladder
        # never inverts in the field).
        self.pbits: int = self.p.bit_length()
        self.pm2_limbs = L.int_to_limbs(self.p - 2, k)
        # G in field-Montgomery form.
        r_mod_p = pone
        self.gx_m = L.int_to_limbs(self.gx * r_mod_p % self.p, k)
        self.gy_m = L.int_to_limbs(self.gy * r_mod_p % self.p, k)
        # 4-bit interleaved-window recoding: ⌈nbits/4⌉ digit positions.
        self.n_windows = (self.nbits + 3) // 4
        self._dev_consts = None
        self._g_tables = None

    def device_consts(self):
        """Cached [K, 1] device arrays of every broadcast curve constant
        (transferred once per curve, broadcast on-device in the core)."""
        if self._dev_consts is None:
            self._dev_consts = tuple(
                jnp.asarray(v)[:, None] for v in (
                    self.p_limbs, self.pprime_limbs, self.pr2_limbs,
                    self.pone_limbs, self.n_limbs, self.nprime_limbs,
                    self.nr2_limbs, self.none_limbs, self.nm2_limbs,
                    self.gx_m, self.gy_m, self.pm2_limbs))
        return self._dev_consts

    # -- host affine arithmetic (table precompute only) -------------------

    def affine_add(self, P: Optional[Tuple[int, int]],
                   Q: Optional[Tuple[int, int]]) -> Optional[Tuple[int, int]]:
        p = self.p
        if P is None:
            return Q
        if Q is None:
            return P
        x1, y1 = P
        x2, y2 = Q
        if x1 == x2:
            if (y1 + y2) % p == 0:
                return None
            lam = (3 * x1 * x1 - 3) * pow(2 * y1, -1, p) % p
        else:
            lam = (y2 - y1) * pow(x2 - x1, -1, p) % p
        x3 = (lam * lam - x1 - x2) % p
        y3 = (lam * (x1 - x3) - y1) % p
        return x3, y3

    def window_rows(self, point: Tuple[int, int]):
        """Host precompute of the 4-bit window table for one point.

        Returns (rows_x, rows_y): [n_windows·15, K] uint32 limb rows in
        field-Montgomery form; row i·15 + (d−1) holds d·2^{4i}·point.
        Never hits infinity: the point has prime order n and
        d·2^{4i} < 16·2^nbits is never ≡ 0 (mod n) for d ∈ [1, 15].
        """
        r_mod_p = L.limbs_to_int(self.pone_limbs)
        nw, k = self.n_windows, self.k
        rows_x = np.empty((nw * 15, k), np.uint32)
        rows_y = np.empty((nw * 15, k), np.uint32)
        base = point
        for i in range(nw):
            acc = None
            for d in range(1, 16):
                acc = self.affine_add(acc, base)
                x, y = acc
                rows_x[i * 15 + d - 1] = L.int_to_limbs(
                    x * r_mod_p % self.p, k)
                rows_y[i * 15 + d - 1] = L.int_to_limbs(
                    y * r_mod_p % self.p, k)
            for _ in range(4):
                base = self.affine_add(base, base)
        return rows_x, rows_y

    def g_tables(self):
        """Cached device window table for the fixed base point G."""
        if self._g_tables is None:
            gx_rows, gy_rows = self.window_rows((self.gx, self.gy))
            self._g_tables = (jnp.asarray(gx_rows), jnp.asarray(gy_rows))
        return self._g_tables

    # -- fast window-table precompute (Jacobian + one batched inverse) ----

    def window_multiples(self, point: Tuple[int, int], w_bits: int,
                         n_windows: int) -> Tuple[list, list]:
        """All d·2^{w·i}·point (d ∈ [1, 2^w−1], i ∈ [0, n_windows)) as
        affine int lists, row order i·(2^w−1) + (d−1).

        The naive per-row affine chain costs one modular inversion per
        point; here the chain runs in Jacobian coordinates (no
        inversions) and ONE batched Montgomery-trick inversion converts
        every row to affine — the difference between seconds and
        minutes for the 12-bit tables (2^12−1 rows × 22 windows/key).
        Never hits infinity: d·2^{w·i} < 2^{w·n_windows + w} is never
        ≡ 0 mod n for the prime-order base points used here.
        """
        p = self.p
        per = (1 << w_bits) - 1
        rows = n_windows * per
        JX = [0] * rows
        JY = [0] * rows
        JZ = [0] * rows
        bx, by = point

        def jdouble(X1, Y1, Z1):
            # dbl-2001-b (a = -3)
            delta = Z1 * Z1 % p
            gamma = Y1 * Y1 % p
            beta = X1 * gamma % p
            alpha = 3 * (X1 - delta) * (X1 + delta) % p
            X3 = (alpha * alpha - 8 * beta) % p
            Z3 = ((Y1 + Z1) ** 2 - gamma - delta) % p
            Y3 = (alpha * (4 * beta - X3) - 8 * gamma * gamma) % p
            return X3, Y3, Z3

        def jmadd(X1, Y1, Z1, x2, y2):
            # madd-2004-hmv (Z2 = 1); caller guarantees the points are
            # distinct and nonzero, so h ≠ 0.
            z1z1 = Z1 * Z1 % p
            u2 = x2 * z1z1 % p
            s2 = y2 * Z1 % p * z1z1 % p
            h = (u2 - X1) % p
            hh = h * h % p
            i4 = 4 * hh % p
            j = h * i4 % p
            r = 2 * (s2 - Y1) % p
            v = X1 * i4 % p
            X3 = (r * r - j - 2 * v) % p
            Y3 = (r * (v - X3) - 2 * Y1 * j) % p
            Z3 = ((Z1 + h) ** 2 - z1z1 - hh) % p
            return X3, Y3, Z3

        for i in range(n_windows):
            base_row = i * per
            # d = 1: the (affine) base itself
            JX[base_row], JY[base_row], JZ[base_row] = bx, by, 1
            if per > 1:
                X, Y, Z = jdouble(bx, by, 1)         # d = 2
                JX[base_row + 1], JY[base_row + 1], JZ[base_row + 1] = \
                    X, Y, Z
                for d in range(3, per + 1):
                    X, Y, Z = jmadd(X, Y, Z, bx, by)
                    r = base_row + d - 1
                    JX[r], JY[r], JZ[r] = X, Y, Z
            # advance base by 2^w for the next window
            BX, BY, BZ = bx, by, 1
            for _ in range(w_bits):
                BX, BY, BZ = jdouble(BX, BY, BZ)
            zi = pow(BZ, -1, p)
            zi2 = zi * zi % p
            bx, by = BX * zi2 % p, BY * zi2 % p * zi % p

        # One batched inversion of all Z (Montgomery's trick).
        pref = [1] * (rows + 1)
        for r in range(rows):
            pref[r + 1] = pref[r] * JZ[r] % p
        inv = pow(pref[rows], -1, p)
        X_out = [0] * rows
        Y_out = [0] * rows
        for r in range(rows - 1, -1, -1):
            zi = pref[r] * inv % p       # = JZ[r]^-1
            inv = inv * JZ[r] % p
            zi2 = zi * zi % p
            X_out[r] = JX[r] * zi2 % p
            Y_out[r] = JY[r] * zi2 % p * zi % p
        return X_out, Y_out


_CURVES_CACHE: Dict[str, CurveParams] = {}


def curve(name: str) -> CurveParams:
    if name not in _CURVES_CACHE:
        _CURVES_CACHE[name] = CurveParams(name)
    return _CURVES_CACHE[name]


class ECKeyTable:
    """Device-resident table of EC public keys for one curve.

    Per key, the full 4-bit interleaved-window table (d·2^{4i}·Q for
    d ∈ [1,15], i ∈ [0, n_windows)) in affine field-Montgomery form —
    the scalar-mult ladder then needs no doublings at all, only gathers
    + mixed adds (the key-gather axis, SURVEY.md §2.6).
    """

    def __init__(self, crv: str, keys: Sequence):
        self.curve = curve(crv)
        self.keys = list(keys)  # cryptography EllipticCurvePublicKey
        self.coord_bytes = self.curve.coord_bytes
        cp = self.curve
        k = cp.k
        nk = len(self.keys)
        rows = cp.n_windows * 15
        qx_rows = np.empty((nk * rows, k), np.uint32)
        qy_rows = np.empty((nk * rows, k), np.uint32)
        for i, key in enumerate(self.keys):
            nums = key.public_numbers()
            rx, ry = cp.window_rows((nums.x, nums.y))
            qx_rows[i * rows:(i + 1) * rows] = rx
            qy_rows[i * rows:(i + 1) * rows] = ry
        self.tqx = jnp.asarray(qx_rows)
        self.tqy = jnp.asarray(qy_rows)
        self._rns = None

    def rns(self):
        """Lazily-built RNS-form window tables (accelerator path)."""
        if self._rns is None:
            from . import ec_rns

            self._rns = ec_rns.ECRNSKeyTable(self.curve.name, self.keys)
        return self._rns


# ---------------------------------------------------------------------------
# Device kernels (all values in field-Montgomery form unless noted)
# ---------------------------------------------------------------------------

def _jac_double(X, Y, Z, p, pp):
    """Jacobian doubling, a = -3 (all NIST curves). 8 field muls.

    Safe at infinity (Z=0 → Z3=0) and for Y=0 (absent on prime-order
    curves).
    """
    from . import bignum as B

    delta = B.mont_mul(Z, Z, p, pp)
    gamma = B.mont_mul(Y, Y, p, pp)
    beta = B.mont_mul(X, gamma, p, pp)
    t1 = B.sub_mod(X, delta, p)
    t2 = B.add_mod(X, delta, p)
    t3 = B.mont_mul(t1, t2, p, pp)
    alpha = B.add_mod(B.add_mod(t3, t3, p), t3, p)
    beta4 = B.add_mod(B.add_mod(beta, beta, p), B.add_mod(beta, beta, p), p)
    beta8 = B.add_mod(beta4, beta4, p)
    X3 = B.sub_mod(B.mont_mul(alpha, alpha, p, pp), beta8, p)
    yz = B.add_mod(Y, Z, p)
    Z3 = B.sub_mod(B.sub_mod(B.mont_mul(yz, yz, p, pp), gamma, p), delta, p)
    g2 = B.mont_mul(gamma, gamma, p, pp)
    g8 = B.add_mod(B.add_mod(g2, g2, p), B.add_mod(g2, g2, p), p)
    g8 = B.add_mod(g8, g8, p)
    Y3 = B.sub_mod(
        B.mont_mul(alpha, B.sub_mod(beta4, X3, p), p, pp), g8, p)
    return X3, Y3, Z3


def _jac_madd(X1, Y1, Z1, x2, y2, p, pp, one_m):
    """Mixed Jacobian + affine addition. 11 field muls.

    Returns (X3, Y3, Z3, degenerate): the exceptional same-x cases
    (P == ±(x2, y2)) are NOT computed — they set ``degenerate`` so the
    caller can re-verify those tokens on the CPU oracle. P at infinity
    is handled (returns the affine addend).
    """
    from . import bignum as B

    z1z1 = B.mont_mul(Z1, Z1, p, pp)
    u2 = B.mont_mul(x2, z1z1, p, pp)
    z1_3 = B.mont_mul(Z1, z1z1, p, pp)
    s2 = B.mont_mul(y2, z1_3, p, pp)
    h = B.sub_mod(u2, X1, p)
    hh = B.mont_mul(h, h, p, pp)
    i4 = B.add_mod(B.add_mod(hh, hh, p), B.add_mod(hh, hh, p), p)
    j = B.mont_mul(h, i4, p, pp)
    s2y1 = B.sub_mod(s2, Y1, p)
    rr = B.add_mod(s2y1, s2y1, p)
    v = B.mont_mul(X1, i4, p, pp)
    r2_ = B.mont_mul(rr, rr, p, pp)
    X3 = B.sub_mod(B.sub_mod(r2_, j, p), B.add_mod(v, v, p), p)
    y1j = B.mont_mul(Y1, j, p, pp)
    Y3 = B.sub_mod(
        B.mont_mul(rr, B.sub_mod(v, X3, p), p, pp),
        B.add_mod(y1j, y1j, p),
        p,
    )
    zh = B.add_mod(Z1, h, p)
    Z3 = B.sub_mod(B.sub_mod(B.mont_mul(zh, zh, p, pp), z1z1, p), hh, p)

    p_inf = B.is_zero(Z1)
    eq_x = B.is_zero(h)
    degenerate = ~p_inf & eq_x  # both the double case and the ±inverse case

    sel = p_inf[None, :]
    X3 = jnp.where(sel, x2, X3)
    Y3 = jnp.where(sel, y2, Y3)
    Z3 = jnp.where(sel, one_m, Z3)
    return X3, Y3, Z3, degenerate


def _affine_madd(x, y, inf, ax, ay, has, p, pp, one_m,
                 p1, pp1, pr2_1, pone1, pm2_1, pbits: int):
    """Batched affine + affine addition, 2M + 1S + one batched inverse.

    x, y: [K, M] affine accumulator (field-Montgomery form, canonical);
    inf: [M] explicit infinity lane; ax, ay: gathered table points
    (never at infinity); has: [M] lanes that add this step (digit > 0).
    The per-lane division λ = (ay−y)/(ax−x) is ONE product-tree
    inversion mod p across all M lanes (``bignum.batch_mont_inverse``
    with the field constants p1..pm2_1 [K, 1]), so the per-lane
    multiply count is 3 + ~3 tree multiplies instead of the Jacobian
    madd's 11. The exceptional cases the complete Jacobian law absorbs
    are explicit here:

    - infinity accumulator → masked select of the addend (lift);
    - doubling (P == Q) and inverse (P == −Q), both x(P) == x(ax) →
      flagged ``degenerate`` (the caller re-verifies on the CPU
      oracle, the same contract as ``_jac_madd``), with the zero
      denominator replaced by 1 so the inversion tree stays
      invertible.

    Returns (x3, y3, inf3, degenerate).
    """
    from . import bignum as B

    dx = B.sub_mod(ax, x, p)
    eqx = B.is_zero(dx)
    live = has & ~inf
    degenerate = live & eqx
    den = jnp.where((live & ~eqx)[None, :], dx, one_m)
    inv = B.batch_mont_inverse(den, p1, pp1, pr2_1, pone1, pm2_1,
                               nbits=pbits)
    dy = B.sub_mod(ay, y, p)
    lam = B.mont_mul(dy, inv, p, pp)
    sq = B.mont_mul(lam, lam, p, pp)
    x3 = B.sub_mod(B.sub_mod(sq, x, p), ax, p)
    y3 = B.sub_mod(B.mont_mul(lam, B.sub_mod(x, x3, p), p, pp), y, p)

    lift = (inf & has)[None, :]
    x3 = jnp.where(lift, ax, x3)
    y3 = jnp.where(lift, ay, y3)
    sel = has[None, :]
    return (jnp.where(sel, x3, x), jnp.where(sel, y3, y),
            inf & ~has, degenerate)


@partial(jax.jit, static_argnames=("nbits", "n_windows", "pbits",
                                   "ladder"))
def _ecdsa_core(r, s, e, key_idx, tqx, tqy, tgx, tgy,
                p, pp, pr2, pone, n, npp, nr2, none_, nm2, gx, gy, pm2,
                nbits: int, n_windows: int, pbits: int = 0,
                ladder: str = "jacobian"):
    """Batched ECDSA verify core.

    r, s, e: [K, N] plain limb values (signature halves, hash int);
    N must be a power of two (the batch-inverse tree pairs it down).
    key_idx: [N] int32 rows into the per-key window tables
    tqx/tqy: [nk·n_windows·15, K]; tgx/tgy: [n_windows·15, K] for G.
    Remaining args: [K, 1] curve constants (broadcast on-device here —
    transferred once per curve, not per batch).

    ``ladder`` selects the window-add law: ``jacobian`` (the complete
    mixed madd, interleaved G/Q chains in one accumulator) or
    ``affine`` (two lane-concatenated affine chains, one batched
    product-tree inversion mod p per window step — see
    :func:`ladder_mode`). Verdicts are bit-exact across both (the
    affine parity suite pins it).

    Returns (ok [N], degenerate [N]).
    """
    from . import bignum as B

    k = r.shape[0]
    shape = r.shape
    n1, npp1, nr21, none1, nm21 = n, npp, nr2, none_, nm2
    p1, pp1, pr2_1, pone1, pm2_1 = p, pp, pr2, pone, pm2
    (p, pp, pr2, pone, n, npp, nr2) = (
        jnp.broadcast_to(a, shape)
        for a in (p, pp, pr2, pone, n, npp, nr2))

    # 1. Range checks: 1 <= r, s < n.
    r_ok = ~B.is_zero(r) & ~B.compare_ge(r, n)
    s_ok = ~B.is_zero(s) & ~B.compare_ge(s, n)

    # 2. w = s⁻¹ mod n via the batch product-tree inverse (Montgomery
    #    domain). Invalid s (0 or ≥ n) is replaced by 1 so the tree
    #    stays invertible; those tokens are rejected by s_ok anyway.
    one_plain = jnp.zeros_like(r).at[0].set(1)
    s_safe = jnp.where(s_ok[None, :], s, one_plain)
    s_m = B.mont_mul(s_safe, nr2, n, npp)
    w_m = B.batch_mont_inverse(s_m, n1, npp1, nr21, none1, nm21,
                               nbits=nbits)

    # 3. u1 = e·w mod n, u2 = r·w mod n (plain limb values: montmul of a
    #    plain operand with a Montgomery operand cancels the R factor).
    u1 = B.mont_mul(e, w_m, n, npp)
    u2 = B.mont_mul(r, w_m, n, npp)

    # 4. Interleaved-window ladder: R = Σ d1_i·(2^{4i}G) + d2_i·(2^{4i}Q).
    #    4-bit digits, little-endian across limbs (LIMB_BITS = 16 → 4
    #    nibbles per limb); no doublings — all multiples precomputed.
    def nibbles(u):
        return jnp.stack(
            [(u >> (4 * j)) & 15 for j in range(4)], axis=1
        ).reshape(4 * k, shape[1]).astype(jnp.int32)

    dig1 = nibbles(u1)
    dig2 = nibbles(u2)
    key_base = key_idx.astype(jnp.int32) * (n_windows * 15)

    if ladder == "affine":
        return _ecdsa_affine_tail(
            r, r_ok, s_ok, dig1, dig2, key_base, tqx, tqy, tgx, tgy,
            p, pp, pr2, pone, n,
            p1, pp1, pr2_1, pone1, pm2_1,
            k=k, n_windows=n_windows, pbits=pbits)

    zeros = jnp.zeros_like(r)
    X0, Y0, Z0 = pone, pone, zeros          # point at infinity (Z = 0)
    deg0 = jnp.zeros(r.shape[1], dtype=bool)

    def add_from_table(carry, tab_x, tab_y, d, row0):
        X, Y, Z, deg = carry
        has = d > 0
        idx = row0 + jnp.where(has, d - 1, 0)
        ax = jnp.take(tab_x, idx, axis=0).T      # [K, N]
        ay = jnp.take(tab_y, idx, axis=0).T
        Xa, Ya, Za, dd = _jac_madd(X, Y, Z, ax, ay, p, pp, pone)
        sel = has[None, :]
        return (jnp.where(sel, Xa, X), jnp.where(sel, Ya, Y),
                jnp.where(sel, Za, Z), deg | (dd & has))

    def ladder_body(i, carry):
        d1 = lax.dynamic_slice_in_dim(dig1, i, 1, axis=0)[0]
        d2 = lax.dynamic_slice_in_dim(dig2, i, 1, axis=0)[0]
        carry = add_from_table(carry, tgx, tgy, d1, i * 15)
        carry = add_from_table(carry, tqx, tqy, d2, key_base + i * 15)
        return carry

    X, Y, Z, deg = lax.fori_loop(0, n_windows, ladder_body,
                                 (X0, Y0, Z0, deg0))

    not_inf = ~B.is_zero(Z)

    # 5. Projective check: X == r·Z² or X == (r+n)·Z² (mod p).
    z2 = B.mont_mul(Z, Z, p, pp)
    r_pm = B.mont_mul(r, pr2, p, pp)        # r < n < p → valid lift
    rhs1 = B.mont_mul(r_pm, z2, p, pp)
    ok1 = jnp.all(X == rhs1, axis=0)

    zero_row = jnp.zeros_like(r[:1])
    rpn = B.carry_normalize(jnp.concatenate([r + n, zero_row], axis=0))
    p_pad = jnp.concatenate([p, zero_row], axis=0)
    rpn_lt_p = ~B.compare_ge(rpn, p_pad)
    rpn_k = rpn[:k]                         # < p when rpn_lt_p
    rpn_pm = B.mont_mul(rpn_k, pr2, p, pp)
    rhs2 = B.mont_mul(rpn_pm, z2, p, pp)
    ok2 = jnp.all(X == rhs2, axis=0) & rpn_lt_p

    ok = r_ok & s_ok & not_inf & (ok1 | ok2)
    return ok, deg & r_ok & s_ok


def _ecdsa_affine_tail(r, r_ok, s_ok, dig1, dig2, key_base,
                       tqx, tqy, tgx, tgy,
                       p, pp, pr2, pone, n,
                       p1, pp1, pr2_1, pone1, pm2_1,
                       k: int, n_windows: int, pbits: int):
    """Affine-ladder tail of the limb-engine verify core.

    The G-digit and Q-digit chains run as TWO lane-concatenated affine
    accumulators ([K, 2N] state), so each window step is ONE affine add
    whose divisions amortize into a single batched product-tree
    inversion over all 2N lanes; the chains merge with one more affine
    add (one inversion over N lanes) and the final check is a direct
    field compare x == r·R mod p — no Z coordinate anywhere.

    Separate chains also shrink the degenerate surface: a single
    prefix-sum chain of one scalar u < n can never hit its own window
    multiple (every partial sum and addend are distinct multiples
    d·P with 0 < d < n of a prime-order point), so in-ladder ``deg``
    flags are adversarially unreachable and only the MERGE can
    degenerate (u1·G == ±u2·Q) — still flagged and CPU-re-verified,
    same contract as the Jacobian path.
    """
    from . import bignum as B

    n_tok = r.shape[1]
    shape2 = (k, 2 * n_tok)
    p2, pp2, pone2 = (jnp.broadcast_to(a, shape2)
                      for a in (p1, pp1, pone1))

    tab_x = jnp.concatenate([tgx, tqx], axis=0)
    tab_y = jnp.concatenate([tgy, tqy], axis=0)
    g_rows = tgx.shape[0]

    x0 = jnp.broadcast_to(pone1, shape2)
    inf0 = jnp.ones(2 * n_tok, dtype=bool)
    deg0 = jnp.zeros(2 * n_tok, dtype=bool)

    def ladder_body(i, carry):
        x, y, inf, deg = carry
        d1 = lax.dynamic_slice_in_dim(dig1, i, 1, axis=0)[0]
        d2 = lax.dynamic_slice_in_dim(dig2, i, 1, axis=0)[0]
        d = jnp.concatenate([d1, d2])
        row0 = jnp.concatenate(
            [jnp.zeros((n_tok,), jnp.int32) + i * 15,
             g_rows + key_base + i * 15])
        has = d > 0
        idx = row0 + jnp.where(has, d - 1, 0)
        ax = jnp.take(tab_x, idx, axis=0).T
        ay = jnp.take(tab_y, idx, axis=0).T
        x, y, inf, dd = _affine_madd(
            x, y, inf, ax, ay, has, p2, pp2, pone2,
            p1, pp1, pr2_1, pone1, pm2_1, pbits)
        return x, y, inf, deg | dd

    x, y, inf, deg2 = lax.fori_loop(0, n_windows, ladder_body,
                                    (x0, x0, inf0, deg0))

    xg, yg = x[:, :n_tok], y[:, :n_tok]
    xq, yq = x[:, n_tok:], y[:, n_tok:]
    inf_g, inf_q = inf[:n_tok], inf[n_tok:]
    deg = deg2[:n_tok] | deg2[n_tok:]

    # Merge: one more affine add with (xq, yq) as the addend; lanes
    # whose addend is at infinity pass the G accumulator through.
    xm, ym, inf_m, ddm = _affine_madd(
        xg, yg, inf_g, xq, yq, ~inf_q, p, pp, pone,
        p1, pp1, pr2_1, pone1, pm2_1, pbits)
    deg = deg | ddm
    not_inf = ~inf_m

    # Affine final check: x == r·R or (r+n)·R (mod p), both canonical.
    r_pm = B.mont_mul(r, pr2, p, pp)
    ok1 = jnp.all(xm == r_pm, axis=0)

    zero_row = jnp.zeros_like(r[:1])
    rpn = B.carry_normalize(jnp.concatenate([r + n, zero_row], axis=0))
    p_pad = jnp.concatenate([p, zero_row], axis=0)
    rpn_lt_p = ~B.compare_ge(rpn, p_pad)
    rpn_pm = B.mont_mul(rpn[:k], pr2, p, pp)
    ok2 = jnp.all(xm == rpn_pm, axis=0) & rpn_lt_p

    ok = r_ok & s_ok & not_inf & (ok1 | ok2)
    return ok, deg & r_ok & s_ok


@partial(jax.jit, static_argnames=("k",))
def _ec_prep(sig_bytes, dig, k: int):
    """Device: raw signature/digest bytes → (r, s, e) limb arrays.

    sig_bytes: [N, 2·cb] u8 (r ‖ s halves, each cb = 2k bytes wide);
    dig: [N, hlen] u8. e is the hash as an integer, left-zero-padded
    (hlen ≤ 2k for every supported alg/curve pairing).
    """
    cb = sig_bytes.shape[1] // 2
    r = L.bytes_to_limbs_device(sig_bytes[:, :cb])
    s = L.bytes_to_limbs_device(sig_bytes[:, cb:])
    hlen = dig.shape[1]
    e_mat = jnp.zeros((dig.shape[0], 2 * k), jnp.uint8)
    e_mat = e_mat.at[:, 2 * k - hlen:].set(dig)
    e = L.bytes_to_limbs_device(e_mat)
    return r, s, e


def verify_ecdsa_arrays_pending(table: ECKeyTable, sig_mat: np.ndarray,
                                sig_lens: np.ndarray,
                                hash_mat: np.ndarray, hash_len: int,
                                key_idx: np.ndarray,
                                ladder: Optional[str] = None):
    """Dispatch the ES* device work; return a finalize() → [N] bool.

    Asynchronous dispatch (see verify_pkcs1v15_arrays_pending);
    degenerate-flagged tokens are re-verified on the CPU oracle inside
    finalize, preserving bit-exact parity. ``ladder`` selects the
    window-add law (None → :func:`ladder_mode`).
    """
    ladder = resolve_ladder(ladder)
    cp = table.curve
    k = cp.k
    cb = cp.coord_bytes
    n_tok = sig_mat.shape[0]

    len_ok = sig_lens == 2 * cb
    safe = np.where(len_ok[:, None], sig_mat[:, : 2 * cb], 0)

    # Pad the batch to a power of two ≥ 128: the inverse tree pairs the
    # batch down, and pow-2 buckets bound XLA recompilation. Padding
    # rows have r = s = 0 → forced invalid, discarded below. Only raw
    # bytes cross the wire; limb conversion happens on device.
    n_pad = 128
    while n_pad < n_tok:
        n_pad *= 2
    dig = hash_mat[:, :hash_len]
    if n_pad != n_tok:
        fill = n_pad - n_tok
        safe = np.pad(safe, ((0, fill), (0, 0)))
        dig = np.pad(dig, ((0, fill), (0, 0)))
        key_idx = np.pad(np.asarray(key_idx, np.int32), (0, fill))

    r_limbs, s_limbs, e_limbs = _ec_prep(
        jnp.asarray(safe), jnp.asarray(np.ascontiguousarray(dig)), k=k)

    from .rns import use_rns

    if use_rns():
        # RNS/MXU point arithmetic (carry-free ladder); scalar math
        # stays in the limb engine inside the same jit.
        from . import ec_rns

        rtab = table.rns()
        consts = cp.device_consts()
        ok_dev, deg_dev = ec_rns._ecdsa_rns_core(
            r_limbs, s_limbs, e_limbs,
            jnp.asarray(key_idx, jnp.int32),
            rtab.tab,
            *consts[4:9],
            crv=cp.name, nbits=cp.nbits, wbits=rtab.ctx.w_bits,
            ladder=ladder,
        )
    else:
        ok_dev, deg_dev = _ecdsa_core(
            r_limbs, s_limbs, e_limbs,
            jnp.asarray(key_idx, jnp.int32),
            table.tqx, table.tqy, *cp.g_tables(),
            *cp.device_consts(),
            nbits=cp.nbits, n_windows=cp.n_windows,
            pbits=cp.pbits, ladder=ladder,
        )

    def finalize() -> np.ndarray:
        ok = np.asarray(ok_dev)[:n_tok] & len_ok
        deg = np.asarray(deg_dev)[:n_tok]
        for j in np.nonzero(deg & len_ok)[0]:
            ok[j] = _cpu_verify_one(table, int(key_idx[j]),
                                    sig_mat[j, : 2 * cb].tobytes(),
                                    hash_mat[j, :hash_len].tobytes())
        return ok

    return finalize


def verify_ecdsa_arrays(table: ECKeyTable, sig_mat: np.ndarray,
                        sig_lens: np.ndarray, hash_mat: np.ndarray,
                        hash_len: int,
                        key_idx: np.ndarray,
                        ladder: Optional[str] = None) -> np.ndarray:
    """Array-native ES* verify: [N] bool verdicts.

    sig_mat: [N, W] left-aligned JOSE raw signatures (r ‖ s, fixed
    width 2·coord_bytes); sig_lens: [N]; hash_mat: [N, ≥hash_len]
    digests; key_idx: [N] table rows. Degenerate-flagged tokens are
    re-verified on the CPU oracle for bit-exact parity.
    """
    return verify_ecdsa_arrays_pending(table, sig_mat, sig_lens,
                                       hash_mat, hash_len, key_idx,
                                       ladder=ladder)()


def _cpu_verify_one(table: ECKeyTable, row: int, sig_raw: bytes,
                    digest: bytes) -> bool:
    """CPU oracle for one (degenerate-flagged) token."""
    if not hasattr(table.keys[row], "verify"):
        # HostECPublicKey tables (no OpenSSL object behind the row)
        return _py_verify_one(table, int(row), sig_raw, digest)
    try:
        from cryptography.exceptions import InvalidSignature
        from cryptography.hazmat.primitives import hashes
        from cryptography.hazmat.primitives.asymmetric import ec as cec
        from cryptography.hazmat.primitives.asymmetric.utils import (
            Prehashed,
            encode_dss_signature,
        )
    except ImportError:
        # No OpenSSL stack in this environment: fall back to the exact
        # host-integer ECDSA oracle below (same verdicts — SEC1 §4.1.4
        # over the curve's own affine arithmetic).
        return _py_verify_one(table, int(row), sig_raw, digest)

    cb = table.curve.coord_bytes
    r = int.from_bytes(sig_raw[:cb], "big")
    s = int.from_bytes(sig_raw[cb:], "big")
    halg = {32: hashes.SHA256, 48: hashes.SHA384, 64: hashes.SHA512}[
        len(digest)]
    try:
        table.keys[row].verify(encode_dss_signature(r, s), digest,
                               cec.ECDSA(Prehashed(halg())))
        return True
    except (InvalidSignature, ValueError):
        return False


def scalar_mult(cp: CurveParams, k: int,
                P: Optional[Tuple[int, int]]) -> Optional[Tuple[int, int]]:
    """Host double-and-add k·P over the curve's affine arithmetic."""
    acc = None
    add = P
    while k:
        if k & 1:
            acc = cp.affine_add(acc, add)
        add = cp.affine_add(add, add)
        k >>= 1
    return acc


class HostECPublicKey:
    """Dependency-free EC public key for device-table construction.

    ``ECKeyTable`` only reads ``public_numbers().x/.y``; this provides
    exactly that surface from host integers, so tables (and the
    pure-integer oracle above) work where the ``cryptography`` package
    is unavailable. Not a drop-in for the OpenSSL-backed key anywhere
    else — the CPU trial-verify paths still require the real stack.
    """

    class _Numbers:
        def __init__(self, x: int, y: int):
            self.x, self.y = x, y

    def __init__(self, crv: str, x: int, y: int):
        self.curve_name = crv
        self._nums = self._Numbers(x, y)

    def public_numbers(self):
        return self._nums

    @classmethod
    def from_private(cls, crv: str, d: int) -> "HostECPublicKey":
        cp = curve(crv)
        qx, qy = scalar_mult(cp, d, (cp.gx, cp.gy))
        return cls(crv, qx, qy)


def host_ecdsa_sign(crv: str, d: int, e: int, k: int) -> Tuple[int, int]:
    """Textbook ECDSA signing over host ints (test/bench fixtures only
    — k must be unique per signature; nothing here is constant-time).
    Returns (r, s); raises if the chosen k yields r == 0 or s == 0.
    """
    cp = curve(crv)
    R = scalar_mult(cp, k, (cp.gx, cp.gy))
    r = R[0] % cp.n
    s = pow(k, -1, cp.n) * (e + r * d) % cp.n
    if r == 0 or s == 0:
        raise ValueError("degenerate nonce; pick another k")
    return r, s


def py_ecdsa_verify(cp: CurveParams, qx: int, qy: int, sig_raw: bytes,
                    digest: bytes) -> bool:
    """Pure-integer ECDSA verify (SEC1 §4.1.4), dependency-free.

    Same acceptance rule as Go crypto/ecdsa and OpenSSL — range checks
    1 <= r, s < n, left-bits hash truncation, accept iff
    (u1·G + u2·Q).x ≡ r (mod n). The oracle behind both the
    degenerate-lane re-verification and the crypto-less
    ``HostECPublicKey`` verify path in jwt/verify.py.
    """
    cb = cp.coord_bytes
    r = int.from_bytes(sig_raw[:cb], "big")
    s = int.from_bytes(sig_raw[cb:], "big")
    if not (1 <= r < cp.n and 1 <= s < cp.n):
        return False
    e = int.from_bytes(digest, "big")
    excess = 8 * len(digest) - cp.nbits
    if excess > 0:
        e >>= excess
    w = pow(s, -1, cp.n)
    u1 = (e * w) % cp.n
    u2 = (r * w) % cp.n
    R = cp.affine_add(scalar_mult(cp, u1, (cp.gx, cp.gy)),
                      scalar_mult(cp, u2, (qx, qy)))
    if R is None:
        return False
    return R[0] % cp.n == r


def _py_verify_one(table: ECKeyTable, row: int, sig_raw: bytes,
                   digest: bytes) -> bool:
    """Table-row wrapper over :func:`py_ecdsa_verify` (the oracle of
    last resort when the ``cryptography`` package is absent)."""
    nums = table.keys[row].public_numbers()
    return py_ecdsa_verify(table.curve, nums.x, nums.y, sig_raw, digest)


def verify_ecdsa_batch(table: ECKeyTable, sigs: Sequence[bytes],
                       msg_hashes: Sequence[bytes],
                       key_idx: np.ndarray,
                       ladder: Optional[str] = None) -> np.ndarray:
    """[N] bool verdicts for one ES* bucket (list-of-bytes interface)."""
    cb = table.curve.coord_bytes
    n_tok = len(sigs)
    w = 2 * cb
    sig_mat = np.zeros((n_tok, w), np.uint8)
    sig_lens = np.empty(n_tok, np.int64)
    for j, sg in enumerate(sigs):
        sig_lens[j] = len(sg)
        if len(sg) == w:
            sig_mat[j] = np.frombuffer(sg, np.uint8)
    hash_len = len(msg_hashes[0]) if msg_hashes else 32
    hash_mat = np.zeros((n_tok, hash_len), np.uint8)
    for j, h in enumerate(msg_hashes):
        hash_mat[j] = np.frombuffer(h[:hash_len], np.uint8)
    return verify_ecdsa_arrays(table, sig_mat, sig_lens, hash_mat,
                               hash_len, key_idx, ladder=ladder)


# ---------------------------------------------------------------------------
# Packed single-transfer dispatch (see rsa.py's packed section: one u8
# record matrix per chunk, one jitted program, sync deferred to the
# batch-wide wave)
# ---------------------------------------------------------------------------

ES_REC_EXTRA = 2          # trailing bytes per record: flags, key row


def es_packed_records(table: ECKeyTable, sig_mat: np.ndarray,
                      sig_lens: np.ndarray, hash_mat: np.ndarray,
                      hash_len: int, key_idx: np.ndarray) -> np.ndarray:
    """Host: packed [N, 2·cb + hash_len + 2] u8 records for one ES* chunk.

    Row layout: signature r‖s bytes (2·cb) ‖ digest (hash_len) ‖
    validity flag u8 ‖ key row u8.
    """
    cb = table.curve.coord_bytes
    len_ok = (sig_lens == 2 * cb).astype(np.uint8)
    safe = np.where(len_ok[:, None] != 0, sig_mat[:, :2 * cb], 0)
    rec = np.empty((sig_mat.shape[0], 2 * cb + hash_len + ES_REC_EXTRA),
                   np.uint8)
    rec[:, :2 * cb] = safe
    rec[:, 2 * cb:2 * cb + hash_len] = hash_mat[:, :hash_len]
    rec[:, 2 * cb + hash_len] = len_ok
    rec[:, 2 * cb + hash_len + 1] = key_idx.astype(np.uint8)
    return rec


def _es_packed_rns_impl(packed, tab, consts, *, crv: str,
                        nbits: int, wbits: int, k: int, cb: int,
                        hlen: int, ladder: str = "jacobian"):
    from . import ec_rns

    sig = packed[:, :2 * cb]
    dig = packed[:, 2 * cb:2 * cb + hlen]
    flags = packed[:, 2 * cb + hlen] != 0
    idx = packed[:, 2 * cb + hlen + 1].astype(jnp.int32)
    r, s, e = _ec_prep(sig, dig, k=k)
    ok, deg = ec_rns._ecdsa_rns_core(r, s, e, idx, tab,
                                     *consts, crv=crv, nbits=nbits,
                                     wbits=wbits, ladder=ladder)
    return ok & flags, deg & flags


def _es_packed_limb_impl(packed, tqx, tqy, g_tabs, consts, *, nbits: int,
                         n_windows: int, k: int, cb: int, hlen: int,
                         pbits: int = 0, ladder: str = "jacobian"):
    sig = packed[:, :2 * cb]
    dig = packed[:, 2 * cb:2 * cb + hlen]
    flags = packed[:, 2 * cb + hlen] != 0
    idx = packed[:, 2 * cb + hlen + 1].astype(jnp.int32)
    r, s, e = _ec_prep(sig, dig, k=k)
    ok, deg = _ecdsa_core(r, s, e, idx, tqx, tqy, *g_tabs, *consts,
                          nbits=nbits, n_windows=n_windows,
                          pbits=pbits, ladder=ladder)
    return ok & flags, deg & flags


_es_packed_jits: Dict[str, object] = {}


def _es_packed_jit(name: str, impl, static_names):
    fn = _es_packed_jits.get(name)
    if fn is None:
        fn = jax.jit(impl, static_argnames=static_names)
        _es_packed_jits[name] = fn
    return fn


def verify_es_packed_pending(table: ECKeyTable, rec: np.ndarray,
                             hash_len: int, mesh=None,
                             ladder: Optional[str] = None):
    """Dispatch one packed ES* chunk; returns device ([N] ok, [N] deg).

    Degenerate-flagged tokens (deg True) must be re-verified on the CPU
    oracle by the caller after the sync wave — same contract as
    verify_ecdsa_arrays_pending. With a mesh the record shards along
    the batch axis and each device verifies its own rows; tables
    replicate (SURVEY.md §2.6). ``ladder``
    selects the window-add law (None → :func:`ladder_mode`).
    """
    ladder = resolve_ladder(ladder)
    cp = table.curve
    from .rns import use_rns

    if use_rns():
        rtab = table.rns()
        name, impl = "rns", _es_packed_rns_impl
        tables = (rtab.tab, tuple(cp.device_consts()[4:9]))
        static = dict(crv=cp.name, nbits=cp.nbits, wbits=rtab.ctx.w_bits,
                      k=cp.k, cb=cp.coord_bytes, hlen=hash_len,
                      ladder=ladder)
    else:
        name, impl = "limb", _es_packed_limb_impl
        tables = (table.tqx, table.tqy, tuple(cp.g_tables()),
                  tuple(cp.device_consts()))
        static = dict(nbits=cp.nbits, n_windows=cp.n_windows, k=cp.k,
                      cb=cp.coord_bytes, hlen=hash_len, pbits=cp.pbits,
                      ladder=ladder)
    if mesh is not None:
        from ..parallel.place import run_batch_sharded

        return run_batch_sharded(impl, mesh, rec, tables, static)
    fn = _es_packed_jit(name, impl, tuple(static))
    return fn(jax.device_put(rec), *tables, **static)
