"""Batched RSA signature verification on TPU.

Replaces crypto/rsa.VerifyPKCS1v15 / VerifyPSS (the reference's hot
loop, jwt/keyset.go:126-139 → go-jose → Go stdlib) with:

- a device-resident key table (moduli + Montgomery constants as limb
  arrays) built once per KeySet/JWKS — the "key-gather parallelism"
  axis from SURVEY.md §2.6: per-token kid indices gather rows;
- one batched modexp over the whole bucket (fast path e=65537, generic
  ladder otherwise);
- PKCS#1 v1.5: the full expected encoded message EM is constructed
  host-side with vectorized numpy (variable per-token key sizes
  supported — mixed 2048/4096 JWKS), compared on device, only a [N]
  bool mask returns to host;
- PSS: modexp on device, EM returned to host, MGF1/salt check per
  token (hashlib; the C++ runtime batches this later).

Bit-exact parity contract: a token verifies here iff it verifies on the
CPU oracle — including rejections (wrong length, s >= n, bad padding,
wrong hash).
"""

from __future__ import annotations

import hashlib
from typing import List, Optional, Sequence

import numpy as np

from . import limbs as L
from . import program_name

# Stable names of the packed programs (the jitted functions below are
# named by them): a profile's module names contain them, so a reader of
# per-family device time finds its program after any refactor.
PROGRAM = "rs_packed"        # RS*: PKCS#1 v1.5
PS_PROGRAM = "ps_packed"     # PS*: EMSA-PSS

# ASN.1 DigestInfo prefixes (RFC 8017 §9.2 notes).
DIGEST_INFO_PREFIX = {
    "sha256": bytes.fromhex("3031300d060960864801650304020105000420"),
    "sha384": bytes.fromhex("3041300d060960864801650304020205000430"),
    "sha512": bytes.fromhex("3051300d060960864801650304020305000440"),
}
HASH_LEN = {"sha256": 32, "sha384": 48, "sha512": 64}


from .limbs import bytes_to_limbs_device


def _expected_em_device(dig, sizes, k: int, hash_name: str):
    """Device construction of the PKCS#1 v1.5 expected EM limbs.

    dig: [N, hlen] u8 digests; sizes: [N] i32 per-token emLen. Builds
    EM = 00 01 FF.. 00 DigestInfo ‖ H right-aligned in [N, 2k] bytes —
    entirely on device, so only the digest crosses the wire.
    """
    import jax.numpy as jnp

    prefix = DIGEST_INFO_PREFIX[hash_name]
    h_len = HASH_LEN[hash_name]
    t_len = len(prefix) + h_len
    width = 2 * k
    n = dig.shape[0]
    cols = jnp.arange(width, dtype=jnp.int32)[None, :]
    start = (width - sizes.astype(jnp.int32))[:, None]
    val = jnp.zeros((n, width), jnp.uint8)
    val = jnp.where(cols == start + 1, jnp.uint8(1), val)
    val = jnp.where((cols >= start + 2) & (cols < width - t_len - 1),
                    jnp.uint8(0xFF), val)
    pref = jnp.asarray(np.frombuffer(prefix, np.uint8))
    val = val.at[:, width - t_len: width - h_len].set(pref[None, :])
    val = val.at[:, width - h_len:].set(dig)
    return bytes_to_limbs_device(val)


def _use_rns() -> bool:
    from .rns import use_rns

    return use_rns()


class RSAKeyTable:
    """Device-resident table of RSA public keys in Montgomery form.

    All keys are padded to a common limb count K (Montgomery with
    R = 2^(16K) works for any n < R), so one compiled modexp serves a
    mixed-size JWKS.
    """

    def __init__(self, public_numbers: Sequence, k: Optional[int] = None):
        """public_numbers: list of (n_int, e_int)."""
        import jax.numpy as jnp

        self.n_ints = [n for n, _ in public_numbers]
        self.e_ints = [e for _, e in public_numbers]
        self.sizes_bytes = [(n.bit_length() + 7) // 8 for n in self.n_ints]
        need = L.nlimbs_for_bits(max(n.bit_length() for n in self.n_ints))
        # One spare limb beyond the modulus width → R ≥ 2^16·n ≥ 4n, the
        # precondition for the subtraction-free Montgomery chain.
        self.k = k if k is not None else max(need + 1, 8)
        if self.k <= need:
            raise ValueError("k too small for lazy Montgomery headroom")

        nk = len(self.n_ints)
        n_tab = np.empty((nk, self.k), np.uint32)
        np_tab = np.empty((nk, self.k), np.uint32)
        r2_tab = np.empty((nk, self.k), np.uint32)
        one_tab = np.empty((nk, self.k), np.uint32)
        from .bignum import mont_params

        for i, n in enumerate(self.n_ints):
            nprime, r2, one_m = mont_params(n, self.k)
            n_tab[i] = L.int_to_limbs(n, self.k)
            np_tab[i] = L.int_to_limbs(nprime, self.k)
            r2_tab[i] = L.int_to_limbs(r2, self.k)
            one_tab[i] = L.int_to_limbs(one_m, self.k)
        # Rows gathered per token then transposed to limb-first on device.
        self.n_tab = jnp.asarray(n_tab)
        self.np_tab = jnp.asarray(np_tab)
        self.r2_tab = jnp.asarray(r2_tab)
        self.one_tab = jnp.asarray(one_tab)
        self.e_arr = np.asarray(self.e_ints, np.uint32)
        self.all_f4 = all(e == 65537 for e in self.e_ints)
        self.max_ebits = max(e.bit_length() for e in self.e_ints)
        # Device-resident per-key scalars for the packed in-jit gathers.
        self.sizes_dev = jnp.asarray(self.sizes_bytes, jnp.int32)
        self.e_dev = jnp.asarray(self.e_arr)
        self.mod_bits_dev = jnp.asarray(
            [n.bit_length() for n in self.n_ints], jnp.int32)
        self._rns = None

    def rns(self):
        """Lazily-built RNS engine (ctx + per-key table); e=65537 only.

        Context bit-width rounds up to a 256-bit grid so mixed-size
        JWKS reuse cached contexts.
        """
        if self._rns is None:
            from . import rns as rns_mod

            nbits = max(n.bit_length() for n in self.n_ints)
            nbits = ((nbits + 255) // 256) * 256
            try:
                ctx = rns_mod.context(nbits, self.k)
                self._rns = (ctx, rns_mod.RNSKeyTable(ctx, self.n_ints))
            except rns_mod.RNSUnsupportedKey:
                self._rns = (None, None)   # degenerate key → limb path
        return self._rns


def _gather_limb_first(tab, idx):
    """[nk, K] table + [N] indices → [K, N] device array."""
    return tab[idx].T


def modexp_for_table(table: RSAKeyTable, s_limbs, key_idx: np.ndarray):
    """Batched s^e mod n for tokens hitting ``table``; returns [K, N] EM limbs.

    s_limbs: [K, N] numpy/jax signature integers; key_idx: [N] int32.
    """
    import jax.numpy as jnp

    from . import bignum

    idx = jnp.asarray(key_idx, jnp.int32)
    s = jnp.asarray(s_limbs)
    n = _gather_limb_first(table.n_tab, idx)
    nprime = _gather_limb_first(table.np_tab, idx)
    r2 = _gather_limb_first(table.r2_tab, idx)
    if table.all_f4:
        return bignum.modexp_65537(s, n, nprime, r2)
    one_m = _gather_limb_first(table.one_tab, idx)
    e = jnp.asarray(table.e_arr, jnp.uint32)[idx]
    return bignum.modexp_vare(s, e, n, nprime, r2, one_m,
                              ebits=table.max_ebits)


def s_in_range_mask(table: RSAKeyTable, s_limbs, key_idx: np.ndarray):
    """[N] bool: signature integer s < n (RFC 8017 step 1 range check)."""
    import jax.numpy as jnp

    from . import bignum

    idx = jnp.asarray(key_idx, jnp.int32)
    n = _gather_limb_first(table.n_tab, idx)
    s = jnp.asarray(s_limbs)
    return ~bignum.compare_ge(s, n)


def expected_pkcs1v15_em(hashes_: Sequence[bytes], hash_name: str,
                         em_lens: np.ndarray, k: int) -> np.ndarray:
    """Vectorized construction of the expected PKCS#1 v1.5 EM per token.

    EM = 0x00 0x01 [0xFF × (emLen − tLen − 3)] 0x00 DigestInfo ‖ H,
    right-aligned in a [N, 2k]-byte matrix → [k, N] limb array.
    """
    n = len(hashes_)
    width = 2 * k
    prefix = DIGEST_INFO_PREFIX[hash_name]
    h_len = HASH_LEN[hash_name]
    t_len = len(prefix) + h_len
    buf = np.zeros((n, width), np.uint8)
    cols = np.arange(width)[None, :]
    starts = width - em_lens[:, None]            # first EM byte per token
    ff_lo = starts + 2
    ff_hi = width - t_len - 1                    # exclusive of 0x00 separator
    buf[(cols >= ff_lo) & (cols < ff_hi)] = 0xFF
    rows = np.arange(n)
    buf[rows, (starts[:, 0] + 1)] = 0x01
    buf[:, width - t_len - 1] = 0x00
    tail = np.frombuffer(prefix, np.uint8)[None, :].repeat(n, 0)
    buf[:, width - t_len: width - h_len] = tail
    hmat = np.zeros((n, h_len), np.uint8)
    for j, h in enumerate(hashes_):
        hmat[j] = np.frombuffer(h, np.uint8)
    buf[:, width - h_len:] = hmat
    hi = buf[:, 0::2].astype(np.uint32)
    lo = buf[:, 1::2].astype(np.uint32)
    limbs_be = (hi << 8) | lo
    return limbs_be[:, ::-1].T.copy()            # [k, N] little-endian


def expected_pkcs1v15_em_mat(hash_mat: np.ndarray, hash_name: str,
                             em_lens: np.ndarray, k: int) -> np.ndarray:
    """Like expected_pkcs1v15_em but takes a [N, hlen] digest matrix."""
    n = hash_mat.shape[0]
    width = 2 * k
    prefix = DIGEST_INFO_PREFIX[hash_name]
    h_len = HASH_LEN[hash_name]
    t_len = len(prefix) + h_len
    buf = np.zeros((n, width), np.uint8)
    cols = np.arange(width)[None, :]
    starts = width - em_lens[:, None]
    ff_lo = starts + 2
    ff_hi = width - t_len - 1
    buf[(cols >= ff_lo) & (cols < ff_hi)] = 0xFF
    buf[np.arange(n), (starts[:, 0] + 1)] = 0x01
    buf[:, width - t_len - 1] = 0x00
    buf[:, width - t_len: width - h_len] = np.frombuffer(prefix, np.uint8)
    buf[:, width - h_len:] = hash_mat[:, :h_len]
    hi = buf[:, 0::2].astype(np.uint32)
    lo = buf[:, 1::2].astype(np.uint32)
    return ((hi << 8) | lo)[:, ::-1].T.copy()


def verify_pkcs1v15_arrays_pending(table: RSAKeyTable, sig_mat: np.ndarray,
                                   sig_lens: np.ndarray,
                                   hash_mat: np.ndarray, hash_name: str,
                                   key_idx: np.ndarray):
    """Dispatch the RS* device work; return a finalize() → [N] bool.

    Dispatch is asynchronous — callers can launch every bucket's device
    program before the first materializing sync (one ~RTT to the
    accelerator instead of one per bucket).
    """
    import jax.numpy as jnp

    from . import bignum  # noqa: F401

    sizes = np.asarray(table.sizes_bytes, np.int64)[key_idx]
    len_ok = sig_lens == sizes
    em_len_ok = sizes >= len(DIGEST_INFO_PREFIX[hash_name]) + \
        HASH_LEN[hash_name] + 11
    host_mask = len_ok & em_len_ok
    # Wire-minimal H2D: raw right-aligned signature bytes + digests +
    # per-token sizes; limb conversion and expected-EM construction run
    # on device (_rs_prep).
    safe_lens = np.where(len_ok, sig_lens, 0)
    aligned = L.right_align_bytes(
        np.where(len_ok[:, None], sig_mat, 0), safe_lens, 2 * table.k)
    h_len = HASH_LEN[hash_name]
    dig = np.ascontiguousarray(hash_mat[:, :h_len])
    s_limbs, expected = _rs_prep(
        jnp.asarray(aligned), jnp.asarray(dig),
        jnp.asarray(sizes, jnp.int32), k=table.k, hash_name=hash_name)
    in_range = s_in_range_mask(table, s_limbs, key_idx)
    if table.all_f4 and _use_rns():
        # MXU path: modexp + EM compare entirely in RNS form.
        from . import rns as rns_mod

        ctx, rtab = table.rns()
        if ctx is not None:
            eq = rns_mod.verify_em_equals_device(
                ctx, rtab, s_limbs, expected, key_idx)
            return lambda: np.asarray(eq & in_range) & host_mask
    em = modexp_for_table(table, s_limbs, key_idx)
    eq = jnp.all(em == expected, axis=0) & in_range
    return lambda: np.asarray(eq) & host_mask


def _rs_prep_impl(sig_bytes, dig, sizes, k: int, hash_name: str):
    return (bytes_to_limbs_device(sig_bytes),
            _expected_em_device(dig, sizes, k, hash_name))


_rs_prep_cache: dict = {}


def _rs_prep(sig_bytes, dig, sizes, k: int, hash_name: str):
    """Jitted device prep: sig bytes → limbs, digest → expected EM."""
    import jax

    key = "rs_prep"
    fn = _rs_prep_cache.get(key)
    if fn is None:
        fn = jax.jit(_rs_prep_impl, static_argnames=("k", "hash_name"))
        _rs_prep_cache[key] = fn
    return fn(sig_bytes, dig, sizes, k=k, hash_name=hash_name)


def verify_pkcs1v15_arrays(table: RSAKeyTable, sig_mat: np.ndarray,
                           sig_lens: np.ndarray, hash_mat: np.ndarray,
                           hash_name: str,
                           key_idx: np.ndarray) -> np.ndarray:
    """Array-native RS* verify: [N] bool verdicts, no per-token Python.

    sig_mat: [N, W] left-aligned signature bytes; sig_lens: [N];
    hash_mat: [N, ≥hlen] digests; key_idx: [N] table rows.
    """
    return verify_pkcs1v15_arrays_pending(
        table, sig_mat, sig_lens, hash_mat, hash_name, key_idx)()


def _limbs_to_bytes_impl(limbs):
    """Device: [K, N] u32 16-bit limbs → [N, 2K] u8 big-endian bytes."""
    import jax.numpy as jnp

    be = limbs.T[:, ::-1]
    hi = (be >> 8).astype(jnp.uint8)
    lo = (be & 0xFF).astype(jnp.uint8)
    return jnp.stack([hi, lo], axis=2).reshape(be.shape[0], -1)


_limbs_to_bytes_jit = None


def _limbs_to_bytes_dev(limbs):
    global _limbs_to_bytes_jit
    if _limbs_to_bytes_jit is None:
        import jax

        _limbs_to_bytes_jit = jax.jit(_limbs_to_bytes_impl)
    return _limbs_to_bytes_jit(limbs)


def verify_pss_arrays_pending(table: RSAKeyTable, sig_mat: np.ndarray,
                              sig_lens: np.ndarray, hash_mat: np.ndarray,
                              hash_name: str, key_idx: np.ndarray):
    """Dispatch the PS* modexp; finalize() runs the host EM/MGF1 check."""
    import jax.numpy as jnp

    n_tok = sig_mat.shape[0]
    sizes = np.asarray(table.sizes_bytes, np.int64)[key_idx]
    mod_bits = np.asarray([n.bit_length() for n in table.n_ints])[key_idx]
    len_ok = sig_lens == sizes
    safe_lens = np.where(len_ok, sig_lens, 0)
    aligned = L.right_align_bytes(
        np.where(len_ok[:, None], sig_mat, 0), safe_lens, 2 * table.k)
    s_limbs = bytes_to_limbs_device(jnp.asarray(aligned))
    if table.all_f4 and _use_rns():
        from . import rns as rns_mod

        ctx, rtab = table.rns()
        if ctx is not None:
            idx = jnp.asarray(key_idx, jnp.int32)
            n_gath = table.n_tab[idx].T
            em_dev = rns_mod.modexp_em_device(ctx, rtab, s_limbs,
                                              key_idx, n_gath)
        else:
            em_dev = modexp_for_table(table, s_limbs, key_idx)
    else:
        em_dev = modexp_for_table(table, s_limbs, key_idx)
    in_range_dev = s_in_range_mask(table, s_limbs, key_idx)
    # D2H diet: ship the EM back as [N, 2k] u8 BYTES (packed on device)
    # instead of [K, N] u32 limbs — half the wire bytes on the return
    # path, which dominates the PS* configs.
    em_bytes_dev = _limbs_to_bytes_dev(em_dev)

    def finalize() -> np.ndarray:
        in_range = np.asarray(in_range_dev)
        valid = len_ok & in_range
        em_mat = np.asarray(em_bytes_dev)
        h_len = HASH_LEN[hash_name]

        from ..runtime import prep

        native = prep._load_native()
        if native is not None:
            ok = native.pss_check_batch(
                em_mat, hash_mat[:, :h_len], mod_bits - 1,
                8 * h_len, valid)
            if ok is not None:
                return ok
        out = np.zeros(n_tok, bool)
        for j in range(n_tok):
            if not valid[j]:
                continue
            out[j] = pss_check_em(em_mat[j].tobytes(),
                                  hash_mat[j, :h_len].tobytes(),
                                  int(mod_bits[j]) - 1, hash_name)
        return out

    return finalize


def verify_pss_arrays(table: RSAKeyTable, sig_mat: np.ndarray,
                      sig_lens: np.ndarray, hash_mat: np.ndarray,
                      hash_name: str, key_idx: np.ndarray) -> np.ndarray:
    """Array-native PS* verify: device modexp, host EM/MGF1 check."""
    return verify_pss_arrays_pending(table, sig_mat, sig_lens, hash_mat,
                                     hash_name, key_idx)()


def verify_pkcs1v15_batch(table: RSAKeyTable, sigs: Sequence[bytes],
                          msg_hashes: Sequence[bytes], hash_name: str,
                          key_idx: np.ndarray) -> np.ndarray:
    """[N] bool verdicts for one RS* bucket. Tokens whose signature length
    doesn't match their key size fail without touching the device."""
    import jax.numpy as jnp

    from . import bignum  # noqa: F401  (jit caches live there)

    n_tok = len(sigs)
    sizes = np.asarray([table.sizes_bytes[i] for i in key_idx])
    len_ok = np.asarray([len(s) for s in sigs]) == sizes
    em_len_ok = sizes >= len(DIGEST_INFO_PREFIX[hash_name]) + \
        HASH_LEN[hash_name] + 11
    s_limbs = L.bytes_be_to_limbs(
        [s if ok else b"" for s, ok in zip(sigs, len_ok)], table.k
    )
    expected_np = expected_pkcs1v15_em(msg_hashes, hash_name, sizes,
                                       table.k)
    in_range = s_in_range_mask(table, s_limbs, key_idx)
    if table.all_f4 and _use_rns():
        from . import rns as rns_mod

        ctx, rtab = table.rns()
        if ctx is not None:
            eq = rns_mod.verify_em_equals(ctx, rtab, s_limbs, expected_np,
                                          np.asarray(key_idx, np.int32))
            return eq & np.asarray(in_range) & len_ok & em_len_ok
    em = modexp_for_table(table, s_limbs, key_idx)
    eq = jnp.all(em == jnp.asarray(expected_np), axis=0)
    ok = np.asarray(eq & in_range)
    return ok & len_ok & em_len_ok


def _mgf1(seed: bytes, mask_len: int, hash_name: str) -> bytes:
    h_len = HASH_LEN[hash_name]
    out = bytearray()
    for counter in range((mask_len + h_len - 1) // h_len):
        out += hashlib.new(hash_name,
                           seed + counter.to_bytes(4, "big")).digest()
    return bytes(out[:mask_len])


def pss_check_em(em: bytes, m_hash: bytes, em_bits: int,
                 hash_name: str, salt_len: Optional[int] = None) -> bool:
    """EMSA-PSS-VERIFY (RFC 8017 §9.1.2) for one token, on the host.

    salt_len None → auto-recover (any salt length), matching the CPU
    oracle's PSS.AUTO verification.
    """
    h_len = HASH_LEN[hash_name]
    em_len = (em_bits + 7) // 8
    if len(em) > em_len:
        # EM must be < 2^emBits: any dropped high bytes must be zero.
        if any(em[: len(em) - em_len]):
            return False
        em = em[-em_len:]
    if em_len < h_len + 2:
        return False
    if em[-1] != 0xBC:
        return False
    masked_db = em[: em_len - h_len - 1]
    h = em[em_len - h_len - 1: em_len - 1]
    db_len = em_len - h_len - 1
    unused_bits = 8 * em_len - em_bits
    if unused_bits and masked_db[0] >> (8 - unused_bits):
        return False
    db_mask = _mgf1(h, db_len, hash_name)
    db = bytes(a ^ b for a, b in zip(masked_db, db_mask))
    if unused_bits:
        db = bytes([db[0] & (0xFF >> unused_bits)]) + db[1:]
    # DB = PS(0x00..) ‖ 0x01 ‖ salt
    sep = db.find(b"\x01")
    if sep == -1 or any(db[:sep]):
        return False
    salt = db[sep + 1:]
    if salt_len is not None and len(salt) != salt_len:
        return False
    m_prime = b"\x00" * 8 + m_hash + salt
    return hashlib.new(hash_name, m_prime).digest() == h


def verify_pss_batch(table: RSAKeyTable, sigs: Sequence[bytes],
                     msg_hashes: Sequence[bytes], hash_name: str,
                     key_idx: np.ndarray) -> np.ndarray:
    """[N] bool verdicts for one PS* bucket: device modexp + host EM check."""
    n_tok = len(sigs)
    sizes = np.asarray([table.sizes_bytes[i] for i in key_idx])
    mod_bits = np.asarray([table.n_ints[i].bit_length() for i in key_idx])
    len_ok = np.asarray([len(s) for s in sigs]) == sizes
    s_limbs = L.bytes_be_to_limbs(
        [s if ok else b"" for s, ok in zip(sigs, len_ok)], table.k
    )
    em_dev = modexp_for_table(table, s_limbs, key_idx)
    in_range = np.asarray(s_in_range_mask(table, s_limbs, key_idx))
    em_bytes = L.limbs_to_bytes_be(np.asarray(em_dev), 2 * table.k)
    out = np.zeros(n_tok, bool)
    for j in range(n_tok):
        if not (len_ok[j] and in_range[j]):
            continue
        em_bits = int(mod_bits[j]) - 1
        out[j] = pss_check_em(em_bytes[j], msg_hashes[j], em_bits, hash_name)
    return out


# ---------------------------------------------------------------------------
# Device-side EMSA-PSS-VERIFY (SHA-256/384/512)
# ---------------------------------------------------------------------------

def _pss_hash_fns(hash_name: str):
    """(fixed_fn, var_fn, h_len) for the device PSS hashing."""
    if hash_name == "sha256":
        from . import sha256 as S

        return S.sha256_fixed, S.sha256_var, 32
    from . import sha512 as S

    if hash_name == "sha384":
        return S.sha384_fixed, S.sha384_var, 48
    if hash_name == "sha512":
        return S.sha512_fixed, S.sha512_var, 64
    raise ValueError(f"unsupported PSS hash {hash_name!r}")


def _vshift_left(mat, sh, max_shift: int):
    """out[i, j] = mat[i, j + sh[i]] (zero fill), sh ∈ [0, max_shift].

    Binary-decomposed variable shift: log2 masked STATIC slices. A
    per-token ``take_along_axis`` byte gather here measured ~40 ms per
    call @16k on chip (u8 lane gathers scalarize); these ~9 selects
    are plain elementwise traffic (docs/PERF.md r5 PSS section).
    """
    import jax.numpy as jnp

    n = mat.shape[0]
    x = mat
    bits = max(1, int(max_shift).bit_length())
    for b in range(bits):
        step = 1 << b
        if step > max_shift:
            break
        shifted = jnp.concatenate(
            [x[:, step:], jnp.zeros((n, step), x.dtype)], axis=1)
        x = jnp.where((sh[:, None] & step) != 0, shifted, x)
    return x


def _pss_verify_device(em_bytes, mhash, mod_bits, *, width: int,
                       hash_name: str):
    """RFC 8017 §9.1.2 on device, salt auto-recovered: [N] bool.

    em_bytes: [N, width] big-endian EM integer bytes (width = 2k);
    mhash: [N, h_len] u8; mod_bits: [N] i32 per-token modulus bits.
    The MGF1 digests and H' run as batched device hashing
    (tpu/sha256.py, tpu/sha512.py — all three PS* families), so NO EM
    bytes ever leave the device; the reference computes all of this
    per token on CPU (jwt/keyset.go:126-139 → crypto/rsa.VerifyPSS).
    All per-token-offset extraction uses _vshift_left — no dynamic
    gathers anywhere.

    Bit-exact with pss_check_em/cap_pss_check_batch: every structural
    rejection (short emLen, missing 0xBC, nonzero leading bits/bytes,
    bad PS/0x01 separator, H' mismatch) reproduces the host verdicts.
    """
    import jax.numpy as jnp

    sha_fixed, sha_var, h_len = _pss_hash_fns(hash_name)

    n = em_bytes.shape[0]
    em_bits = mod_bits.astype(jnp.int32) - 1
    em_len = (em_bits + 7) // 8                     # [N]
    start = width - em_len                          # first EM byte
    cols = jnp.arange(width, dtype=jnp.int32)[None, :]

    # EM < 2^emBits: bytes before `start` must be zero.
    lead_ok = jnp.all(jnp.where(cols < start[:, None], em_bytes, 0) == 0,
                      axis=1)
    db_len = em_len - h_len - 1                     # [N]
    len_ok = em_len >= h_len + 2
    trailer_ok = em_bytes[:, width - 1] == 0xBC

    # H and maskedDB, extracted at per-token offsets (variable shift).
    h_mat = em_bytes[:, width - 1 - h_len: width - 1]       # [N, h_len]
    db_max = width - h_len - 1
    dbj = jnp.arange(db_max, dtype=jnp.int32)[None, :]
    start_c = jnp.clip(start, 0, width)
    masked_db = _vshift_left(em_bytes, start_c, width)[:, :db_max]
    in_db = dbj < db_len[:, None]
    masked_db = jnp.where(in_db, masked_db, 0)

    unused = 8 * em_len - em_bits                   # [N] ∈ [0, 7]
    top_mask = (0xFF >> unused).astype(jnp.uint8)   # [N]
    top_ok = (unused == 0) | \
        ((masked_db[:, 0] >> (8 - unused).astype(jnp.uint8)) == 0)

    # MGF1(H, dbLen): ceil(db_max/h_len) fixed-size single-block
    # hashes; mask byte j = Hash(H ‖ be32(j // h_len))[j % h_len].
    n_ctr = (db_max + h_len - 1) // h_len
    seeds = jnp.zeros((n, h_len + 4), jnp.uint8)
    seeds = seeds.at[:, :h_len].set(h_mat)
    mask_parts = []
    for ctr in range(n_ctr):
        s = seeds.at[:, h_len + 3].set(jnp.uint8(ctr & 0xFF))
        s = s.at[:, h_len + 2].set(jnp.uint8((ctr >> 8) & 0xFF))
        mask_parts.append(sha_fixed(s))
    mask = jnp.concatenate(mask_parts, axis=1)[:, :db_max]
    db = masked_db ^ jnp.where(in_db, mask, 0)
    db = db.at[:, 0].set(db[:, 0] & top_mask)

    # DB = 0x00.. ‖ 0x01 ‖ salt: first nonzero byte must be 0x01.
    nz = (db != 0) & in_db
    sep = jnp.argmax(nz, axis=1).astype(jnp.int32)  # 0 when none
    any_nz = jnp.any(nz, axis=1)
    sep_byte = jnp.sum(
        jnp.where(dbj == sep[:, None], db.astype(jnp.int32), 0), axis=1)
    sep_ok = any_nz & (sep_byte == 1)
    salt_len = db_len - sep - 1                     # [N]

    # M' = 0^8 ‖ mHash ‖ salt; salt = db shifted left by sep+1.
    salt_max = db_max - 1
    mp_len = 8 + h_len + salt_len
    mp_max = 8 + h_len + salt_max
    sj = jnp.arange(salt_max, dtype=jnp.int32)[None, :]
    salt = _vshift_left(db, sep + 1, db_max)[:, :salt_max]
    salt = jnp.where(sj < salt_len[:, None], salt, 0)
    mprime = jnp.zeros((n, mp_max), jnp.uint8)
    mprime = mprime.at[:, 8:8 + h_len].set(mhash[:, :h_len])
    mprime = mprime.at[:, 8 + h_len:].set(salt)
    hprime = sha_var(mprime, mp_len, mp_max)

    h_ok = jnp.all(hprime[:, :h_len] == h_mat, axis=1)
    return (lead_ok & len_ok & trailer_ok & top_ok & sep_ok & h_ok &
            (db_len > 0))


# ---------------------------------------------------------------------------
# Packed single-transfer dispatch (the H2D-pipelined hot path)
# ---------------------------------------------------------------------------
#
# Round-1 link probes (docs/PERF.md) showed the host↔device link
# rewarding FEW, LARGE transfers: bandwidth rises from
# ~6 MB/s at 1 MB to ~24 MB/s at 64 MB, concurrent streams do NOT
# aggregate, and transfers DO overlap device compute. So the hot path
# ships ONE u8 record matrix per chunk — [sig ‖ digest ‖ flags ‖ kid]
# rows — and runs unpack + limb building + expected-EM construction +
# modexp + compare as ONE jitted program returning a [N] bool that is
# only materialized in the batch-wide sync wave.

RS_REC_EXTRA = 2          # trailing bytes per record: flags, key row


def rs_packed_records(table: RSAKeyTable, sig_mat: np.ndarray,
                      sig_lens: np.ndarray, hash_mat: np.ndarray,
                      hash_name: str, key_idx: np.ndarray) -> np.ndarray:
    """Host: build the packed [N, 2k + hlen + 2] u8 record matrix.

    Row layout: right-aligned signature bytes (2k) ‖ digest (hlen) ‖
    validity flag u8 ‖ key row u8. Invalid-length signatures are zeroed
    with flag 0 (their verdict is decided host-side, matching the CPU
    oracle's rejections).
    """
    sizes = np.asarray(table.sizes_bytes, np.int64)[key_idx]
    len_ok = sig_lens == sizes
    em_len_ok = sizes >= len(DIGEST_INFO_PREFIX[hash_name]) + \
        HASH_LEN[hash_name] + 11
    flags = (len_ok & em_len_ok).astype(np.uint8)
    safe_lens = np.where(len_ok, sig_lens, 0)
    width = 2 * table.k
    aligned = L.right_align_bytes(
        np.where(len_ok[:, None], sig_mat[:, :width], 0), safe_lens, width)
    h_len = HASH_LEN[hash_name]
    rec = np.empty((sig_mat.shape[0], width + h_len + RS_REC_EXTRA),
                   np.uint8)
    rec[:, :width] = aligned
    rec[:, width:width + h_len] = hash_mat[:, :h_len]
    rec[:, width + h_len] = flags
    rec[:, width + h_len + 1] = key_idx.astype(np.uint8)
    return rec


def _rs_packed_unpack(packed, k: int, h_len: int):
    """In-jit: record matrix → (s_limbs, dig, flags, idx)."""
    import jax.numpy as jnp

    width = 2 * k
    s_limbs = bytes_to_limbs_device(packed[:, :width])
    dig = packed[:, width:width + h_len]
    flags = packed[:, width + h_len] != 0
    idx = packed[:, width + h_len + 1].astype(jnp.int32)
    return s_limbs, dig, flags, idx


@program_name(f"{PROGRAM}_rns")
def _rs_packed_rns_impl(packed, sizes_tab, n_tab, sig_c_tab, n_B_tab,
                        a2_A_tab, a2_B_tab, *, k: int, hash_name: str,
                        ctx):
    import jax.numpy as jnp

    from . import bignum
    from .rns import _rns_verify_core

    s_limbs, dig, flags, idx = _rs_packed_unpack(packed, k,
                                                 HASH_LEN[hash_name])
    sizes = sizes_tab[idx]
    expected = _expected_em_device(dig, sizes, k, hash_name)
    in_range = ~bignum.compare_ge(s_limbs, n_tab[idx].T)
    ok = _rns_verify_core(ctx, s_limbs, expected, sig_c_tab[idx].T,
                          n_B_tab[idx].T, a2_A_tab[idx].T,
                          a2_B_tab[idx].T)
    return ok & in_range & flags


@program_name(f"{PROGRAM}_limb")
def _rs_packed_limb_impl(packed, sizes_tab, n_tab, np_tab, r2_tab,
                         one_tab, e_tab, *, k: int, hash_name: str,
                         ebits: int, all_f4: bool):
    import jax.numpy as jnp

    from . import bignum

    s_limbs, dig, flags, idx = _rs_packed_unpack(packed, k,
                                                 HASH_LEN[hash_name])
    sizes = sizes_tab[idx]
    expected = _expected_em_device(dig, sizes, k, hash_name)
    n = n_tab[idx].T
    in_range = ~bignum.compare_ge(s_limbs, n)
    nprime = np_tab[idx].T
    r2 = r2_tab[idx].T
    if all_f4:
        em = bignum.modexp_65537(s_limbs, n, nprime, r2)
    else:
        em = bignum.modexp_vare(s_limbs, e_tab[idx], n, nprime, r2,
                                one_tab[idx].T, ebits=ebits)
    eq = jnp.all(em == expected, axis=0)
    return eq & in_range & flags


@program_name(f"{PS_PROGRAM}_rns")
def _ps_packed_rns_impl(packed, mod_bits_tab, n_tab, sig_c_tab, n_B_tab,
                        a2_A_tab, a2_B_tab, *, k: int, hash_name: str,
                        ctx):
    from . import bignum
    from .rns import _rns_modexp_em_core

    s_limbs, dig, flags, idx = _rs_packed_unpack(packed, k,
                                                 HASH_LEN[hash_name])
    n_g = n_tab[idx].T
    in_range = ~bignum.compare_ge(s_limbs, n_g)
    em = _rns_modexp_em_core(ctx, k + 1, s_limbs, sig_c_tab[idx].T,
                             n_B_tab[idx].T, a2_A_tab[idx].T,
                             a2_B_tab[idx].T, n_g)
    em_bytes = _limbs_to_bytes_impl(em[:k])   # canonical < n < 2^16k
    ok = _pss_verify_device(em_bytes, dig, mod_bits_tab[idx],
                            width=2 * k, hash_name=hash_name)
    return ok & in_range & flags


@program_name(f"{PS_PROGRAM}_limb")
def _ps_packed_limb_impl(packed, mod_bits_tab, n_tab, np_tab, r2_tab,
                         one_tab, e_tab, *, k: int, hash_name: str,
                         ebits: int, all_f4: bool):
    from . import bignum

    s_limbs, dig, flags, idx = _rs_packed_unpack(packed, k,
                                                 HASH_LEN[hash_name])
    n = n_tab[idx].T
    in_range = ~bignum.compare_ge(s_limbs, n)
    nprime = np_tab[idx].T
    r2 = r2_tab[idx].T
    if all_f4:
        em = bignum.modexp_65537(s_limbs, n, nprime, r2)
    else:
        em = bignum.modexp_vare(s_limbs, e_tab[idx], n, nprime, r2,
                                one_tab[idx].T, ebits=ebits)
    em_bytes = _limbs_to_bytes_impl(em)
    ok = _pss_verify_device(em_bytes, dig, mod_bits_tab[idx],
                            width=2 * k, hash_name=hash_name)
    return ok & in_range & flags


_rs_packed_jits: dict = {}


def _rs_packed_jit(name: str, impl, static_names):
    fn = _rs_packed_jits.get(name)
    if fn is None:
        import jax

        fn = jax.jit(impl, static_argnames=static_names)
        _rs_packed_jits[name] = fn
    return fn


def _run_packed(name: str, impl, rec, tables, static: dict, mesh):
    """Dispatch one packed program: on one device, or split along the
    batch axis of ``mesh`` with the tables replicated."""
    if mesh is not None:
        from ..parallel.place import run_batch_sharded

        return run_batch_sharded(impl, mesh, rec, tables, static)
    import jax

    fn = _rs_packed_jit(name, impl, tuple(static))
    return fn(jax.device_put(rec), *tables, **static)


def verify_rs_packed_pending(table: RSAKeyTable, rec: np.ndarray,
                             hash_name: str, mesh=None):
    """Dispatch one packed RS* chunk; returns the device [N] bool.

    One H2D transfer (the record matrix), one compiled program, no
    materialization — the caller syncs the whole batch at once. With a
    mesh, the record shards along the batch axis and the tables
    replicate (each device verifies its own rows — SURVEY.md §2.6).
    """
    if table.all_f4 and _use_rns():
        ctx, rtab = table.rns()
        if ctx is not None:
            return _run_packed(
                "rns", _rs_packed_rns_impl, rec,
                (table.sizes_dev, table.n_tab, rtab.sig_c, rtab.n_B,
                 rtab.a2_A, rtab.a2_B),
                dict(k=table.k, hash_name=hash_name, ctx=ctx), mesh)
    return _run_packed(
        "limb", _rs_packed_limb_impl, rec,
        (table.sizes_dev, table.n_tab, table.np_tab, table.r2_tab,
         table.one_tab, table.e_dev),
        dict(k=table.k, hash_name=hash_name, ebits=table.max_ebits,
             all_f4=table.all_f4), mesh)


def verify_ps_packed_pending(table: RSAKeyTable, rec: np.ndarray,
                             hash_name: str, mesh=None):
    """Dispatch one packed PS* chunk; returns the device [N] bool.

    Like verify_rs_packed_pending, but the expected-EM compare is
    replaced by the FULL device-side EMSA-PSS-VERIFY — modexp, MGF1,
    separator scan, and H' hashing all stay on device, so the EM bytes
    (as large as the signature upload) never cross back to the host.
    All three hash families (tpu/sha256.py, tpu/sha512.py).
    """
    if table.all_f4 and _use_rns():
        ctx, rtab = table.rns()
        if ctx is not None:
            return _run_packed(
                "ps_rns", _ps_packed_rns_impl, rec,
                (table.mod_bits_dev, table.n_tab, rtab.sig_c, rtab.n_B,
                 rtab.a2_A, rtab.a2_B),
                dict(k=table.k, hash_name=hash_name, ctx=ctx), mesh)
    return _run_packed(
        "ps_limb", _ps_packed_limb_impl, rec,
        (table.mod_bits_dev, table.n_tab, table.np_tab, table.r2_tab,
         table.one_tab, table.e_dev),
        dict(k=table.k, hash_name=hash_name, ebits=table.max_ebits,
             all_f4=table.all_f4), mesh)
