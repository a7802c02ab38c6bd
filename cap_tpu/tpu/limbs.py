"""Host-side limb packing: Python ints / big-endian bytes ↔ limb arrays.

Numbers are little-endian base-2^16 limb vectors. Device arrays are
limb-first ([K, N]); host packing produces numpy arrays in that layout.
"""

from __future__ import annotations

from typing import Iterable, List, Sequence

import numpy as np

LIMB_BITS = 16
LIMB_BASE = 1 << LIMB_BITS
LIMB_MASK = LIMB_BASE - 1


def nlimbs_for_bits(bits: int) -> int:
    return (bits + LIMB_BITS - 1) // LIMB_BITS


def int_to_limbs(value: int, k: int) -> np.ndarray:
    """One int → [k] uint32 little-endian limb vector."""
    if value < 0:
        raise ValueError("negative values are not representable")
    if value >> (k * LIMB_BITS):
        raise ValueError(f"value does not fit in {k} limbs")
    out = np.empty(k, dtype=np.uint32)
    for i in range(k):
        out[i] = value & LIMB_MASK
        value >>= LIMB_BITS
    return out


def ints_to_limbs(values: Sequence[int], k: int) -> np.ndarray:
    """N ints → [k, N] uint32 limb-first array."""
    n = len(values)
    out = np.empty((k, n), dtype=np.uint32)
    for j, v in enumerate(values):
        out[:, j] = int_to_limbs(v, k)
    return out


def limbs_to_int(limbs: np.ndarray) -> int:
    """[k] limb vector → int (limbs need not be normalized)."""
    value = 0
    for i in range(limbs.shape[0] - 1, -1, -1):
        value = (value << LIMB_BITS) + int(limbs[i])
    return value


def limbs_to_ints(limbs: np.ndarray) -> List[int]:
    """[k, N] limb-first array → list of N ints."""
    return [limbs_to_int(limbs[:, j]) for j in range(limbs.shape[1])]


def bytes_be_to_limbs(chunks: Iterable[bytes], k: int) -> np.ndarray:
    """N big-endian byte strings → [k, N] limb array (vectorized).

    All chunks must have length ≤ 2*k bytes. This is the hot host-side
    conversion (signatures and hashes into device layout), so it works
    on a padded [N, 2k] byte matrix instead of per-item Python ints.
    """
    chunk_list = list(chunks)
    n = len(chunk_list)
    width = 2 * k
    buf = np.zeros((n, width), dtype=np.uint8)
    for j, c in enumerate(chunk_list):
        if len(c) > width:
            raise ValueError(f"chunk of {len(c)} bytes exceeds {k} limbs")
        if c:
            buf[j, width - len(c):] = np.frombuffer(c, dtype=np.uint8)
    # big-endian bytes → little-endian 16-bit limbs
    hi = buf[:, 0::2].astype(np.uint32)
    lo = buf[:, 1::2].astype(np.uint32)
    limbs_be = (hi << 8) | lo          # [N, k] most-significant limb first
    return limbs_be[:, ::-1].T.copy()  # → [k, N] little-endian, limb-first


def right_align_bytes(mat: np.ndarray, lens: np.ndarray,
                      width: int) -> np.ndarray:
    """Vectorized: left-aligned [N, W] byte rows → right-aligned [N, width].

    Row i's value occupies its first lens[i] bytes; output rows are
    zero-padded on the left (big-endian integer layout).
    """
    n, w = mat.shape
    if int(lens.max(initial=0)) > width:
        raise ValueError("value exceeds capacity")
    cols = np.arange(width)[None, :]
    src = cols - (width - lens[:, None])
    valid = src >= 0
    return np.where(valid, mat[np.arange(n)[:, None],
                               np.clip(src, 0, w - 1)], 0).astype(np.uint8)


def bytes_to_limbs_device(mat):
    """Device: [N, 2K] u8 right-aligned big-endian → [K, N] u32 limbs.

    The host ships raw bytes (half the wire size of u32 limb arrays —
    host↔device bandwidth is a scarce resource);
    the big-endian-bytes → little-endian-limbs transform runs on
    device.
    """
    import jax.numpy as jnp

    m = mat.astype(jnp.uint32)
    hi = m[:, 0::2]
    lo = m[:, 1::2]
    return ((hi << 8) | lo)[:, ::-1].T


def bytes_matrix_to_limbs(mat: np.ndarray, lens: np.ndarray,
                          k: int) -> np.ndarray:
    """Vectorized: left-aligned big-endian byte rows → [k, N] limb array.

    mat: [N, W] uint8 with each row's value occupying its first lens[i]
    bytes (tail is padding). Values longer than 2*k bytes raise.
    """
    buf = right_align_bytes(mat, lens, 2 * k)
    hi = buf[:, 0::2].astype(np.uint32)
    lo = buf[:, 1::2].astype(np.uint32)
    limbs_be = (hi << 8) | lo
    return limbs_be[:, ::-1].T.copy()


def limbs_to_bytes_be(limbs: np.ndarray, nbytes: int) -> List[bytes]:
    """[k, N] limb array → N big-endian byte strings of length nbytes."""
    k, n = limbs.shape
    if nbytes > 2 * k:
        raise ValueError("nbytes exceeds limb capacity")
    le = limbs.T.astype(np.uint32)                   # [N, k] little-endian
    be = le[:, ::-1]                                 # most-significant first
    out = np.empty((n, 2 * k), dtype=np.uint8)
    out[:, 0::2] = (be >> 8).astype(np.uint8)
    out[:, 1::2] = (be & 0xFF).astype(np.uint8)
    return [out[j, 2 * k - nbytes:].tobytes() for j in range(n)]
