"""Batched Keccak-f[1600] + SHAKE-128/256 device lanes (FIPS 202).

The post-quantum verify families are SHAKE-bound: ML-DSA's μ/c̃
absorb-squeeze ran on the host per token (the last per-token host hash
in any packed path), and SLH-DSA verify is ~2-6k Keccak permutations
per signature — *pure hash*, nothing else. This module makes Keccak a
batch-lane workload like everything else in ``cap_tpu/tpu``:

- **state layout**: each 64-bit Keccak lane rides as a **uint32
  bit-interleaved pair** — word 0 holds the even-indexed bits, word 1
  the odd-indexed bits — so a 64-bit rotation is two independent
  32-bit rotations (the classic 32-bit Keccak trick), and no int64
  ever appears (TPUs have no 64-bit integer units; the same posture
  as the NTT's 16-bit-limb Montgomery). A batch is ``[..., 25, 2]``
  uint32; XOR/AND/NOT are interleaving-transparent.
- ``f1600`` is the jitted jnp permutation (``lax.fori_loop`` over the
  24 rounds, ρ/π unrolled per lane); ``f1600_pallas`` runs the whole
  permutation as ONE Pallas kernel on a ``[50, L]`` VMEM tile (rows =
  25 even + 25 odd planes) in the ``pallas_madd``/``redc``/``edw``
  house pattern, with interpret-mode fallback on CPU. ``permute``
  dispatches between them via :func:`enabled`.
- absorb/squeeze drivers: the HOST does byte-level padding only
  (cheap, branchy, variable-length — never a hash); blocks ship as
  pre-interleaved lane tensors and the device runs the masked
  per-token block loop, so tokens of different lengths share one
  fixed-shape graph.

``f1600_ref``/``shake128_ref``/``shake256_ref`` are the numpy uint64
host references — pinned against stdlib ``hashlib.shake_128/256`` on
arbitrary absorb/squeeze lengths by tests/test_pallas_keccak.py (the
``ntt_ref`` contract, extended), and the bit-equality reference for
both device paths. They also back the numpy-batched fixture signer in
``slhdsa.py``.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence, Tuple

import numpy as np

# jax is imported inside the device entry points: the numpy reference
# must stay importable on accelerator-less hosts (same lazy-jax stance
# as ntt.py).

RATE_SHAKE128 = 168               # bytes; 21 lanes
RATE_SHAKE256 = 136               # bytes; 17 lanes
DOMAIN_SHAKE = 0x1F               # FIPS 202 SHAKE domain + pad10*1 head


def _gen_round_constants() -> np.ndarray:
    """The 24 ι round constants from the rc(t) LFSR (FIPS 202 §3.2.5)
    — generated, not transcribed, so they cannot be mistyped."""
    def rc_bits():
        r = 1
        while True:
            yield r & 1
            r <<= 1
            if r & 0x100:
                r ^= 0x171
    bits = rc_bits()
    out = []
    for _ in range(24):
        rc = 0
        for j in range(7):
            if next(bits):
                rc |= 1 << ((1 << j) - 1)
        out.append(rc)
    return np.array(out, np.uint64)


def _gen_rho_offsets() -> np.ndarray:
    """ρ rotation offsets per flat lane x+5y (FIPS 202 §3.2.2),
    generated from the (t+1)(t+2)/2 walk."""
    r = np.zeros(25, np.int64)
    x, y = 1, 0
    for t in range(24):
        r[x + 5 * y] = ((t + 1) * (t + 2) // 2) % 64
        x, y = y, (2 * x + 3 * y) % 5
    return r


RC64 = _gen_round_constants()
RHO = _gen_rho_offsets()
def _gen_pi() -> np.ndarray:
    # π: input lane x+5y lands at output flat lane y + 5*((2x+3y)%5).
    dest = np.zeros(25, np.int64)
    for x in range(5):
        for y in range(5):
            dest[x + 5 * y] = y + 5 * ((2 * x + 3 * y) % 5)
    return dest


PI_DEST = _gen_pi()
# PI_SRC[l'] = the input lane that lands at output lane l'.
PI_SRC = np.zeros(25, np.int64)
PI_SRC[PI_DEST] = np.arange(25)


# ---------------------------------------------------------------------------
# numpy uint64 reference (exact; the oracle-side transform)
# ---------------------------------------------------------------------------

def _rotl64(v: np.ndarray, r: int) -> np.ndarray:
    if r == 0:
        return v
    return (v << np.uint64(r)) | (v >> np.uint64(64 - r))


def f1600_ref(state: np.ndarray) -> np.ndarray:
    """Keccak-f[1600] on uint64 lanes ``[..., 25]`` (flat index x+5y)."""
    a = np.asarray(state, np.uint64).copy()
    for rc in RC64:
        # θ
        c = a[..., 0:5].copy()
        for y in range(1, 5):
            c ^= a[..., 5 * y: 5 * y + 5]
        d = np.empty_like(c)
        for x in range(5):
            d[..., x] = c[..., (x - 1) % 5] ^ _rotl64(c[..., (x + 1) % 5], 1)
        for y in range(5):
            a[..., 5 * y: 5 * y + 5] ^= d
        # ρ + π
        b = np.empty_like(a)
        for l in range(25):
            b[..., PI_DEST[l]] = _rotl64(a[..., l], int(RHO[l]))
        # χ
        for y in range(5):
            row = b[..., 5 * y: 5 * y + 5]
            a[..., 5 * y: 5 * y + 5] = row ^ (
                ~np.roll(row, -1, axis=-1) & np.roll(row, -2, axis=-1))
        # ι
        a[..., 0] ^= rc
    return a


def _shake_ref(data: bytes, rate: int, outlen: int) -> bytes:
    """SHAKE sponge on the numpy reference permutation."""
    msg = bytearray(data)
    msg.append(DOMAIN_SHAKE)
    while len(msg) % rate:
        msg.append(0)
    msg[-1] ^= 0x80
    state = np.zeros(25, np.uint64)
    nl = rate // 8
    for off in range(0, len(msg), rate):
        block = np.frombuffer(bytes(msg[off: off + rate]),
                              np.uint8).view("<u8")
        state[:nl] ^= block
        state = f1600_ref(state)
    out = bytearray()
    while len(out) < outlen:
        out += state[:nl].tobytes()[:rate]
        if len(out) < outlen:
            state = f1600_ref(state)
    return bytes(out[:outlen])


def shake128_ref(data: bytes, outlen: int) -> bytes:
    return _shake_ref(data, RATE_SHAKE128, outlen)


def shake256_ref(data: bytes, outlen: int) -> bytes:
    return _shake_ref(data, RATE_SHAKE256, outlen)


# ---------------------------------------------------------------------------
# bit interleaving (host numpy; uint64 <-> uint32 even/odd pairs)
# ---------------------------------------------------------------------------

def _compress_even_u64(x: np.ndarray) -> np.ndarray:
    """Gather the even-indexed bits of uint64 lanes into the low 32."""
    m = np.uint64
    x = x & m(0x5555555555555555)
    x = (x | (x >> m(1))) & m(0x3333333333333333)
    x = (x | (x >> m(2))) & m(0x0F0F0F0F0F0F0F0F)
    x = (x | (x >> m(4))) & m(0x00FF00FF00FF00FF)
    x = (x | (x >> m(8))) & m(0x0000FFFF0000FFFF)
    x = (x | (x >> m(16))) & m(0x00000000FFFFFFFF)
    return x.astype(np.uint32)


def _spread_u32_to_even_u64(x: np.ndarray) -> np.ndarray:
    """Inverse of :func:`_compress_even_u64`: u32 -> even bits of u64."""
    m = np.uint64
    x = x.astype(np.uint64)
    x = (x | (x << m(16))) & m(0x0000FFFF0000FFFF)
    x = (x | (x << m(8))) & m(0x00FF00FF00FF00FF)
    x = (x | (x << m(4))) & m(0x0F0F0F0F0F0F0F0F)
    x = (x | (x << m(2))) & m(0x3333333333333333)
    x = (x | (x << m(1))) & m(0x5555555555555555)
    return x


def interleave(lanes64: np.ndarray) -> np.ndarray:
    """uint64 lanes ``[...]`` -> interleaved uint32 pairs ``[..., 2]``
    (``[..., 0]`` = even bits, ``[..., 1]`` = odd bits)."""
    lanes64 = np.asarray(lanes64, np.uint64)
    e = _compress_even_u64(lanes64)
    o = _compress_even_u64(lanes64 >> np.uint64(1))
    return np.stack([e, o], axis=-1)


def deinterleave(il: np.ndarray) -> np.ndarray:
    """Interleaved uint32 pairs ``[..., 2]`` -> uint64 lanes ``[...]``."""
    il = np.asarray(il, np.uint32)
    e = _spread_u32_to_even_u64(il[..., 0])
    o = _spread_u32_to_even_u64(il[..., 1])
    return e | (o << np.uint64(1))


RC_IL = interleave(RC64)                                  # [24, 2]
# ι as a one-hot XOR mask over the full state (broadcasts in the
# fori_loop body without dynamic-update ops).
RC_ONEHOT = np.zeros((24, 25, 2), np.uint32)
RC_ONEHOT[:, 0, :] = RC_IL

# 64-bit rotation in the interleaved domain: even r -> both words
# rotate by r/2; odd r -> the words swap roles, the (new) even word
# rotates one extra step. Precomputed per lane for the ρ offsets.
_RHO_SWAP = (RHO % 2).astype(bool)
_RHO_RE = np.where(_RHO_SWAP, (RHO + 1) // 2, RHO // 2)   # rot for E'
_RHO_RO = RHO // 2                                        # rot for O'


# ---------------------------------------------------------------------------
# jnp permutation on interleaved lanes (the CPU/XLA device path)
# ---------------------------------------------------------------------------

def _rotl32(w, s: int):
    if s == 0:
        return w
    return (w << np.uint32(s)) | (w >> np.uint32(32 - s))


# ρ/π fused for the vectorized jnp path: output lane lp takes input
# lane PI_SRC[lp] rotated by RHO[PI_SRC[lp]] — rotation amounts and
# the odd-rotation word swap indexed per OUTPUT lane.
_PI_RE = _RHO_RE[PI_SRC].astype(np.uint32)
_PI_RO = _RHO_RO[PI_SRC].astype(np.uint32)
_PI_SWAP = _RHO_SWAP[PI_SRC]


def _rotv(w, s):
    """Per-element uint32 rotate-left (s in [0, 32), vector amounts)."""
    import jax.numpy as jnp

    return jnp.where(s == 0, w,
                     (w << s) | (w >> ((np.uint32(32) - s)
                                       & np.uint32(31))))


def _round_il(a, rc_onehot):
    """One Keccak round on ``[..., 25, 2]`` uint32 interleaved lanes
    (fully vectorized across lanes — per-lane rotation amounts ride as
    element-wise shift vectors, no python lane loop)."""
    import jax.numpy as jnp

    lead = a.shape[:-2]
    a5 = a.reshape(lead + (5, 5, 2))          # [..., y, x, 2]
    c = a5[..., 0, :, :] ^ a5[..., 1, :, :] ^ a5[..., 2, :, :] \
        ^ a5[..., 3, :, :] ^ a5[..., 4, :, :]             # [..., x, 2]
    cm1 = jnp.roll(c, 1, axis=-2)
    cp1 = jnp.roll(c, -1, axis=-2)
    # rot64 by 1 (odd): E' = rotl32(O, 1), O' = E
    cp1r = jnp.stack([_rotl32(cp1[..., 1], 1), cp1[..., 0]], axis=-1)
    d = cm1 ^ cp1r                                        # [..., x, 2]
    a = (a5 ^ d[..., None, :, :]).reshape(lead + (25, 2))
    # ρ + π in one gather + two vector rotates
    g = jnp.take(a, jnp.asarray(PI_SRC), axis=-2)         # [..., 25, 2]
    ge, go = g[..., 0], g[..., 1]
    re = jnp.asarray(_PI_RE)
    ro = jnp.asarray(_PI_RO)
    swap = jnp.asarray(_PI_SWAP)
    be = jnp.where(swap, _rotv(go, re), _rotv(ge, re))
    bo = jnp.where(swap, _rotv(ge, ro), _rotv(go, ro))
    b5 = jnp.stack([be, bo], axis=-1).reshape(lead + (5, 5, 2))
    a = (b5 ^ (~jnp.roll(b5, -1, axis=-2) & jnp.roll(b5, -2, axis=-2))) \
        .reshape(lead + (25, 2))
    return a ^ rc_onehot


def f1600(state):
    """Keccak-f[1600] on ``[..., 25, 2]`` uint32 interleaved lanes
    (jnp; jit-safe — the 24 rounds ride a ``fori_loop``)."""
    import jax
    import jax.numpy as jnp

    rc = jnp.asarray(RC_ONEHOT)

    def body(i, a):
        return _round_il(a, rc[i])

    return jax.lax.fori_loop(0, 24, body, state)


# ---------------------------------------------------------------------------
# Pallas kernel: the whole permutation on one [50, L] VMEM tile
# ---------------------------------------------------------------------------

_TILE = int(os.environ.get("CAP_TPU_KECCAK_TILE", 256))   # lanes/step


def enabled() -> bool:
    """Fused Pallas Keccak kernel: CAP_TPU_PALLAS_KECCAK=1/0 overrides.

    Default ON for accelerator backends (the Mosaic target the house
    kernels compile for); CPU stays on the jnp path — interpret mode
    is a correctness harness, not a fast path (docs/PERF.md; the
    bench_stages kernel rows publish the honest CPU A/B).
    """
    v = os.environ.get("CAP_TPU_PALLAS_KECCAK")
    if v is not None:
        return v not in ("0", "false", "no")
    import jax

    return jax.default_backend() == "tpu"


def _round_planes(planes, rc_e, rc_o):
    """One round on a [50, T] plane stack (rows 0-24 even words, rows
    25-49 odd); ``rc_e``/``rc_o`` are the round's interleaved ι
    constant words (scalars).
    Static row slices only (the Mosaic gather rule, as in
    pallas_madd's cpA handling); shared by the kernel's round loop."""
    import jax.numpy as jnp

    e = [planes[l: l + 1, :] for l in range(25)]
    o = [planes[25 + l: 26 + l, :] for l in range(25)]
    ce = [e[x] ^ e[x + 5] ^ e[x + 10] ^ e[x + 15] ^ e[x + 20]
          for x in range(5)]
    co = [o[x] ^ o[x + 5] ^ o[x + 10] ^ o[x + 15] ^ o[x + 20]
          for x in range(5)]
    de = [ce[(x - 1) % 5] ^ _rotl32(co[(x + 1) % 5], 1)
          for x in range(5)]
    do = [co[(x - 1) % 5] ^ ce[(x + 1) % 5] for x in range(5)]
    e = [e[l] ^ de[l % 5] for l in range(25)]
    o = [o[l] ^ do[l % 5] for l in range(25)]
    be: List = [None] * 25
    bo: List = [None] * 25
    for l in range(25):
        ee, oo = e[l], o[l]
        if _RHO_SWAP[l]:
            ne = _rotl32(oo, int(_RHO_RE[l]))
            no = _rotl32(ee, int(_RHO_RO[l]))
        else:
            ne = _rotl32(ee, int(_RHO_RE[l]))
            no = _rotl32(oo, int(_RHO_RO[l]))
        be[int(PI_DEST[l])] = ne
        bo[int(PI_DEST[l])] = no
    e = [be[l] ^ (~be[5 * (l // 5) + (l + 1) % 5]
                  & be[5 * (l // 5) + (l + 2) % 5]) for l in range(25)]
    o = [bo[l] ^ (~bo[5 * (l // 5) + (l + 1) % 5]
                  & bo[5 * (l // 5) + (l + 2) % 5]) for l in range(25)]
    e[0] = e[0] ^ rc_e
    o[0] = o[0] ^ rc_o
    return jnp.concatenate(e + o, axis=0)


def _f1600_kernel(s_ref, rc_ref, o_ref):
    """The 24 rounds as an in-kernel ``fori_loop`` on a [50, T] VMEM
    tile — one compact round body instead of a 24x-unrolled graph
    (the unrolled form compiled for minutes in interpret mode). The
    round constants sit in SMEM and are read as scalars at the loop
    index: Mosaic lowers no ``dynamic_slice`` of a value."""
    import jax

    def body(rnd, planes):
        return _round_planes(planes, rc_ref[rnd, 0], rc_ref[rnd, 1])

    o_ref[:] = jax.lax.fori_loop(0, 24, body, s_ref[:])


def _f1600_call(planes, interpret: bool):
    import jax
    import jax.numpy as jnp
    from functools import partial
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    @partial(jax.jit, static_argnames=("interpret",))
    def call(planes, rc, interpret: bool):
        n = planes.shape[1]
        grid = n // _TILE
        spec = pl.BlockSpec((50, _TILE), lambda i: (0, i),
                            memory_space=pltpu.VMEM)
        rc_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
        return pl.pallas_call(
            _f1600_kernel,
            out_shape=jax.ShapeDtypeStruct((50, n), jnp.uint32),
            grid=(grid,),
            in_specs=[spec, rc_spec],
            out_specs=spec,
            interpret=interpret,
        )(planes, rc)

    return call(planes, jnp.asarray(RC_IL), interpret)


def f1600_pallas(state, interpret: Optional[bool] = None):
    """Pallas-kernel permutation on ``[..., 25, 2]`` interleaved lanes
    — bit-identical to :func:`f1600` (pinned interpret-mode on CPU by
    tests + make pallas-smoke). Lanes fold onto the kernel's [50, L]
    plane layout; L pads to the tile size."""
    import jax.numpy as jnp

    if interpret is None:
        import jax

        interpret = jax.default_backend() != "tpu"
    lead = state.shape[:-2]
    n = 1
    for s in lead:
        n *= s
    flat = state.reshape((n, 25, 2))
    planes = jnp.concatenate([flat[:, :, 0].T, flat[:, :, 1].T], axis=0)
    pad = (-n) % _TILE
    if pad:
        planes = jnp.pad(planes, ((0, 0), (0, pad)))
    out = _f1600_call(planes, interpret)[:, :n]
    return jnp.stack([out[:25].T, out[25:].T], axis=-1).reshape(
        lead + (25, 2))


def permute(state, interpret: Optional[bool] = None):
    """The permutation the device drivers call: the Pallas kernel when
    :func:`enabled`, the jnp graph otherwise. Bit-identical either
    way."""
    if enabled():
        return f1600_pallas(state, interpret=interpret)
    return f1600(state)


# ---------------------------------------------------------------------------
# host packing + device absorb/squeeze drivers
# ---------------------------------------------------------------------------

def pad_message(data: bytes, rate: int) -> bytes:
    """SHAKE pad10*1 with the 0x1F domain: whole rate-blocks out."""
    msg = bytearray(data)
    msg.append(DOMAIN_SHAKE)
    while len(msg) % rate:
        msg.append(0)
    msg[-1] ^= 0x80
    return bytes(msg)


def pack_blocks(msgs: Sequence[bytes], rate: int,
                min_blocks: int = 1) -> Tuple[np.ndarray, np.ndarray]:
    """Pad + interleave a batch of variable-length messages.

    Returns ``(blocks [B, NB, 25, 2] uint32, nblk [B] int32)`` where
    NB = max(ceil((len+1)/rate)) over the batch (at least
    ``min_blocks``); capacity lanes and blocks past a token's count
    are zero. The HOST does only byte shuffling here — no hashing.
    """
    nl = rate // 8
    padded = [pad_message(m, rate) for m in msgs]
    nblk = np.array([len(p) // rate for p in padded], np.int32)
    nb = max(int(nblk.max()) if len(padded) else 1, min_blocks)
    out = np.zeros((len(padded), nb, 25, 2), np.uint32)
    for i, p in enumerate(padded):
        lanes = np.frombuffer(p, np.uint8).view("<u8").reshape(-1, nl)
        out[i, : lanes.shape[0], :nl] = interleave(lanes)
    return out, nblk


def absorb(blocks, nblk):
    """Masked batched absorb: ``blocks`` [..., NB, 25, 2] uint32 (from
    :func:`pack_blocks`, already on device or host), ``nblk`` [...]
    int32. Lanes finish at their own block count and freeze — the
    per-lane select that lets one fixed-shape graph serve a whole
    mixed-length batch. Returns the final states [..., 25, 2]."""
    import jax.numpy as jnp

    state = jnp.zeros(blocks.shape[:-3] + (25, 2), jnp.uint32)
    for blk in range(blocks.shape[-3]):
        nxt = permute(state ^ blocks[..., blk, :, :])
        live = (nblk > blk)[..., None, None]
        state = jnp.where(live, nxt, state)
    return state


def absorb_fixed(blocks):
    """Absorb with a UNIFORM block count (no mask): ``blocks``
    [..., NB, 25, 2] where every lane uses all NB blocks — the
    fixed-length hash path (w1 encode, tree nodes, WOTS chains)."""
    import jax.numpy as jnp

    state = jnp.zeros(blocks.shape[:-3] + (25, 2), jnp.uint32)
    for blk in range(blocks.shape[-3]):
        state = permute(state ^ blocks[..., blk, :, :])
    return state


def squeeze_lanes(state, rate: int, n_blocks: int):
    """``n_blocks`` squeeze blocks of interleaved lanes from absorbed
    states [B, 25, 2] -> [B, n_blocks * rate//8, 2]."""
    import jax.numpy as jnp

    nl = rate // 8
    outs = [state[..., :nl, :]]
    for _ in range(n_blocks - 1):
        state = permute(state)
        outs.append(state[..., :nl, :])
    return jnp.concatenate(outs, axis=-2)


def lanes_to_bytes(lanes):
    """Interleaved lanes [..., L, 2] -> bytes [..., L*8] uint32-valued
    (each entry in [0, 256)) — the device-side deinterleave, built
    from 16->32 bit spreads so no int64 appears."""
    import jax.numpy as jnp

    def spread16(x):
        x = x & np.uint32(0xFFFF)
        x = (x | (x << np.uint32(8))) & np.uint32(0x00FF00FF)
        x = (x | (x << np.uint32(4))) & np.uint32(0x0F0F0F0F)
        x = (x | (x << np.uint32(2))) & np.uint32(0x33333333)
        x = (x | (x << np.uint32(1))) & np.uint32(0x55555555)
        return x

    e, o = lanes[..., 0], lanes[..., 1]
    lo = spread16(e) | (spread16(o) << np.uint32(1))
    hi = spread16(e >> np.uint32(16)) | \
        (spread16(o >> np.uint32(16)) << np.uint32(1))
    w = jnp.stack([lo, hi], axis=-1)          # [..., L, 2] u32 (lo,hi)
    shifts = np.arange(4, dtype=np.uint32) * 8
    by = (w[..., None] >> shifts) & np.uint32(0xFF)
    return by.reshape(by.shape[:-3] + (-1,))


def bits_to_lanes(bits):
    """Little-endian bit tensor [..., L*64] (values 0/1 uint32) ->
    interleaved lanes [..., L, 2]: even/odd bits fold directly into
    the two words, skipping the byte stage entirely."""
    import jax.numpy as jnp

    lead = bits.shape[:-1]
    nl = bits.shape[-1] // 64
    v = bits.reshape(lead + (nl, 32, 2)).astype(jnp.uint32)
    shifts = np.arange(32, dtype=np.uint32)
    e = jnp.sum(v[..., 0] << shifts, axis=-1, dtype=jnp.uint32)
    o = jnp.sum(v[..., 1] << shifts, axis=-1, dtype=jnp.uint32)
    return jnp.stack([e, o], axis=-1)
