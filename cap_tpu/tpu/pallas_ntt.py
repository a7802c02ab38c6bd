"""Fused layered-butterfly NTT/INTT over Z_8380417 as Pallas kernels.

The stagewise jnp graph in ``ntt.py`` materializes the whole
``[..., 256]`` lane array to HBM between each of the 8 butterfly
stages — the same per-layer traffic tax the RNS REDC layers paid
before ``pallas_madd`` (docs/PERF.md round-3: measured ~6x the pure
read+write traffic per layer). This module runs ALL 8 stages (forward
or inverse, including the folded 256⁻¹ scaling) on one VMEM tile per
row block: inside the kernel HBM is touched once for inputs and once
for outputs (plus one XLA transpose each side into the kernel's
coefficient-major layout), the shape of the win the GPU Dilithium
engine (PAPERS.md, arxiv 2211.12265) demonstrates for exactly this
transform.

Arithmetic is ``ntt.py``'s verbatim: uint32 Montgomery lanes, 16-bit
limb ``_mulhi32`` REDC, no int64 anywhere (``mont_mul``/``add_q``/
``sub_q`` are imported and used unchanged, so the two paths cannot
drift). Twiddles ride in Montgomery form as kernel constants.

Numerical contract: bit-identical to ``ntt.ntt``/``ntt.intt`` and the
int64 ``ntt_ref``/``intt_ref`` host references — pinned by
tests/test_pallas_ntt.py in interpret mode on CPU and by
``make pallas-smoke``; tests/test_chip_compile.py compiles both
kernels for a described v5e chip. Enabled via CAP_TPU_PALLAS_NTT (default ON for
TPU backends; CPU keeps the XLA path — interpret mode is a
correctness harness, and the bench_stages kernel rows publish the
honest CPU A/B).
"""

from __future__ import annotations

import os
from functools import partial
from typing import Optional

import numpy as np

from . import ntt as _ntt

N = _ntt.N
_LANES = 128                      # batch rows per vreg lane row
_TILE_S = 8                       # sublane rows per grid step


def _reverse_stage_segments(table) -> np.ndarray:
    """Each inverse stage's twiddle segment [nblk, 2·nblk) reversed, so
    the kernel slices a constant table and never reverses (Mosaic has
    no ``rev``). Segments of different stages are disjoint."""
    out = np.array(table, np.uint32)
    for s in range(8):
        nb = N >> (s + 1)
        out[nb: 2 * nb] = out[nb: 2 * nb][::-1].copy()
    return out


_NEG_ZETAS_REV = _reverse_stage_segments(_ntt.NEG_ZETAS_MONT)


def enabled() -> bool:
    """Fused Pallas NTT: CAP_TPU_PALLAS_NTT=1/0 overrides; default ON
    only for accelerator backends (the pallas_madd stance)."""
    v = os.environ.get("CAP_TPU_PALLAS_NTT")
    if v is not None:
        return v not in ("0", "false", "no")
    import jax

    return jax.default_backend() == "tpu"


# In-kernel layout is coefficient-major: x is [256, S, 128] with the
# 256 coefficients on the UNTILED leading axis and the batch on the
# (sublane, lane) tile. Every stage reshape then touches only leading
# dims, so no shape cast ever splits the 128-lane axis (the sub-128
# cast the chip's compiler refuses), and each butterfly is plain
# full-vreg elementwise work. ``z`` is [256, 1, 128]: each twiddle
# pre-broadcast along the lanes.

def _ntt_stages(x, z):
    """All 8 forward Cooley-Tukey stages; ntt.ntt's loop body."""
    import jax.numpy as jnp

    tail = x.shape[1:]
    for s in range(8):
        ln = 128 >> s
        nblk = N // (2 * ln)
        v = x.reshape((nblk, 2, ln) + tail)
        lo_, hi_ = v[:, 0], v[:, 1]
        t = _ntt.mont_mul(z[nblk: 2 * nblk][:, None], hi_)
        x = jnp.stack([_ntt.add_q(lo_, t), _ntt.sub_q(lo_, t)],
                      axis=1).reshape((N,) + tail)
    return x


def _intt_stages(x, z_rev, inv256):
    """All 8 Gentleman-Sande inverse stages + the folded 256⁻¹ scale;
    ntt.intt's loop body, with ``z_rev`` the pre-reversed table."""
    import jax.numpy as jnp

    tail = x.shape[1:]
    for s in range(8):
        ln = 1 << s
        nblk = N // (2 * ln)
        v = x.reshape((nblk, 2, ln) + tail)
        lo_, hi_ = v[:, 0], v[:, 1]
        t = lo_
        lo_ = _ntt.add_q(t, hi_)
        hi_ = _ntt.mont_mul(z_rev[nblk: 2 * nblk][:, None],
                            _ntt.sub_q(t, hi_))
        x = jnp.stack([lo_, hi_], axis=1).reshape((N,) + tail)
    return _ntt.mont_mul(inv256, x)


def _ntt_kernel(x_ref, z_ref, o_ref):
    o_ref[:] = _ntt_stages(x_ref[:], z_ref[:])


def _intt_kernel(x_ref, z_ref, o_ref):
    o_ref[:] = _intt_stages(x_ref[:], z_ref[:],
                            np.uint32(_ntt.INV256_MONT))


def _call(xt, inverse: bool, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    @partial(jax.jit, static_argnames=("inverse", "interpret"))
    def run(xt, z, inverse: bool, interpret: bool):
        s_rows = xt.shape[1]
        tile = min(_TILE_S, s_rows)
        spec = pl.BlockSpec((N, tile, _LANES), lambda i: (0, i, 0),
                            memory_space=pltpu.VMEM)
        z_spec = pl.BlockSpec((N, 1, _LANES), lambda i: (0, 0, 0),
                              memory_space=pltpu.VMEM)
        return pl.pallas_call(
            _intt_kernel if inverse else _ntt_kernel,
            out_shape=jax.ShapeDtypeStruct(xt.shape, jnp.uint32),
            grid=(s_rows // tile,),
            in_specs=[spec, z_spec], out_specs=spec,
            interpret=interpret)(xt, z)

    table = _NEG_ZETAS_REV if inverse else _ntt.ZETAS_MONT
    z = jnp.asarray(np.broadcast_to(
        np.asarray(table, np.uint32)[:, None, None], (N, 1, _LANES)))
    return run(xt, z, inverse, interpret)


def _apply(x, inverse: bool, interpret: Optional[bool]):
    import jax.numpy as jnp

    if interpret is None:
        import jax

        interpret = jax.default_backend() != "tpu"
    shape = x.shape
    rows = 1
    for s in shape[:-1]:
        rows *= s
    s_rows = -(-rows // _LANES)
    if s_rows > _TILE_S:
        s_rows += (-s_rows) % _TILE_S
    x2 = x.reshape(rows, N)
    pad = s_rows * _LANES - rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    xt = x2.reshape(s_rows, _LANES, N).transpose(2, 0, 1)
    out = _call(xt, inverse, interpret).transpose(1, 2, 0)
    return out.reshape(s_rows * _LANES, N)[:rows].reshape(shape)


def ntt_fused(x, interpret: Optional[bool] = None):
    """Forward NTT on ``[..., 256]`` uint32 lanes in [0, q) — one
    kernel, bit-identical to ``ntt.ntt``."""
    return _apply(x, False, interpret)


def intt_fused(x, interpret: Optional[bool] = None):
    """Inverse NTT (scaling folded) — one kernel, bit-identical to
    ``ntt.intt``."""
    return _apply(x, True, interpret)
