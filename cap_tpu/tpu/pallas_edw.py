"""Fused Edwards mixed-add (extended + precomputed) as one Pallas TPU
kernel — the Ed25519 ladder analog of pallas_madd.

Each of the 32 ladder windows runs ``ed25519_rns._edw_madd_rns``: 7
field multiplies (each a full Bajard/Kawamura REDC) plus lazy
adds/subs on (X, Y, Z, T) residue-plane pairs. Under XLA those REDCs
materialize their [I, 2N] neighborhoods to HBM between kernels even
with the fused-REDC kernel serving each multiply (pallas_redc); this
kernel runs the WHOLE mixed-add on VMEM tiles, touching HBM once for
inputs and once for outputs. The Edwards addition law here is complete
(a = -1, add-2008-hwcd-3) and the window tables carry identity rows
for digit 0, so — unlike the Jacobian kernel — there are no masks, no
degeneracy probe, and no infinity lift.

Numerical contract: bit-identical to _edw_madd_rns (same fixed-point
ops via pallas_redc.make_rns_ops — ``rmul_many``'s lane concatenation
is elementwise per lane, so per-pair rmuls produce the same digits).
Parity pinned by tests/test_pallas_madd.py in interpret mode and
compiled on chip. Default ON for TPU once measured faster (A/B in
docs/PERF.md); CAP_TPU_PALLAS_EDW=1/0 overrides.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp

from .pallas_redc import make_rns_ops

I32 = jnp.int32

_TILE = int(os.environ.get("CAP_TPU_EDW_TILE", 512))  # lanes/step


def enabled() -> bool:
    """Fused Edwards mixed-add: CAP_TPU_PALLAS_EDW=1/0 overrides.

    Default ON for the TPU backend (GPU keeps the XLA path, like
    pallas_madd): three same-minutes on-chip A/B pairs @16k resident
    EdDSA, min-of-3 slope, fused vs per-REDC-fused baseline —
    619→652, 623→707, 658→985 k verifies/s; fused won every pair
    (the spread is dispatch noise). CPU defaults to the XLA
    path (the parity reference); CAP_TPU_PALLAS_EDW=1 on CPU runs
    interpret mode, which the parity tests use.
    """
    v = os.environ.get("CAP_TPU_PALLAS_EDW")
    if v is not None:
        return v not in ("0", "false", "no")
    return jax.default_backend() == "tpu"


def _edw_kernel(xa_ref, xb_ref, ya_ref, yb_ref, za_ref, zb_ref,
                ta_ref, tb_ref,
                yma_ref, ymb_ref, ypa_ref, ypb_ref, t2a_ref, t2b_ref,
                mA_ref, mB_ref, sigc_ref, nB_ref,
                wab_ref, wba_ref,
                amodb_ref, bmoda_ref, invab_ref, invmib_ref,
                cpA_ref, cpB_ref, c14a_ref, c14b_ref,
                oxa_ref, oxb_ref, oya_ref, oyb_ref, oza_ref, ozb_ref,
                ota_ref, otb_ref):
    _, _, rmul, radd, rsub, _ = make_rns_ops(
        mA_ref[:], mB_ref[:], sigc_ref[:], nB_ref[:],
        wab_ref[:], wba_ref[:],
        amodb_ref[:], bmoda_ref[:], invab_ref[:], invmib_ref[:],
        cpA_ref[:], cpB_ref[:], c14a_ref[:], c14b_ref[:])

    X = (xa_ref[:], xb_ref[:])
    Y = (ya_ref[:], yb_ref[:])
    Z = (za_ref[:], zb_ref[:])
    T = (ta_ref[:], tb_ref[:])
    ym = (yma_ref[:], ymb_ref[:])
    yp = (ypa_ref[:], ypb_ref[:])
    t2 = (t2a_ref[:], t2b_ref[:])

    # _edw_madd_rns, layer for layer (digit/value bounds live there).
    a = rmul(rsub(Y, X, 4, 1), ym)
    b = rmul(radd(Y, X), yp)
    cc = rmul(T, t2)
    d = radd(Z, Z)
    e = rsub(b, a, 4, 1)
    f = rsub(d, cc, 4, 1)
    g = radd(d, cc)
    h = radd(b, a)
    X3 = rmul(e, f)
    Y3 = rmul(g, h)
    Z3 = rmul(f, g)
    T3 = rmul(e, h)

    oxa_ref[:], oxb_ref[:] = X3
    oya_ref[:], oyb_ref[:] = Y3
    oza_ref[:], ozb_ref[:] = Z3
    ota_ref[:], otb_ref[:] = T3


_CONSTS: Dict[int, tuple] = {}


def _ctx_consts(c) -> tuple:
    """Kernel constant set for a FieldRNSContext (host numpy, cached).

    Reuses pallas_redc's cached 14-entry REDC constant set (one
    derivation to keep in sync), inserting only the pre-transposed
    c·p residue tables this kernel's rsub needs.
    """
    from . import pallas_redc

    def build():
        # pallas_redc's 12-entry tuple ends (..., invmib, c14a, c14b);
        # this kernel's signature wants cpA/cpB before the c14 pair.
        r = pallas_redc._ctx_consts(c)
        return r[:10] + (
            np.ascontiguousarray(np.asarray(c.cp_A, np.int32).T),
            np.ascontiguousarray(np.asarray(c.cp_B, np.int32).T),
        ) + r[10:]

    return pallas_redc.pinned_ctx_cache(_CONSTS, c, build)


@partial(jax.jit, static_argnames=("ia", "ib", "interpret"))
def _edw_call(xa, xb, ya, yb, za, zb, ta, tb,
              yma, ymb, ypa, ypb, t2a, t2b,
              mA, mB, sigc, nB, wab, wba,
              amodb, bmoda, invab, invmib, cpA, cpB, c14a, c14b,
              ia: int, ib: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = xa.shape[1]
    grid = (n // _TILE,)

    def col_spec(rows):
        return pl.BlockSpec((rows, _TILE), lambda i: (0, i),
                            memory_space=pltpu.VMEM)

    def const_spec(shape):
        return pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape),
                            memory_space=pltpu.VMEM)

    consts = (mA, mB, sigc, nB, wab, wba, amodb, bmoda,
              invab, invmib, cpA, cpB, c14a, c14b)
    outs = (jax.ShapeDtypeStruct((ia, n), I32),
            jax.ShapeDtypeStruct((ib, n), I32)) * 4
    return pl.pallas_call(
        _edw_kernel,
        out_shape=outs,
        grid=grid,
        in_specs=[col_spec(ia), col_spec(ib)] * 7
        + [const_spec(a.shape) for a in consts],
        out_specs=tuple([col_spec(ia), col_spec(ib)] * 4),
        interpret=interpret,
    )(xa, xb, ya, yb, za, zb, ta, tb, yma, ymb, ypa, ypb, t2a, t2b,
      *consts)


def edw_madd_fused(c, X, Y, Z, T, ym, yp, t2, interpret: bool = False):
    """Fused _edw_madd_rns step: returns (X', Y', Z', T').

    All operands are (A, B) residue-plane pairs [I, N]; N pads to the
    tile size with zero lanes (every fix maps zeros to valid residues
    and the caller's slices drop them).
    """
    ia = X[0].shape[0]
    ib = X[1].shape[0]
    n = X[0].shape[1]
    pad = (-n) % _TILE

    def p2(pair):
        if not pad:
            return pair
        return (jnp.pad(pair[0], ((0, 0), (0, pad))),
                jnp.pad(pair[1], ((0, 0), (0, pad))))

    Xp, Yp, Zp, Tp = p2(X), p2(Y), p2(Z), p2(T)
    ymp, ypp, t2p = p2(ym), p2(yp), p2(t2)
    out = _edw_call(Xp[0], Xp[1], Yp[0], Yp[1], Zp[0], Zp[1],
                    Tp[0], Tp[1], ymp[0], ymp[1], ypp[0], ypp[1],
                    t2p[0], t2p[1], *_ctx_consts(c),
                    ia=ia, ib=ib, interpret=interpret)
    sl = slice(0, n)
    return ((out[0][:, sl], out[1][:, sl]),
            (out[2][:, sl], out[3][:, sl]),
            (out[4][:, sl], out[5][:, sl]),
            (out[6][:, sl], out[7][:, sl]))
