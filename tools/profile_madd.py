#!/usr/bin/env python3
"""Where does the fused-madd ES256 core spend its time?

Slope-times, on device-resident operands at ladder shapes ([I, 2N]
planes, 2N lanes):
  madd   — the Pallas fused mixed-add kernel alone, chained
  gather — the fused x‖y window-table gather alone, chained
  core   — the full _ecdsa_rns_core for reference

All chains use the slope method ((t(1+R) - t(1)) / R) so dispatch and
sync constants cancel (slope methodology, docs/PERF.md).
"""

import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

N = int(os.environ.get("N", 32768))
REPS = int(os.environ.get("REPS", 3))
CHAIN = int(os.environ.get("CHAIN", 32))   # windows per rep

os.environ.setdefault("CAP_TPU_RNS", "1")

from cap_tpu import testing as T
from cap_tpu.tpu import ec as tpuec
from cap_tpu.tpu import ec_rns, pallas_madd

import jax
import jax.numpy as jnp
from jax import lax


def slope(fn, sync):
    sync(fn(1))
    sync(fn(1 + REPS))
    t0 = time.perf_counter()
    sync(fn(1))
    t1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    sync(fn(1 + REPS))
    tR = time.perf_counter() - t0
    return (tR - t1) / REPS


def main():
    print(f"backend={jax.default_backend()} N={N} lanes={2*N} "
          f"chain={CHAIN}", flush=True)
    c = ec_rns.ctx_for("P-256")
    rng = np.random.default_rng(0)
    ia, ib = c.A.count, c.B.count
    lanes = 2 * N
    print(f"I_A={ia} I_B={ib} tile={pallas_madd._TILE}")

    def plane():
        return (jax.device_put(rng.integers(
                    0, 4000, (ia, lanes)).astype(np.int32)),
                jax.device_put(rng.integers(
                    0, 4000, (ib, lanes)).astype(np.int32)))

    X, Y, Z = plane(), plane(), plane()
    iap = ec_rns.packed_cols(c)
    x2 = jax.device_put(  # packed A|B<<16 table words
        (rng.integers(0, 4000, (iap, lanes))
         | (rng.integers(0, 4000, (iap, lanes)) << 16)).astype(np.int32))
    y2 = jax.device_put(
        (rng.integers(0, 4000, (iap, lanes))
         | (rng.integers(0, 4000, (iap, lanes)) << 16)).astype(np.int32))
    inf = jax.device_put(np.zeros(lanes, bool))
    has = jax.device_put(np.ones(lanes, bool))

    # (a) fused madd kernel chained CHAIN times
    @partial(jax.jit, static_argnames=("reps",))
    def madd_chain(Xa, Xb, Ya, Yb, Za, Zb, reps: int):
        def body(i, st):
            Xs, Ys, Zs = st
            Xn, Yn, Zn, dd = pallas_madd.madd_fused(
                c, Xs, Ys, Zs, inf, has, x2, y2)
            return (Xn, Yn, Zn)

        Xs, Ys, Zs = lax.fori_loop(
            0, reps * CHAIN, body, ((Xa, Xb), (Ya, Yb), (Za, Zb)))
        return Xs[0]

    t = slope(lambda r: madd_chain(X[0], X[1], Y[0], Y[1], Z[0], Z[1],
                                   reps=r),
              lambda o: float(jnp.sum(o)))
    print(f"madd kernel x{CHAIN}:   {t*1000:7.1f} ms "
          f"({t/CHAIN*1e3:.2f} ms/window)", flush=True)

    # (b) gather chained: fused x||y table, per-lane rows
    keys = [T.generate_keys("ES256")[1] for _ in range(8)]
    table = tpuec.ECKeyTable("P-256", keys)
    rtab = table.rns()
    tab = rtab.tab
    print(f"table: {tab.shape} = {tab.nbytes/(1<<20):.1f} MB")
    idx = jax.device_put(
        rng.integers(0, tab.shape[0], lanes).astype(np.int32))

    @partial(jax.jit, static_argnames=("reps",))
    def gather_chain(i0, reps: int):
        def body(i, acc):
            g = jnp.take(tab, (i0 + i) % tab.shape[0], axis=0).T
            return acc + jnp.sum(g, axis=0)

        return lax.fori_loop(0, reps * CHAIN, body,
                             jnp.zeros(lanes, jnp.int32))

    t = slope(lambda r: gather_chain(idx, reps=r),
              lambda o: float(jnp.sum(o)))
    print(f"gather x{CHAIN}:        {t*1000:7.1f} ms "
          f"({t/CHAIN*1e3:.2f} ms/window)", flush=True)

    # (c) full core
    cp = table.curve
    consts = cp.device_consts()
    k = cp.k
    r_np = rng.integers(1, 1 << 16, (k, N), dtype=np.int64).astype(np.uint32)
    s_np = rng.integers(1, 1 << 16, (k, N), dtype=np.int64).astype(np.uint32)
    e_np = rng.integers(0, 1 << 16, (k, N), dtype=np.int64).astype(np.uint32)
    kid = rng.integers(0, 8, N).astype(np.int32)
    rr = jax.device_put(r_np)
    ss = jax.device_put(s_np)
    ee = jax.device_put(e_np)
    kidd = jax.device_put(kid)

    def run():
        return ec_rns._ecdsa_rns_core(
            rr, ss, ee, kidd, rtab.tab, *consts[4:9],
            crv=cp.name, nbits=cp.nbits)

    ok, deg = run()
    float(jnp.sum(ok))
    t0 = time.perf_counter()
    ok, deg = run()
    float(jnp.sum(ok))
    t1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    outs = [run() for _ in range(1 + REPS)]
    acc = outs[0][0]
    for o, _ in outs[1:]:
        acc = acc ^ o
    float(jnp.sum(acc))
    tR = time.perf_counter() - t0
    per = (tR - t1) / REPS
    print(f"full core:          {per*1000:7.1f} ms "
          f"= {N/per:,.0f}/s resident", flush=True)


if __name__ == "__main__":
    main()
