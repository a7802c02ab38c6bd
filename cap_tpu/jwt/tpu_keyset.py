"""TPUBatchKeySet — the accelerated KeySet implementation.

The north-star component (BASELINE.json): cap's per-token verify hot
path lifted into ``verify_batch(tokens)``, dispatched to the JAX/TPU
engine in cap_tpu/tpu. Gated behind the same ``KeySet`` interface as
the CPU implementations, so the Validator and the OIDC Provider share
one accelerated path while pure-CPU stays the default.

Pipeline per batch:
1. host prep (C++ runtime when built, Python fallback): JOSE split,
   base64url decode, header alg/kid scan, SHA-2 of the signing input;
2. kid → key-table row resolution (the "key gather" axis);
3. bucket by (family, hash): one static-shape device dispatch per
   bucket, padded to power-of-two sizes to bound XLA recompilation;
4. RS*/PS* → batched Montgomery modexp; ES*/EdDSA → batched EC kernels
   (curve tables); anything unbucketable falls back to the CPU oracle;
5. per-token verdicts: claims dict or the taxonomy error — identical
   outcomes to the CPU path, on failures as well as successes.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .. import telemetry
from ..errors import (
    InvalidParameterError,
    InvalidSignatureError,
    MalformedTokenError,
    NilParameterError,
)
from ..obs import decision as _decision
from ..obs import occupancy as _occupancy
from . import algs
from .jose import ParsedJWS, is_json_form, parse_jws
from .jwk import JWK
from .keyset import KeySet
from .verify import key_matches_alg, verify_parsed

_RS = {algs.RS256: "sha256", algs.RS384: "sha384", algs.RS512: "sha512"}
_PS = {algs.PS256: "sha256", algs.PS384: "sha384", algs.PS512: "sha512"}
_ES = {algs.ES256: "P-256", algs.ES384: "P-384", algs.ES512: "P-521"}

_MIN_BUCKET = 128
N_COEFF = 256                 # ML-DSA ring degree (FIPS 204)

# RSA key-table rows encode as class * _RSA_CLS_STRIDE + row. The
# stride must exceed any realistic per-class key count: with a 256
# stride, key 256 of class 0 would alias to class 1 row 0 and dispatch
# against the wrong modulus table (a persistent false rejection).
_RSA_CLS_STRIDE = 1 << 16


def _pad_size(n: int, max_chunk: int) -> int:
    """Next power of two ≥ n (≥ _MIN_BUCKET), capped at max_chunk."""
    size = _MIN_BUCKET
    while size < n:
        size *= 2
    return min(size, max_chunk)


def _mldsa_alg_indices(pb, ok: np.ndarray, name: str) -> np.ndarray:
    """Token indices whose protected alg is the ML-DSA set ``name``.

    The native prep only interns the ten classical alg names
    (``ALG_NAMES``); everything else carries ``alg_id == -1`` plus the
    raw alg bytes — so the ML-DSA bucket match is a vectorized compare
    against ``alg_raw``, no per-token Python parsing.
    """
    nb = np.frombuffer(name.encode(), np.uint8)
    cand = ok & (pb.alg_id == -1) & (pb.alg_len == len(nb))
    if not cand.any():
        return np.zeros(0, np.int64)
    match = (pb.alg_raw[:, : len(nb)] == nb).all(axis=1)
    return np.nonzero(cand & match)[0]


def _pad_telemetry(family: str, m: int, pad: int) -> None:
    """Per-family dispatch-lane accounting: how many device lanes each
    chunk used (``pad``, the padded bucket size) and how many of them
    were WASTE (padding rows verifying zeros). The fill-ratio
    histogram plus the waste counter let the exposition surface show,
    per family, how much device work the bucket rounding costs — the
    per-stage occupancy attribution the FPGA/GPU engines in PAPERS.md
    report, measured instead of assumed."""
    telemetry.observe(f"device.{family}.lanes", pad)
    telemetry.observe(f"device.{family}.fill_ratio", m / pad if pad else 0.0)
    if pad > m:
        telemetry.count(f"device.{family}.pad_waste_rows", pad - m)
    telemetry.gauge(f"device.{family}.last_lanes", pad)


def _pack_rsa_record(pb, table, kind: str, hash_name: str,
                     chunk: np.ndarray, crows: np.ndarray,
                     pad: int) -> np.ndarray:
    """One packed RS*/PS* record matrix for ``chunk`` (native packer
    when built, numpy fallback otherwise). Shared by the dispatch path
    and the resident engine benchmark so both measure the same bytes."""
    from ..tpu import rsa as tpursa

    h_len = tpursa.HASH_LEN[hash_name]
    width = 2 * table.k
    m = len(chunk)
    sizes_all = np.asarray(table.sizes_bytes, np.int64)
    sizes = sizes_all[crows]
    if kind == "rs":
        # PKCS#1 v1.5 needs emLen ≥ tLen + 11; the PSS equivalent
        # checks run on device.
        t_len = len(tpursa.DIGEST_INFO_PREFIX[hash_name]) + h_len
        extra = (sizes >= t_len + 11).astype(np.uint8)
    else:
        extra = np.ones(m, np.uint8)
    rec = pb.pack_sig_records(chunk, sizes, extra, crows, width,
                              h_len, pad)
    if rec is None:               # pre-packer .so: numpy path
        sig_mat = np.zeros((pad, width), np.uint8)
        sig_mat[:m] = pb.sig_matrix(chunk, width)
        sig_lens = np.zeros(pad, np.int64)
        sig_lens[:m] = pb.sig_len[chunk]
        hash_mat = np.zeros((pad, 64), np.uint8)
        hash_mat[:m] = pb.digest[chunk]
        key_idx = np.zeros(pad, np.int32)
        key_idx[:m] = crows
        rec = tpursa.rs_packed_records(table, sig_mat, sig_lens,
                                       hash_mat, hash_name, key_idx)
        if kind == "ps":
            # rs_packed_records applies the v1.5 emLen flag; PSS
            # keeps plain length validity.
            len_ok = (sig_lens == sizes_all[
                np.concatenate([crows, np.zeros(pad - m, np.int32)])])
            rec[:, width + h_len] = len_ok.astype(np.uint8)
            rec[m:, width + h_len] = 0
    return rec


def _pack_es_record(pb, table, chunk: np.ndarray, crows: np.ndarray,
                    hash_len: int, pad: int) -> np.ndarray:
    """One packed ES* record matrix for ``chunk`` (native packer when
    built, numpy fallback otherwise)."""
    from ..tpu import ec as tpuec

    cb = table.curve.coord_bytes
    width = 2 * cb
    m = len(chunk)
    rec = pb.pack_sig_records(chunk, np.full(m, width, np.int64),
                              np.ones(m, np.uint8), crows, width,
                              hash_len, pad)
    if rec is None:               # pre-packer .so: numpy path
        sig_mat = np.zeros((pad, width), np.uint8)
        sig_mat[:m] = pb.sig_matrix(chunk, width)
        sig_lens = np.zeros(pad, np.int64)
        sig_lens[:m] = pb.sig_len[chunk]
        hash_mat = np.zeros((pad, 64), np.uint8)
        hash_mat[:m] = pb.digest[chunk]
        key_idx = np.zeros(pad, np.int32)
        key_idx[:m] = crows
        rec = tpuec.es_packed_records(table, sig_mat, sig_lens,
                                      hash_mat, hash_len, key_idx)
    return rec


def resident_dispatchers(ks: "TPUBatchKeySet", tokens: Sequence[str],
                         repeat: int = 1, records_out: Optional[list] = None):
    """Device-RESIDENT dispatch closures for the engine benchmark.

    Preps + packs ``tokens`` ONCE, places every packed family record on
    the device, and returns ``(n_tokens, [fn, ...])`` where each ``fn()``
    re-dispatches the full packed verify program (record unpack, limb
    build, modexp / EC ladder, verdict reduce) on the already-resident
    record and returns a device array of per-slot accept bits summed to
    a scalar. Nothing host-side — prep, packing, H2D — happens on the
    timed path, so slope-timing these closures measures ENGINE speed
    independent of link bandwidth (bench.py ``resident_mixed_vps``;
    the reference's whole verify hot path is keyset.go:126-139).

    Every token must route to a packed family (RS*/PS*/ES*/EdDSA with
    device tables and known kids) — anything that would fall back to
    the CPU oracle raises, so the resident number can never silently
    measure a subset.

    ``repeat``: tile every packed record ``repeat``× along the batch
    axis before placing it on device. Dispatching a repeat-R set does
    R× the device work in the SAME number of dispatches — the slope
    between a repeat-1 and a repeat-(1+R) run cancels per-dispatch
    host overhead exactly (resident_slope_vps scaled mode).
    The advertised token count stays the base n; accept sums are
    checked against repeat·n.

    ``records_out``: optional list the placed device records are
    appended to — bench.py's mesh mode reads their
    ``addressable_shards`` to publish the ACTUAL per-device shard
    sizes rather than the intended n/N split.
    """
    import jax.numpy as jnp

    from ..runtime.native_binding import ALG_NAMES, prepare_batch_arrays
    from ..tpu import ec as tpuec
    from ..tpu import ed25519 as tpued
    from ..tpu import rsa as tpursa

    pb = prepare_batch_arrays(list(tokens))
    if not (pb.status == 0).all():
        raise InvalidParameterError(
            "resident bench tokens must all prep cleanly")
    alg_ids = {name: i for i, name in enumerate(ALG_NAMES)}
    covered = np.zeros(pb.n, bool)
    fns = []

    def occ_fn(fam: str, fn):
        """Each resident closure is an engine dispatch site: its
        re-dispatch records a per-family busy interval into the
        occupancy plane (no-op while telemetry is off, so the timed
        bench path is untouched)."""
        def dispatch_fn():
            with _occupancy.interval(fam):
                return fn()
        return dispatch_fn

    def dev_put(rec):
        import jax

        if repeat > 1:
            rec = np.tile(rec, (repeat,) + (1,) * (rec.ndim - 1))
        if ks._mesh is not None:
            # Place SHARDED up front: the verify fns' own shard_batch
            # then sees the target sharding and is a no-op, keeping
            # the timed path free of cross-device copies.
            from ..parallel.place import shard_batch

            rec = shard_batch(ks._mesh, rec)
        else:
            rec = jax.device_put(rec)
        if records_out is not None:
            records_out.append(rec)
        return rec

    for alg_name, hash_name in list(_RS.items()) + list(_PS.items()):
        kind = "rs" if alg_name in _RS else "ps"
        idx = np.nonzero(pb.alg_id == alg_ids[alg_name])[0]
        if len(idx) == 0:
            continue
        rows = pb.kid_rows(idx, ks._kid_rsa_row)
        if ks._n_rsa_keys == 1:
            rows = np.where(rows == -1, 0, rows)
        if (rows < 0).any():
            raise InvalidParameterError(
                f"{alg_name}: tokens with unknown kid")
        covered[idx] = True
        for cls, table in enumerate(ks._rsa_tables):
            sel = (rows // _RSA_CLS_STRIDE) == cls
            if not sel.any():
                continue
            if len(table.n_ints) > 255:   # u8 kid row, arrays path
                raise InvalidParameterError(
                    f"{alg_name}: >255 keys in one size class is "
                    "outside the packed path")
            chunk = idx[sel]
            crows = (rows[sel] % _RSA_CLS_STRIDE).astype(np.int32)
            pad = _pad_size(len(chunk), ks._max_chunk)
            if len(chunk) > pad:
                raise InvalidParameterError("bucket exceeds max_chunk")
            rec = dev_put(_pack_rsa_record(pb, table, kind, hash_name,
                                           chunk, crows, pad))
            verify = (tpursa.verify_rs_packed_pending if kind == "rs"
                      else tpursa.verify_ps_packed_pending)

            def fn(rec=rec, table=table, hash_name=hash_name,
                   verify=verify):
                # device_put inside is a no-op: rec is already resident
                return jnp.sum(verify(table, rec, hash_name,
                                      mesh=ks._mesh).astype(jnp.int32))

            fns.append((len(chunk), occ_fn("rsa", fn)))

    for alg_name, crv in _ES.items():
        idx = np.nonzero(pb.alg_id == alg_ids[alg_name])[0]
        if len(idx) == 0:
            continue
        if crv not in ks._ec_tables:
            raise InvalidParameterError(f"no {crv} device table")
        table = ks._ec_tables[crv]
        if len(table.keys) > 255:         # u8 kid row, arrays path
            raise InvalidParameterError(
                f"{alg_name}: >255 keys is outside the packed path")
        rows = pb.kid_rows(idx, ks._kid_ec_row[crv])
        if len(table.keys) == 1:
            rows = np.where(rows == -1, 0, rows)
        if (rows < 0).any():
            raise InvalidParameterError(
                f"{alg_name}: tokens with unknown kid")
        covered[idx] = True
        hash_len = tpursa.HASH_LEN[algs.HASH_FOR_ALG[alg_name]]
        pad = _pad_size(len(idx), ks._max_chunk)
        if len(idx) > pad:
            raise InvalidParameterError("bucket exceeds max_chunk")
        rec = dev_put(_pack_es_record(pb, table, idx,
                                      rows.astype(np.int32),
                                      hash_len, pad))

        def fn(rec=rec, table=table, hash_len=hash_len):
            # deg slots are CPU-re-verified on the real path, so they
            # count as accepts here (deg is flags-masked: padded slots
            # contribute nothing). The OR also keeps the deg output
            # live so XLA cannot dead-code any of the ladder.
            ok_dev, deg_dev = tpuec.verify_es_packed_pending(
                table, rec, hash_len, mesh=ks._mesh,
                ladder=ks._ec_ladder)
            return jnp.sum((ok_dev | deg_dev).astype(jnp.int32))

        fns.append((len(idx), occ_fn("ec", fn)))

    idx = np.nonzero(pb.alg_id == alg_ids[algs.EdDSA])[0]
    if len(idx) > 0:
        table = ks._ed_table
        if table is None:
            raise InvalidParameterError("no EdDSA device table")
        if len(table.keys) > 255:         # u8 kid row, arrays path
            raise InvalidParameterError(
                "EdDSA: >255 keys is outside the packed path")
        rows = pb.kid_rows(idx, ks._kid_ed_row)
        if len(table.keys) == 1:
            rows = np.where(rows == -1, 0, rows)
        if (rows < 0).any():
            raise InvalidParameterError("EdDSA: tokens with unknown kid")
        covered[idx] = True
        pad = _pad_size(len(idx), ks._max_chunk)
        if len(idx) > pad:
            raise InvalidParameterError("bucket exceeds max_chunk")
        sigs = [pb.signature(int(j)) for j in idx]
        msgs = [pb.signing_input(int(j)) for j in idx]
        fill = pad - len(idx)
        key_idx = np.concatenate([rows.astype(np.int32),
                                  np.zeros(fill, np.int32)])
        rec = dev_put(tpued.ed_packed_records(
            table, sigs + [b""] * fill, msgs + [b""] * fill, key_idx))

        def fn(rec=rec, table=table):
            return jnp.sum(tpued.verify_ed_packed_pending(
                table, rec, mesh=ks._mesh).astype(jnp.int32))

        fns.append((len(idx), occ_fn("ed", fn)))

    for pset in sorted(getattr(ks._tables, "mldsa_tables", {})):
        from ..tpu import mldsa as tpumldsa

        table = ks._tables.mldsa_tables[pset]
        idx = _mldsa_alg_indices(pb, pb.status == 0, pset)
        if len(idx) == 0:
            continue
        rows = pb.kid_rows(idx, ks._kid_mldsa_row[pset])
        if len(table.keys) == 1:
            rows = np.where(rows == -1, 0, rows)
        if (rows < 0).any():
            raise InvalidParameterError(
                f"{pset}: tokens with unknown kid")
        covered[idx] = True
        pad = _pad_size(len(idx), ks._max_chunk)
        if len(idx) > pad:
            raise InvalidParameterError("bucket exceeds max_chunk")
        sigs = [pb.signature(int(j)) for j in idx]
        msgs = [pb.signing_input(int(j)) for j in idx]
        if tpumldsa.fused_enabled():
            # Fused arm: the WHOLE single-round-trip program (Keccak
            # μ/c̃ + SampleInBall + NTT network + w1Encode + compare)
            # re-dispatches on resident lanes; the accept-bit sum IS
            # the integrity check, exactly like the classical
            # families (the verdict is computed on-device).
            fprep = tpumldsa._FusedPrep(table, sigs, msgs,
                                        rows.astype(np.int32), pad)
            if not fprep.valid[: len(idx)].all():
                raise InvalidParameterError(
                    f"{pset}: resident bench tokens must decode "
                    "cleanly")
            pair = tpumldsa._W1_PAD.get(pset)
            if pair is None:
                pair = tpumldsa._W1_PAD[pset] = \
                    tpumldsa._w1_pad_lanes(table.params)
            import jax

            devs = [dev_put(a) for a in
                    (fprep.mu_blocks, fprep.mu_nblk, fprep.ct_block,
                     fprep.ct_cmp, fprep.z, fprep.h, fprep.key_idx,
                     fprep.valid)]
            # constant pad tensor: never tiled/sharded (not batched)
            w1p = jax.device_put(pair[1])
            p = table.params

            def fn(devs=devs, w1p=w1p, table=table, p=p,
                   tpumldsa=tpumldsa):
                ok, _exh = tpumldsa._fused_jit()(
                    table.a_mont, table.t1_mont, *devs, w1p,
                    p.gamma2, p.tau, p.w1_bits)
                return jnp.sum(ok.astype(jnp.int32))

            fns.append((len(idx), occ_fn("mldsa", fn)))
            continue
        prep = tpumldsa._PreppedChunk(table, sigs, msgs,
                                      rows.astype(np.int32), pad)
        if not prep.valid[: len(idx)].all():
            raise InvalidParameterError(
                f"{pset}: resident bench tokens must decode cleanly")
        # The accept bit needs the host-side μ/c̃ SHAKE compare, which
        # must stay OFF the timed path — so the resident program
        # instead matches the engine's w1 lanes against the pure-int
        # oracle's, per token, ON DEVICE. Oracle-accept is asserted
        # here once; a broken engine then mismatches lanes and the
        # slope harness's accept-sum check fails exactly as for the
        # classical families.
        expected = tpumldsa.host_w1(table, prep).astype(np.uint8)
        ok_host = prep.finalize(table, expected)
        if not ok_host[: len(idx)].all():
            raise InvalidParameterError(
                f"{pset}: resident bench tokens must all verify")
        live = np.zeros(pad, np.uint8)
        live[: len(idx)] = 1
        zd = dev_put(prep.z)
        cd = dev_put(prep.c)
        hd = dev_put(prep.h)
        kd = dev_put(prep.key_idx)
        ed = dev_put(expected)
        md = dev_put(live)

        def fn(zd=zd, cd=cd, hd=hd, kd=kd, ed=ed, md=md, table=table,
               tpumldsa=tpumldsa):
            w1 = tpumldsa.w1_resident(table, zd, cd, hd, kd)
            eq = jnp.all(w1 == ed, axis=(1, 2)) & (md != 0)
            return jnp.sum(eq.astype(jnp.int32))

        fns.append((len(idx), occ_fn("mldsa", fn)))

    for pset in sorted(getattr(ks._tables, "slhdsa_tables", {})):
        from ..tpu import slhdsa as tpuslh

        table = ks._tables.slhdsa_tables[pset]
        idx = _mldsa_alg_indices(pb, pb.status == 0, pset)
        if len(idx) == 0:
            continue
        rows = pb.kid_rows(idx, ks._kid_slhdsa_row[pset])
        if len(table.keys) == 1:
            rows = np.where(rows == -1, 0, rows)
        if (rows < 0).any():
            raise InvalidParameterError(
                f"{pset}: tokens with unknown kid")
        covered[idx] = True
        pad = _pad_size(len(idx), ks._max_chunk)
        if len(idx) > pad:
            raise InvalidParameterError("bucket exceeds max_chunk")
        sigs = [pb.signature(int(j)) for j in idx]
        msgs = [pb.signing_input(int(j)) for j in idx]
        if repeat > 1 or ks._mesh is not None:
            # The hypertree arrays are layer-major ([d, B, ...]) —
            # batch-axis tiling/sharding would hit the wrong axis.
            raise InvalidParameterError(
                f"{pset}: scaled/mesh resident mode is not supported "
                "for the SLH-DSA records")
        sprep = tpuslh._SLHPrep(table, sigs, msgs,
                                rows.astype(np.int32), pad)
        if not sprep.valid[: len(idx)].all():
            raise InvalidParameterError(
                f"{pset}: resident bench tokens must decode cleanly")
        # The verdict (hash-forest root compare) is computed entirely
        # on-device, so the accept-bit sum IS the integrity check —
        # same contract as the classical families.
        sdevs = [dev_put(a) for a in sprep.arrays()]

        def fn(sdevs=sdevs, table=table, tpuslh=tpuslh):
            ok = tpuslh._slh_jit()(table.pk_seed_l, table.pk_root_l,
                                   *sdevs)
            return jnp.sum(ok.astype(jnp.int32))

        fns.append((len(idx), occ_fn("slhdsa", fn)))

    if not covered.all():
        raise InvalidParameterError(
            "tokens outside the packed families: "
            f"{np.nonzero(~covered)[0][:5].tolist()}...")
    return int(covered.sum()), fns


def resident_slope_vps(n: int, fns, reps: int = 4,
                       trials: int = 3,
                       details: bool = False,
                       fns_scaled=None):
    """Slope-time resident dispatchers → verifies/sec, or None.

    THE resident methodology (bench.py ``resident_mixed_vps``,
    tools/profile_families.py — one implementation so a fix cannot
    diverge): each trial times a 1× run and a (1+``reps``)× run and
    takes the slope, cancelling dispatch/sync constants; the MINIMUM
    per-rep time across ``trials`` trials is the engine's (dispatch
    and the materializing sync ride the host, so one stall shifts a
    single-trial slope by 2× — docs/PERF.md). Every run's accept-bit
    sum is checked against the token count, so a broken engine cannot
    produce a clean rate. Returns None when no trial yields a positive
    slope (timer noise on sub-millisecond families).

    ``fns_scaled``: dispatchers built with
    ``resident_dispatchers(..., repeat=1+reps)``. When given, the
    (1+reps)× run is ONE dispatch per family on (1+reps)×-tiled
    resident records instead of 1+reps dispatches — both slope points
    then issue the same dispatch count, so per-dispatch host
    overhead (NOT engine time) cancels exactly instead of inflating the
    slope. Without it, the old dispatch-k-times behavior applies.

    ``details=True`` returns ``(vps_or_None, per_trial_vps)`` so
    callers can publish measurement spread alongside the estimate
    (VERDICT r4 #5: the point estimate alone hides stability). Note
    min-of-3 is over per-rep TIME, so in vps terms the estimate is
    the FASTEST trial: ``vps == max(per_trial_vps)``.
    """
    def run_multi(reps_: int) -> None:
        outs = []
        for _ in range(reps_):
            outs.extend(fn() for _, fn in fns)
        total = outs[0]
        for o in outs[1:]:
            total = total + o
        got = int(total)                  # materializing sync
        if got != reps_ * n:
            raise RuntimeError(
                f"resident engine verdict mismatch: {got} accepts "
                f"for {reps_}×{n} valid tokens")

    def run_scaled(reps_: int) -> None:
        use = fns if reps_ == 1 else fns_scaled
        outs = [fn() for _, fn in use]
        total = outs[0]
        for o in outs[1:]:
            total = total + o
        got = int(total)
        if got != reps_ * n:
            raise RuntimeError(
                f"resident engine verdict mismatch: {got} accepts "
                f"for {reps_}×{n} valid tokens")

    run = run_multi if fns_scaled is None else run_scaled
    run(1)                                # compile + settle
    run(1 + reps)
    per_trial = []
    for _ in range(trials):
        t0 = time.perf_counter()
        run(1)
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        run(1 + reps)
        tr = time.perf_counter() - t0
        per = (tr - t1) / reps
        if per > 0:
            per_trial.append(n / per)
    vps = max(per_trial) if per_trial else None
    if details:
        return vps, per_trial
    return vps


class _KeyTables(object):
    """One epoch's immutable key-table set: JWKs partitioned into
    per-family device tables plus the kid-routing maps.

    Everything a batch needs to resolve kids and dispatch lives here,
    built ONCE and never mutated — ``TPUBatchKeySet.swap_keys``
    installs a fresh instance atomically, so an in-flight batch that
    captured the previous instance finishes entirely on its epoch.
    """

    __slots__ = ("epoch", "jwks", "by_kid", "kids", "rsa_tables",
                 "n_rsa_keys", "ec_tables", "ed_table", "rsa_rows",
                 "ec_rows", "ed_rows", "kid_rsa_row", "kid_ec_row",
                 "kid_ed_row", "ec_keys", "ed_keys", "mldsa_keys",
                 "mldsa_rows", "mldsa_tables", "kid_mldsa_row",
                 "slhdsa_keys", "slhdsa_rows", "slhdsa_tables",
                 "kid_slhdsa_row")

    def __init__(self, jwks: Sequence[JWK], epoch: int = 0):
        # The OpenSSL-backed key types need the ``cryptography``
        # package; ML-DSA (AKP) keys and HostECPublicKey-backed EC
        # keys are dependency-free, so the partition duck-types those
        # FIRST and only isinstance-checks the crypto classes when
        # the package exists — an ML-DSA/host-EC keyset builds (and
        # hot-swaps) on crypto-less hosts.
        try:
            from cryptography.hazmat.primitives.asymmetric import (
                ec,
                ed25519,
                rsa,
            )
        except ImportError:
            ec = ed25519 = rsa = None

        self.epoch = int(epoch)
        self.jwks = list(jwks)
        # Partition keys into family tables; remember each JWK's slot.
        # RSA keys additionally split into SIZE CLASSES (one table per
        # limb width): a mixed 2048/4096 JWKS must not pad every
        # token's wire record to the widest key (the round-1 config-②
        # cliff). Rows encode as class*_RSA_CLS_STRIDE + row.
        from ..tpu.limbs import nlimbs_for_bits

        rsa_classes: List[list] = []      # per class: [(n, e), ...]
        rsa_class_need: List[int] = []    # per class: limb width
        self.rsa_rows: Dict[int, int] = {}
        self.ec_keys: Dict[str, list] = {}
        self.ec_rows: Dict[str, Dict[int, int]] = {}
        self.ed_keys, self.ed_rows = [], {}
        # Post-quantum: one table per parameter set (alg name = set
        # name), mirroring the per-curve EC layout. ML-DSA and
        # SLH-DSA keys both carry ``parameter_set``; the set name
        # routes the family.
        from ..tpu.slhdsa import PARAMS as _SLH_PARAMS

        self.mldsa_keys: Dict[str, list] = {}
        self.mldsa_rows: Dict[str, Dict[int, int]] = {}
        self.slhdsa_keys: Dict[str, list] = {}
        self.slhdsa_rows: Dict[str, Dict[int, int]] = {}
        for i, jwk in enumerate(self.jwks):
            key = jwk.key
            pset = getattr(key, "parameter_set", None)
            host_crv = getattr(key, "curve_name", None)
            if pset is not None and pset in _SLH_PARAMS:
                rows = self.slhdsa_rows.setdefault(pset, {})
                rows[i] = len(self.slhdsa_keys.setdefault(pset, []))
                self.slhdsa_keys[pset].append(key)
            elif pset is not None:               # MLDSAPublicKey
                rows = self.mldsa_rows.setdefault(pset, {})
                rows[i] = len(self.mldsa_keys.setdefault(pset, []))
                self.mldsa_keys[pset].append(key)
            elif host_crv is not None:           # HostECPublicKey
                rows = self.ec_rows.setdefault(host_crv, {})
                rows[i] = len(self.ec_keys.setdefault(host_crv, []))
                self.ec_keys[host_crv].append(key)
            elif rsa is not None and isinstance(key, rsa.RSAPublicKey):
                nums = key.public_numbers()
                need = nlimbs_for_bits(nums.n.bit_length())
                try:
                    cls = rsa_class_need.index(need)
                except ValueError:
                    cls = len(rsa_classes)
                    rsa_classes.append([])
                    rsa_class_need.append(need)
                self.rsa_rows[i] = (cls * _RSA_CLS_STRIDE
                                    + len(rsa_classes[cls]))
                rsa_classes[cls].append((nums.n, nums.e))
            elif ec is not None and isinstance(
                    key, ec.EllipticCurvePublicKey):
                crv = {"secp256r1": "P-256", "secp384r1": "P-384",
                       "secp521r1": "P-521"}[key.curve.name]
                rows = self.ec_rows.setdefault(crv, {})
                rows[i] = len(self.ec_keys.setdefault(crv, []))
                self.ec_keys[crv].append(key)
            elif ed25519 is not None and isinstance(
                    key, ed25519.Ed25519PublicKey):
                self.ed_rows[i] = len(self.ed_keys)
                self.ed_keys.append(key)

        self.rsa_tables: List[Any] = []
        if rsa_classes:
            from ..tpu.rsa import RSAKeyTable
            self.rsa_tables = [RSAKeyTable(nums) for nums in rsa_classes]
        self.n_rsa_keys = sum(len(c) for c in rsa_classes)
        # Every family with keys gets its device table here; an engine
        # that fails to import or build is an error at table build,
        # never a silent route of the whole family to the CPU oracle.
        from ..tpu.ec import ECKeyTable
        from ..tpu.ed25519 import Ed25519KeyTable
        from ..tpu.mldsa import MLDSAKeyTable
        from ..tpu.slhdsa import SLHDSAKeyTable

        self.ec_tables: Dict[str, Any] = {
            crv: ECKeyTable(crv, keys) for crv, keys in self.ec_keys.items()}
        self.ed_table = (Ed25519KeyTable(self.ed_keys) if self.ed_keys
                         else None)
        self.mldsa_tables: Dict[str, Any] = {
            pset: MLDSAKeyTable(pset, keys)
            for pset, keys in self.mldsa_keys.items()}
        self.slhdsa_tables: Dict[str, Any] = {
            pset: SLHDSAKeyTable(pset, keys)
            for pset, keys in self.slhdsa_keys.items()}

        self.by_kid: Dict[str, List[int]] = {}
        for i, jwk in enumerate(self.jwks):
            if jwk.kid:
                self.by_kid.setdefault(jwk.kid, []).append(i)
        self.kids = frozenset(self.by_kid)

        # kid → family table row, for kids resolving to exactly one key
        # (ambiguous kids take the trial-verify slow path)
        self.kid_rsa_row: Dict[str, int] = {}
        self.kid_ec_row: Dict[str, Dict[str, int]] = {c: {} for c in
                                                      self.ec_rows}
        self.kid_ed_row: Dict[str, int] = {}
        self.kid_mldsa_row: Dict[str, Dict[str, int]] = {
            p: {} for p in self.mldsa_rows}
        self.kid_slhdsa_row: Dict[str, Dict[str, int]] = {
            p: {} for p in self.slhdsa_rows}
        for kid, idxs in self.by_kid.items():
            if len(idxs) != 1:
                continue
            i = idxs[0]
            if i in self.rsa_rows:
                self.kid_rsa_row[kid] = self.rsa_rows[i]
            for crv, rows in self.ec_rows.items():
                if i in rows:
                    self.kid_ec_row[crv][kid] = rows[i]
            if i in self.ed_rows:
                self.kid_ed_row[kid] = self.ed_rows[i]
            for pset, rows in self.mldsa_rows.items():
                if i in rows:
                    self.kid_mldsa_row[pset][kid] = rows[i]
            for pset, rows in self.slhdsa_rows.items():
                if i in rows:
                    self.kid_slhdsa_row[pset][kid] = rows[i]


class TPUBatchKeySet(KeySet):
    """KeySet whose batch path runs on the TPU verify engine.

    Construct from JWKs (key + kid metadata). Single-token
    ``verify_signature`` uses the CPU oracle; ``verify_batch`` buckets
    and dispatches to the device.

    ``mesh``: an optional ``jax.sharding.Mesh`` — every packed chunk
    (RS*/ES*/EdDSA) then shards along the batch axis across the mesh's
    devices with replicated key tables (SURVEY.md §2.6 batch-DP +
    key-gather; validated on the virtual 8-device mesh by
    tests/test_parallel.py and the driver's dryrun_multichip).

    ``ec_ladder``: the ES* window-add law — ``"jacobian"``,
    ``"affine"``, or None for the global default
    (``cap_tpu.tpu.ec.ladder_mode``, env CAP_TPU_EC_LADDER). Verdicts
    are bit-exact either way; see docs/PERF.md for the A/B.

    ``epoch``: the key-material version this initial table set
    represents (the keyplane's counter); :meth:`swap_keys` installs
    later epochs without restarting anything — see docs/KEYPLANE.md.
    """

    def __init__(self, jwks: Sequence[JWK], max_chunk: int = 32768,
                 cpu_fallback: bool = True, mesh=None,
                 ec_ladder: Optional[str] = None, epoch: int = 0):
        if not jwks:
            raise NilParameterError("at least one key is required")
        if ec_ladder is not None:
            from ..tpu.ec import resolve_ladder

            resolve_ladder(ec_ladder)     # raises on unknown modes
        self._ec_ladder = ec_ladder
        self._max_chunk = max_chunk
        self._cpu_fallback = cpu_fallback
        self._mesh = mesh
        # Wire-adaptive chunk sizing (VERDICT r3 #3): EWMA of the
        # OBSERVED effective H2D byte rate, updated after every batch
        # collect; _chunk_tokens sizes chunks to a time budget against
        # it so a slow link gets smaller chunks (bounded p99) and a
        # fast link keeps big ones (throughput). None until the first
        # batch completes (the static 5 MB default applies).
        self._wire_bps: Optional[float] = None
        self._last_collect_t: Optional[float] = None
        self._chunk_budget_s = float(os.environ.get(
            "CAP_TPU_CHUNK_BUDGET_MS", "250")) / 1e3
        import threading

        self._swap_lock = threading.Lock()
        self._tables = _KeyTables(jwks, epoch=epoch)

    # -- epoch-versioned key tables (keyplane hot swap) --------------------

    @property
    def key_epoch(self) -> int:
        """Epoch of the tables NEW batches dispatch against."""
        return self._tables.epoch

    def swap_keys(self, jwks, epoch: Optional[int] = None,
                  grace_s: float = 30.0) -> int:
        """Hot-swap the key tables to a new epoch; returns the epoch.

        ``jwks``: a JWKS document (dict — parsed via
        :func:`cap_tpu.jwt.jwk.parse_jwks`) or a sequence of
        :class:`JWK`. ``epoch``: the keyplane's version for this
        material (default: current + 1).

        Semantics:

        - the replacement tables are built OFF the serving path (in
          the caller's thread — refresher/push threads, never a verify
          thread) and installed with one atomic reference swap;
        - batches already dispatched keep the table set they captured
          and finish entirely on their epoch;
        - for ``grace_s`` seconds, kids that exist only in the OLD
          epoch still resolve (the installed set is the new JWKS plus
          the retired-kid keys), so tokens signed moments before the
          rotation don't flap to unknown-kid rejects; after the grace
          window a pure new-epoch table set is built in the background
          and takes over.
        """
        if isinstance(jwks, dict):
            from .jwk import parse_jwks

            jwks = parse_jwks(jwks)
        jwks = list(jwks)
        if not jwks:
            raise NilParameterError("at least one key is required")
        import threading

        t0 = time.perf_counter()
        with self._swap_lock:
            old = self._tables
            new_epoch = old.epoch + 1 if epoch is None else int(epoch)
            new_kids = {j.kid for j in jwks if j.kid}
            retained = ([j for j in old.jwks
                         if j.kid and j.kid not in new_kids]
                        if grace_s > 0 else [])
            with telemetry.span(telemetry.SPAN_KEYPLANE_SWAP):
                self._tables = _KeyTables(jwks + retained,
                                          epoch=new_epoch)
        if retained:
            telemetry.count("keyplane.grace_kids", len(retained))
            timer = threading.Timer(
                grace_s, self._retire_grace, args=(jwks, new_epoch))
            timer.daemon = True
            timer.start()
        telemetry.count("keyplane.swaps")
        telemetry.observe("keyplane.swap_s", time.perf_counter() - t0)
        telemetry.gauge("keyplane.epoch", new_epoch)
        return new_epoch

    def _retire_grace(self, jwks, epoch: int) -> None:
        """Grace expiry: install the pure new-epoch tables (background
        thread — the build never runs on a verify path). A newer swap
        having landed meanwhile makes this a no-op."""
        try:
            pure = _KeyTables(jwks, epoch=epoch)
        except Exception:  # noqa: BLE001 - keep serving graced tables
            telemetry.count("keyplane.grace_retire_errors")
            return
        with self._swap_lock:
            if self._tables.epoch == epoch:
                self._tables = pure
                telemetry.count("keyplane.grace_retired")

    # Compatibility delegates: the pre-keyplane attribute names, used
    # by resident_dispatchers/bench/tests, read the CURRENT epoch.
    @property
    def _jwks(self):
        return self._tables.jwks

    @property
    def _by_kid(self):
        return self._tables.by_kid

    @property
    def _rsa_tables(self):
        return self._tables.rsa_tables

    @property
    def _n_rsa_keys(self):
        return self._tables.n_rsa_keys

    @property
    def _ec_tables(self):
        return self._tables.ec_tables

    @property
    def _ed_table(self):
        return self._tables.ed_table

    @property
    def _rsa_rows(self):
        return self._tables.rsa_rows

    @property
    def _ec_rows(self):
        return self._tables.ec_rows

    @property
    def _ed_rows(self):
        return self._tables.ed_rows

    @property
    def _kid_rsa_row(self):
        return self._tables.kid_rsa_row

    @property
    def _kid_ec_row(self):
        return self._tables.kid_ec_row

    @property
    def _kid_ed_row(self):
        return self._tables.kid_ed_row

    @property
    def _ec_keys(self):
        return self._tables.ec_keys

    @property
    def _ed_keys(self):
        return self._tables.ed_keys

    @property
    def _mldsa_tables(self):
        return self._tables.mldsa_tables

    @property
    def _kid_mldsa_row(self):
        return self._tables.kid_mldsa_row

    @property
    def _slhdsa_tables(self):
        return self._tables.slhdsa_tables

    @property
    def _kid_slhdsa_row(self):
        return self._tables.kid_slhdsa_row

    # -- single-token path (CPU oracle) -----------------------------------

    def _candidate_indices(self, parsed: ParsedJWS,
                           tables: Optional[_KeyTables] = None
                           ) -> List[int]:
        t = self._tables if tables is None else tables
        if parsed.kid is not None and parsed.kid in t.by_kid:
            pool = t.by_kid[parsed.kid]
        else:
            pool = range(len(t.jwks))
        return [i for i in pool
                if key_matches_alg(t.jwks[i].key, parsed.alg)]

    def verify_signature(self, token: str) -> Dict[str, Any]:
        return self._verify_parsed_trial(parse_jws(token))

    # -- batch path --------------------------------------------------------

    def _verify_parsed_trial(self, parsed: ParsedJWS,
                             tables: Optional[_KeyTables] = None):
        """Trial-verify one parsed token against the candidate keys —
        the single-token verdict logic, shared by verify_signature and
        the batch path's non-compactable JSON-form fallback."""
        t = self._tables if tables is None else tables
        last: Optional[Exception] = None
        for i in self._candidate_indices(parsed, t):
            try:
                verify_parsed(parsed, t.jwks[i].key)
                return parsed.claims()
            except InvalidSignatureError as e:
                last = e
        raise InvalidSignatureError(
            "no known key successfully validated the token signature"
        ) from last

    def verify_batch(self, tokens: Sequence[str]) -> List[Any]:
        from ..runtime import prep

        telemetry.count("verify_batch.calls")
        telemetry.count("verify_batch.tokens", len(tokens))
        with telemetry.span("verify_batch.total"):
            if prep._load_native() is not None:
                return self._collect_batch(self._dispatch_batch(tokens))
            # non-native prep parses every serialization itself
            return self._verify_batch_objects(tokens)

    def verify_batch_async(self, tokens: Sequence[str],
                           raw: bool = False):
        """Dispatch a batch; returns collect() → per-token results.

        All device work (transfers + programs) is queued before this
        returns; the returned thunk blocks on the one materializing
        sync. Dispatching the NEXT batch before collecting the previous
        one keeps the host↔device wire busy during host-side prep —
        the 2-deep pipelining the serve layer and bench use.

        ``raw``: accepted tokens yield payload BYTES instead of claims
        dicts (see verify_batch_async_raw).
        """
        from ..runtime import prep

        telemetry.count("verify_batch.calls")
        telemetry.count("verify_batch.tokens", len(tokens))
        if prep._load_native() is None:
            results = self._verify_batch_objects(tokens)
            if raw:
                from .jose import b64url_decode

                for i, r in enumerate(results):
                    if not isinstance(r, Exception):
                        # the dict was built from exactly these bytes
                        if is_json_form(tokens[i]):
                            results[i] = parse_jws(tokens[i]).payload
                        else:
                            results[i] = b64url_decode(
                                tokens[i].split(".")[1])
            return lambda: results
        state = self._dispatch_batch(tokens)
        if raw:
            state["raw"] = True
        return lambda: self._collect_batch(state)

    def verify_batch_raw(self, tokens: Sequence[str]) -> List[Any]:
        """Like verify_batch, but verified tokens yield their RAW
        payload bytes — the exact claims JSON the IdP signed."""
        with telemetry.span("verify_batch.total"):
            return self.verify_batch_async(tokens, raw=True)()

    def verify_batch_async_raw(self, tokens: Sequence[str]):
        """verify_batch_async returning payload BYTES for accepted
        tokens instead of parsed dicts.

        The serve path's zero-reserialization mode: the worker would
        otherwise build 64k claims dicts (tape phase 2) only to
        json.dumps them straight back onto the wire — the signed
        payload bytes ARE that JSON. Signature semantics are identical,
        including the claims()-path rejection of verified signatures
        over non-object payloads (phase-1 validation runs during the
        device drain as a fast filter; json.loads stays authoritative
        on the tokens it flags, so accept/reject decisions are
        byte-identical to the dict path's).
        """
        return self.verify_batch_async(tokens, raw=True)

    def verify_stream(self, batches, depth: int = 2):
        """Pipelined verification of an iterable of token batches.

        Yields each batch's results in order while keeping up to
        ``depth`` batches in flight: batch k+1's host prep + packing +
        H2D overlap batch k's device drain. The throughput shape the
        reference's sequential loop (jwt/keyset.go:126-139 per token)
        cannot express.
        """
        from collections import deque

        inflight: deque = deque()
        for tokens in batches:
            inflight.append(self.verify_batch_async(tokens))
            if len(inflight) >= depth:
                yield inflight.popleft()()
        while inflight:
            yield inflight.popleft()()

    def _dispatch_batch(self, tokens: Sequence[str]) -> dict:
        """Phase 1: prep, bucket, pack, and queue ALL device work."""
        from ..runtime.native_binding import ALG_NAMES, prepare_batch_arrays

        # Epoch capture: ONE immutable table set serves this whole
        # batch (dispatch, collect, slow-path trials) — a swap_keys
        # landing mid-batch changes only batches dispatched after it.
        tables = self._tables
        # Wire-estimate span starts HERE: transfers drain while later
        # chunks are still being packed, so measuring from dispatch END
        # would overestimate the link (the sync would block briefly on
        # an already-drained wire).
        t_dispatch = time.perf_counter()
        # Occupancy plane: the whole batch counts as ONE dispatch-level
        # busy interval spanning dispatch start → collect end (work in
        # flight); the per-family enqueue slices below are recorded
        # with dispatch=False so they feed lane-share accounting
        # without inflating device.dispatches or idle-gap records.
        occ_t0 = _occupancy.begin()
        from .jose import normalize_batch

        tokens, specials = normalize_batch(tokens)
        with telemetry.span("prep.native"):
            pb = prepare_batch_arrays(tokens)
        n = pb.n
        results: List[Any] = [None] * n
        ok = pb.status == 0
        for i in np.nonzero(~ok)[0]:
            results[int(i)] = pb.error(int(i))
        special_payloads: Dict[int, bytes] = {}
        for i, sp in specials.items():
            # normalization verdicts outrank the ""-sentinel's prep
            # error: the exact parse exception, or (non-compactable
            # JSON form) the single-token trial verdict.
            if isinstance(sp, Exception):
                results[i] = sp
            else:
                try:
                    results[i] = self._verify_parsed_trial(sp, tables)
                    special_payloads[i] = sp.payload
                except Exception as e:  # noqa: BLE001 - per-token
                    results[i] = e

        slow: List[int] = []
        # Two-phase device interaction: every bucket's device work is
        # DISPATCHED here (transfers are asynchronous on the JAX
        # runtime — they queue on the wire and overlap the packing of
        # later chunks and the next batch's prep), then _collect_batch
        # materializes ONE concatenated verdict array. Hot families
        # (RS*, ES*) go through the PACKED path: one u8 record transfer
        # + one compiled program per chunk. Compute-heavy families
        # dispatch first so their device time overlaps the later
        # families' H2D transfers (docs/PERF.md).
        pending: List[tuple] = []
        packed_parts: List[Any] = []      # device [pad] bool arrays
        packed_meta: List[tuple] = []     # (n_slots, consume(arrs))
        stats = {"h2d": 0}                # record bytes this batch
        alg_ids = {name: i for i, name in enumerate(ALG_NAMES)}

        def run_family(alg_name: str, runner) -> None:
            idx = np.nonzero(ok & (pb.alg_id == alg_ids[alg_name]))[0]
            if len(idx) == 0:
                return
            runner(alg_name, idx)

        def run_rs(alg_name: str, idx: np.ndarray) -> None:
            with _occupancy.interval("rsa", dispatch=False):
                self._run_rsa_packed("rs", _RS[alg_name], idx, pb,
                                     packed_parts, packed_meta, pending,
                                     slow, results, stats, tables)

        def run_ps(alg_name: str, idx: np.ndarray) -> None:
            # Every PS* family rides the packed single-transfer path
            # with the device-side EMSA-PSS check (SHA-256 via
            # tpu/sha256.py, SHA-384/512 via the u32-pair engine in
            # tpu/sha512.py) — no EM bytes return to the host.
            with _occupancy.interval("rsa", dispatch=False):
                self._run_rsa_packed("ps", _PS[alg_name], idx, pb,
                                     packed_parts, packed_meta,
                                     pending, slow, results, stats,
                                     tables)

        def run_es(alg_name: str, idx: np.ndarray) -> None:
            with _occupancy.interval("ec", dispatch=False):
                self._run_ec_packed(alg_name, idx, pb, packed_parts,
                                    packed_meta, pending, slow, results,
                                    stats, tables)

        def run_ed(alg_name: str, idx: np.ndarray) -> None:
            with _occupancy.interval("ed", dispatch=False):
                self._run_ed_packed(idx, pb, packed_parts, packed_meta,
                                    pending, slow, results, stats,
                                    tables)

        # Post-quantum first: the deepest device programs (the
        # SLH-DSA hash forest, then the ML-DSA NTT network) go on the
        # wire before the cheaper families, so their device time
        # overlaps the later families' packing + transfers.
        for pset in sorted(tables.slhdsa_tables):
            idx = _mldsa_alg_indices(pb, ok, pset)
            if len(idx):
                with _occupancy.interval("slhdsa", dispatch=False):
                    self._run_slhdsa_packed(pset, idx, pb, pending,
                                            slow, stats, tables)
        for pset in sorted(tables.mldsa_tables):
            idx = _mldsa_alg_indices(pb, ok, pset)
            if len(idx):
                with _occupancy.interval("mldsa", dispatch=False):
                    self._run_mldsa_packed(pset, idx, pb, pending,
                                           slow, stats, tables)
        for a, crv in _ES.items():
            if crv in tables.ec_tables:
                run_family(a, run_es)
        if tables.ed_table is not None:
            run_family(algs.EdDSA, run_ed)
        if tables.rsa_tables:
            for a in _RS:
                run_family(a, run_rs)
            for a in _PS:
                run_family(a, run_ps)

        return dict(pb=pb, n=n, ok=ok, results=results, slow=slow,
                    pending=pending, packed_parts=packed_parts,
                    packed_meta=packed_meta, stats=stats,
                    t_dispatch=t_dispatch, occ_t0=occ_t0, tables=tables,
                    special_payloads=special_payloads)

    def _collect_batch(self, state: dict) -> List[Any]:
        """Phase 2: claims prefetch, materializing sync, verdicts."""
        pb, n, ok = state["pb"], state["n"], state["ok"]
        results, slow = state["results"], state["slow"]
        pending = state["pending"]
        packed_parts = state["packed_parts"]
        packed_meta = state["packed_meta"]

        raw = state.get("raw", False)
        with telemetry.span("device.sync"):
            if raw:
                # Raw mode replaces dict building with the phase-1-only
                # object check; the mask drives _finish_arrays for the
                # packed AND arrays paths, overlapping the drain.
                with telemetry.span("claims.validate"):
                    idxs = np.nonzero(ok)[0]
                    mask = np.zeros(n, bool)
                    mask[idxs] = pb.payload_object_ok(idxs)
                    pb._raw_ok = mask
            if packed_parts:
                import jax.numpy as jnp

                flat_dev = (jnp.concatenate(packed_parts)
                            if len(packed_parts) > 1 else packed_parts[0])
                # Overlap the host-side claims parsing with the device
                # drain (transfers + compute are still in flight; only
                # np.asarray below truly blocks). Every ok-status token
                # still has results[i] None here (only prep errors are
                # filled), so the index set is just the ok mask.
                if not raw:
                    with telemetry.span("claims.prefetch"):
                        pb.prefetch_claims(np.nonzero(ok)[0])
                flat = np.asarray(flat_dev)
                off = 0
                for n_slots, consume in packed_meta:
                    arrs = []
                    for sz in n_slots:
                        arrs.append(flat[off:off + sz])
                        off += sz
                    consume(arrs)
            for chunk, m, fin in pending:
                self._finish_arrays(chunk, fin()[:m], pb, results)

        # families without device tables (or EC/Ed engines not built):
        slow_set = set(slow)
        for j in range(n):
            if ok[j] and results[j] is None and j not in slow_set:
                slow_set.add(j)

        if slow_set:
            telemetry.count("cpu_fallback.tokens", len(slow_set))
            with telemetry.span("cpu_fallback"):
                for j in sorted(slow_set):
                    out = self._verify_one_parsed(pb.parsed(j),
                                                  state.get("tables"))
                    if raw and not isinstance(out, Exception):
                        # the oracle built the dict from these bytes
                        out = pb.payload_bytes(j)
                    results[j] = out
        if raw:
            # non-compactable JSON-form tokens verified on the object
            # path during dispatch: same raw contract, their bytes.
            for i, pay in state.get("special_payloads", {}).items():
                if not isinstance(results[i], Exception):
                    results[i] = pay
        self._observe_wire(state)
        # Device-surface decision records: families come straight from
        # the prep arrays (no token re-parsing on the hot path).
        if telemetry.active() is not None:
            from ..runtime.native_binding import ALG_NAMES

            fam_for = [_decision.family_for_alg(a) for a in ALG_NAMES]
            alg_id = pb.alg_id

            def fam(j: int) -> str:
                if not ok[j]:
                    return "unknown"
                aid = int(alg_id[j])
                if aid >= 0:
                    return fam_for[aid]
                # non-interned algs (ML-DSA et al.) carry raw bytes
                return _decision.family_for_alg(pb.alg(j))

            fams = [fam(j) for j in range(n)]
            t_dispatch = state.get("t_dispatch")
            _decision.record_batch(
                "tpu", results, families=fams,
                latency_s=(time.perf_counter() - t_dispatch
                           if t_dispatch is not None else None))
        # Close the batch's dispatch-level busy interval: dispatch
        # start → collect end is the window this batch held device
        # work in flight (the occupancy numerator).
        _occupancy.end("flight", state.get("occ_t0"))
        return results

    def _observe_wire(self, state: dict) -> None:
        """Update the observed effective H2D rate after one batch.

        Two candidate estimates, take the MAX:
        - bytes / (now - previous collect end): the bench's
          steady-state definition — right under pipelined load but
          poisoned by idle gaps between batches;
        - bytes / (now - this batch's dispatch start): spans up to
          ``depth`` intervals under pipelining (≈2× low) but contains
          no idle time.
        Under load the interval estimate wins; when idle the span
        estimate wins — so the EWMA never collapses from a quiet
        period and chunks don't shrink to the floor for no reason.
        """
        now = time.perf_counter()
        h2d = state.get("stats", {}).get("h2d", 0)
        t_dispatch = state.get("t_dispatch")
        last, self._last_collect_t = self._last_collect_t, now
        if not h2d or t_dispatch is None:
            return
        span = now - t_dispatch
        est = h2d / span if span > 0 else 0.0
        if last is not None and now > last:
            est = max(est, h2d / (now - last))
        if est <= 0:
            return
        prev = self._wire_bps
        self._wire_bps = est if prev is None else 0.5 * prev + 0.5 * est
        telemetry.observe("wire.est_mbps", self._wire_bps / (1 << 20))

    @staticmethod
    def _finish_arrays(chunk, okv, pb, results: List[Any]) -> None:
        """Write per-token verdicts for one array-path device chunk.

        Raw mode (``pb._raw_ok`` set by _collect_batch): accepted
        tokens yield their payload BYTES; a verified signature over a
        non-object payload raises through claims() so the error object
        is byte-identical to the dict path's.
        """
        raw_ok = getattr(pb, "_raw_ok", None)
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
                        # The phase-1 mask is only a FAST FILTER:
                        # json.loads stays authoritative (it accepts
                        # e.g. BOM-prefixed payloads the strict scan
                        # flags), exactly like the dict path.
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

    def _chunk_tokens(self, rec_width: int) -> int:
        """Tokens per packed chunk, pow-2 for shape reuse.

        Until the first batch completes: target ~5 MB transfers (the
        round-1 link probe's bandwidth sweet spot). After:
        target the TIME budget (CAP_TPU_CHUNK_BUDGET_MS, default 250)
        against the observed effective H2D rate, clamped to [1, 8] MB —
        a 6 MB/s trough then gets ~1.5 MB chunks (bounded per-chunk
        latency, finer pipeline overlap) while a fast link keeps large
        ones (VERDICT r3 #3)."""
        budget_bytes = 5 << 20
        bps = self._wire_bps
        if bps:
            budget_bytes = min(max(int(bps * self._chunk_budget_s),
                                   1 << 20), 8 << 20)
        c = 1024
        while c * 2 * rec_width <= budget_bytes:
            c *= 2
        return min(self._max_chunk, max(1024, c))

    def _run_rsa_packed(self, kind: str, hash_name: str,
                        idx: np.ndarray, pb,
                        packed_parts: List[Any],
                        packed_meta: List[tuple],
                        pending: List[tuple],
                        slow: List[int], results: List[Any],
                        stats: dict,
                        tables: Optional[_KeyTables] = None) -> None:
        from ..tpu import rsa as tpursa

        t = self._tables if tables is None else tables
        rows = pb.kid_rows(idx, t.kid_rsa_row)
        if t.n_rsa_keys == 1:
            rows = np.where(rows == -1, 0, rows)
        fast = rows >= 0
        slow.extend(int(i) for i in idx[~fast])
        idx = idx[fast]
        rows = rows[fast].astype(np.int32)
        if len(idx) == 0:
            return
        h_len = tpursa.HASH_LEN[hash_name]
        for cls, table in enumerate(t.rsa_tables):
            sel = (rows // _RSA_CLS_STRIDE) == cls
            if not sel.any():
                continue
            cls_idx = idx[sel]
            cls_rows = rows[sel] % _RSA_CLS_STRIDE
            if len(table.n_ints) > 255:    # kid row must fit a u8
                self._run_rsa_arrays(kind, hash_name, cls_idx, pb,
                                     pending, slow, stats, cls=cls,
                                     tables=t)
                continue
            width = 2 * table.k
            chunk_n = self._chunk_tokens(width + h_len
                                         + tpursa.RS_REC_EXTRA)
            for lo in range(0, len(cls_idx), chunk_n):
                chunk = cls_idx[lo: lo + chunk_n]
                crows = cls_rows[lo: lo + chunk_n]
                m = len(chunk)
                pad = _pad_size(m, chunk_n)
                telemetry.count(f"device.{kind}.tokens", m)
                _pad_telemetry(kind, m, pad)
                with telemetry.span(f"dispatch.{kind}.{hash_name}"):
                    rec = _pack_rsa_record(pb, table, kind, hash_name,
                                           chunk, crows, pad)
                    telemetry.count("h2d.bytes", rec.nbytes)
                    stats["h2d"] += rec.nbytes
                    if kind == "rs":
                        ok_dev = tpursa.verify_rs_packed_pending(
                            table, rec, hash_name, mesh=self._mesh)
                    else:
                        ok_dev = tpursa.verify_ps_packed_pending(
                            table, rec, hash_name, mesh=self._mesh)
                packed_parts.append(ok_dev)

                def consume(arrs, chunk=chunk, m=m):
                    self._finish_arrays(chunk, arrs[0][:m], pb, results)

                packed_meta.append(([pad], consume))

    def _run_ec_packed(self, alg: str, idx: np.ndarray, pb,
                       packed_parts: List[Any],
                       packed_meta: List[tuple],
                       pending: List[tuple],
                       slow: List[int], results: List[Any],
                       stats: dict,
                       tables: Optional[_KeyTables] = None) -> None:
        from ..tpu import ec as tpuec
        from ..tpu.rsa import HASH_LEN

        t = self._tables if tables is None else tables
        crv = _ES[alg]
        table = t.ec_tables[crv]
        if len(table.keys) > 255:
            return self._run_ec_arrays(alg, idx, pb, pending, slow,
                                       stats, tables=t)
        hash_len = HASH_LEN[algs.HASH_FOR_ALG[alg]]
        rows = pb.kid_rows(idx, t.kid_ec_row[crv])
        if len(table.keys) == 1:
            rows = np.where(rows == -1, 0, rows)
        fast = rows >= 0
        slow.extend(int(i) for i in idx[~fast])
        idx = idx[fast]
        rows = rows[fast].astype(np.int32)
        if len(idx) == 0:
            return
        cb = table.curve.coord_bytes
        width = 2 * cb
        chunk_n = self._chunk_tokens(width + hash_len + tpuec.ES_REC_EXTRA)
        for lo in range(0, len(idx), chunk_n):
            chunk = idx[lo: lo + chunk_n]
            crows = rows[lo: lo + chunk_n]
            m = len(chunk)
            pad = _pad_size(m, chunk_n)
            telemetry.count("device.es.tokens", m)
            _pad_telemetry("es", m, pad)
            with telemetry.span(f"dispatch.es.{crv}"):
                rec = _pack_es_record(pb, table, chunk, crows,
                                      hash_len, pad)
                telemetry.count("h2d.bytes", rec.nbytes)
                stats["h2d"] += rec.nbytes
                ok_dev, deg_dev = tpuec.verify_es_packed_pending(
                    table, rec, hash_len, mesh=self._mesh,
                    ladder=self._ec_ladder)
            packed_parts.append(ok_dev)
            packed_parts.append(deg_dev)

            def consume(arrs, chunk=chunk, m=m, rec=rec, crows=crows,
                        table=table, cb=cb, hash_len=hash_len):
                okv = np.array(arrs[0][:m])
                deg = arrs[1][:m]
                for j in np.nonzero(deg)[0]:
                    okv[j] = tpuec._cpu_verify_one(
                        table, int(crows[j]),
                        rec[j, : 2 * cb].tobytes(),
                        rec[j, 2 * cb: 2 * cb + hash_len].tobytes())
                self._finish_arrays(chunk, okv, pb, results)

            packed_meta.append(([pad, pad], consume))

    def _run_mldsa_packed(self, pset: str, idx: np.ndarray, pb,
                          pending: List[tuple],
                          slow: List[int], stats: dict,
                          tables: Optional[_KeyTables] = None) -> None:
        """One ML-DSA parameter set through the two-phase device path.

        Default (``mldsa.fused_enabled()``): the FUSED single-round-
        trip engine — host work per token is byte decode ONLY (length/
        range/hint gates, lane packing); μ, SampleInBall, the NTT
        network, w1Encode, and the c̃ compare all run in one device
        dispatch (batched Keccak lanes), and the verdict closure just
        materializes bits. Zero per-token host SHAKE — span/counter-
        pinned by tests/test_mldsa_fused.py. With the fused path off
        (CAP_TPU_MLDSA_FUSED=0) the r11 two-phase split applies: host
        μ/c̃ hashing around the device NTT. Tokens whose kid cannot be
        routed fall to the CPU oracle — which for ML-DSA is the same
        pure-int ``py_verify`` math, so verdict parity is structural.
        """
        from ..tpu import mldsa as tpumldsa

        t = self._tables if tables is None else tables
        table = t.mldsa_tables[pset]
        p = table.params
        rows = pb.kid_rows(idx, t.kid_mldsa_row[pset])
        if len(table.keys) == 1:
            rows = np.where(rows == -1, 0, rows)
        fast = rows >= 0
        slow.extend(int(i) for i in idx[~fast])
        idx = idx[fast]
        rows = rows[fast].astype(np.int32)
        if len(idx) == 0:
            return
        # Per-token device bytes: z lanes (l·256 u32) + c lanes
        # (256 u32) + hint lanes (k·256 u8) + the key row.
        bpt = (p.l + 1) * N_COEFF * 4 + p.k * N_COEFF + 4
        chunk_n = self._chunk_tokens(max(1, bpt // 2))
        for lo in range(0, len(idx), chunk_n):
            chunk = idx[lo: lo + chunk_n]
            crows = rows[lo: lo + chunk_n]
            m = len(chunk)
            pad = _pad_size(m, chunk_n)
            sigs = [pb.signature(int(j)) for j in chunk]
            msgs = [pb.signing_input(int(j)) for j in chunk]
            telemetry.count("device.mldsa.tokens", m)
            _pad_telemetry("mldsa", m, pad)
            h2d = pad * bpt
            telemetry.count("h2d.bytes", h2d)
            stats["h2d"] += h2d
            with telemetry.span(f"dispatch.mldsa.{pset}"):
                verify = (tpumldsa.verify_mldsa_fused_pending
                          if tpumldsa.fused_enabled()
                          else tpumldsa.verify_mldsa_pending)
                fin = verify(table, sigs, msgs, crows, pad=pad,
                             mesh=self._mesh)
            pending.append((chunk, m, fin))

    def _run_slhdsa_packed(self, pset: str, idx: np.ndarray, pb,
                           pending: List[tuple],
                           slow: List[int], stats: dict,
                           tables: Optional[_KeyTables] = None) -> None:
        """One SLH-DSA parameter set through the two-phase device
        path: host decode (sig split + the single H_msg SHAKE + ADRS
        lane precompute) at dispatch, the whole FORS/hypertree hash
        forest queued on the device, verdict bits at the batch-wide
        sync. Unroutable kids fall to the CPU oracle — the same
        hashlib math, so verdict parity is structural."""
        from ..tpu import slhdsa as tpuslh

        t = self._tables if tables is None else tables
        table = t.slhdsa_tables[pset]
        p = table.params
        rows = pb.kid_rows(idx, t.kid_slhdsa_row[pset])
        if len(table.keys) == 1:
            rows = np.where(rows == -1, 0, rows)
        fast = rows >= 0
        slow.extend(int(i) for i in idx[~fast])
        idx = idx[fast]
        rows = rows[fast].astype(np.int32)
        if len(idx) == 0:
            return
        # Per-token device bytes ≈ the signature's hash values plus
        # ~500 precomputed 32-byte ADRS words as interleaved lanes.
        bpt = p.sig_size + 32 * (p.k * (p.a + 1) + 1
                                 + p.d * (p.wlen + p.hp + 1))
        chunk_n = self._chunk_tokens(max(1, bpt // 2))
        for lo in range(0, len(idx), chunk_n):
            chunk = idx[lo: lo + chunk_n]
            crows = rows[lo: lo + chunk_n]
            m = len(chunk)
            # Pow-2 padding with a 16-row floor instead of the global
            # _MIN_BUCKET: one SLH-DSA lane-row is ~300x the device
            # work of a classical record, so at small batches the
            # fill-ratio waste dominates what recompile amortization
            # saves (device.slhdsa.fill_ratio tells the story).
            pad = 16
            while pad < m:
                pad *= 2
            pad = min(pad, chunk_n)
            sigs = [pb.signature(int(j)) for j in chunk]
            msgs = [pb.signing_input(int(j)) for j in chunk]
            telemetry.count("device.slhdsa.tokens", m)
            _pad_telemetry("slhdsa", m, pad)
            h2d = pad * bpt
            telemetry.count("h2d.bytes", h2d)
            stats["h2d"] += h2d
            with telemetry.span(f"dispatch.slhdsa.{pset}"):
                fin = tpuslh.verify_slhdsa_pending(
                    table, sigs, msgs, crows, pad=pad, mesh=self._mesh)
            pending.append((chunk, m, fin))

    def _run_rsa_arrays(self, kind: str, hash_name: str, idx: np.ndarray,
                        pb, pending: List[tuple],
                        slow: List[int], stats: dict,
                        cls: Optional[int] = None,
                        tables: Optional[_KeyTables] = None) -> None:
        from ..tpu import rsa as tpursa

        t = self._tables if tables is None else tables
        rows = pb.kid_rows(idx, t.kid_rsa_row)
        if t.n_rsa_keys == 1:
            # single-key family: kid-less tokens have exactly one
            # candidate — dispatch them to the device (row 0), matching
            # the object path's single-candidate routing
            rows = np.where(rows == -1, 0, rows)
        fast = rows >= 0
        slow.extend(int(i) for i in idx[~fast])
        idx = idx[fast]
        rows = rows[fast].astype(np.int32)
        if len(idx) == 0:
            return
        for c, table in enumerate(t.rsa_tables):
            if cls is not None and c != cls:
                continue
            sel = (rows // _RSA_CLS_STRIDE) == c
            if not sel.any():
                continue
            cls_idx = idx[sel]
            cls_rows = rows[sel] % _RSA_CLS_STRIDE
            width = 2 * table.k
            for lo in range(0, len(cls_idx), self._max_chunk):
                chunk = cls_idx[lo: lo + self._max_chunk]
                crows = cls_rows[lo: lo + self._max_chunk]
                m = len(chunk)
                pad = _pad_size(m, self._max_chunk)
                sig_mat = np.zeros((pad, width), np.uint8)
                sig_mat[:m] = pb.sig_matrix(chunk, width)
                sig_lens = np.zeros(pad, np.int64)
                sig_lens[:m] = pb.sig_len[chunk]
                hash_mat = np.zeros((pad, 64), np.uint8)
                hash_mat[:m] = pb.digest[chunk]
                key_idx = np.zeros(pad, np.int32)
                key_idx[:m] = crows
                telemetry.count(f"device.{kind}.tokens", m)
                _pad_telemetry(kind, m, pad)
                h2d = (sig_mat.nbytes + sig_lens.nbytes
                       + hash_mat.nbytes + key_idx.nbytes)
                telemetry.count("h2d.bytes", h2d)
                stats["h2d"] += h2d
                with telemetry.span(f"dispatch.{kind}.{hash_name}"):
                    if kind == "rs":
                        fin = tpursa.verify_pkcs1v15_arrays_pending(
                            table, sig_mat, sig_lens, hash_mat,
                            hash_name, key_idx)
                    else:
                        fin = tpursa.verify_pss_arrays_pending(
                            table, sig_mat, sig_lens, hash_mat,
                            hash_name, key_idx)
                pending.append((chunk, m, fin))

    def _run_ec_arrays(self, alg: str, idx: np.ndarray, pb,
                       pending: List[tuple], slow: List[int],
                       stats: dict,
                       tables: Optional[_KeyTables] = None) -> None:
        from ..tpu import ec as tpuec
        from ..tpu.rsa import HASH_LEN

        t = self._tables if tables is None else tables
        crv = _ES[alg]
        table = t.ec_tables[crv]
        hash_len = HASH_LEN[algs.HASH_FOR_ALG[alg]]
        rows = pb.kid_rows(idx, t.kid_ec_row[crv])
        if len(table.keys) == 1:
            # kid-less tokens have exactly one candidate on this curve
            rows = np.where(rows == -1, 0, rows)
        fast = rows >= 0
        slow.extend(int(i) for i in idx[~fast])
        idx = idx[fast]
        rows = rows[fast].astype(np.int32)
        if len(idx) == 0:
            return
        width = 2 * table.coord_bytes
        for lo in range(0, len(idx), self._max_chunk):
            chunk = idx[lo: lo + self._max_chunk]
            crows = rows[lo: lo + self._max_chunk]
            m = len(chunk)
            pad = _pad_size(m, self._max_chunk)
            sig_mat = np.zeros((pad, width), np.uint8)
            sig_mat[:m] = pb.sig_matrix(chunk, width)
            sig_lens = np.zeros(pad, np.int64)
            sig_lens[:m] = pb.sig_len[chunk]
            hash_mat = np.zeros((pad, 64), np.uint8)
            hash_mat[:m] = pb.digest[chunk]
            key_idx = np.zeros(pad, np.int32)
            key_idx[:m] = crows
            telemetry.count("device.es.tokens", m)
            _pad_telemetry("es", m, pad)
            h2d = (sig_mat.nbytes + sig_lens.nbytes + hash_mat.nbytes
                   + key_idx.nbytes)
            telemetry.count("h2d.bytes", h2d)
            stats["h2d"] += h2d
            with telemetry.span(f"dispatch.es.{crv}"):
                fin = tpuec.verify_ecdsa_arrays_pending(
                    table, sig_mat, sig_lens, hash_mat, hash_len,
                    key_idx, ladder=self._ec_ladder)
            pending.append((chunk, m, fin))

    def _run_ed_packed(self, idx: np.ndarray, pb,
                       packed_parts: List[Any],
                       packed_meta: List[tuple],
                       pending: List[tuple],
                       slow: List[int], results: List[Any],
                       stats: dict,
                       tables: Optional[_KeyTables] = None) -> None:
        from ..tpu import ed25519 as tpued

        t = self._tables if tables is None else tables
        table = t.ed_table
        if len(table.keys) > 255:
            return self._run_ed_arrays(idx, pb, pending, slow, stats,
                                       tables=t)
        rows = pb.kid_rows(idx, t.kid_ed_row)
        if len(table.keys) == 1:
            rows = np.where(rows == -1, 0, rows)
        fast = rows >= 0
        slow.extend(int(i) for i in idx[~fast])
        idx = idx[fast]
        rows = rows[fast].astype(np.int32)
        if len(idx) == 0:
            return
        chunk_n = self._chunk_tokens(64 + 32 + tpued.ED_REC_EXTRA)
        for lo in range(0, len(idx), chunk_n):
            chunk = idx[lo: lo + chunk_n]
            crows = rows[lo: lo + chunk_n]
            m = len(chunk)
            pad = _pad_size(m, chunk_n)
            sigs = [pb.signature(int(j)) for j in chunk]
            msgs = [pb.signing_input(int(j)) for j in chunk]
            fill = pad - m
            sigs += [b""] * fill
            msgs += [b""] * fill
            key_idx = np.concatenate([crows, np.zeros(fill, np.int32)])
            telemetry.count("device.ed.tokens", m)
            _pad_telemetry("ed", m, pad)
            with telemetry.span("dispatch.ed25519"):
                rec = tpued.ed_packed_records(table, sigs, msgs, key_idx)
                telemetry.count("h2d.bytes", rec.nbytes)
                stats["h2d"] += rec.nbytes
                ok_dev = tpued.verify_ed_packed_pending(
                    table, rec, mesh=self._mesh)
            packed_parts.append(ok_dev)

            def consume(arrs, chunk=chunk, m=m):
                self._finish_arrays(chunk, arrs[0][:m], pb, results)

            packed_meta.append(([pad], consume))

    def _run_ed_arrays(self, idx: np.ndarray, pb,
                       pending: List[tuple], slow: List[int],
                       stats: dict,
                       tables: Optional[_KeyTables] = None) -> None:
        from ..tpu import ed25519 as tpued

        t = self._tables if tables is None else tables
        table = t.ed_table
        rows = pb.kid_rows(idx, t.kid_ed_row)
        if len(table.keys) == 1:
            # kid-less tokens have exactly one EdDSA candidate
            rows = np.where(rows == -1, 0, rows)
        fast = rows >= 0
        slow.extend(int(i) for i in idx[~fast])
        idx = idx[fast]
        rows = rows[fast].astype(np.int32)
        if len(idx) == 0:
            return
        for lo in range(0, len(idx), self._max_chunk):
            chunk = idx[lo: lo + self._max_chunk]
            crows = rows[lo: lo + self._max_chunk]
            m = len(chunk)
            pad = _pad_size(m, self._max_chunk)
            sigs = [pb.signature(int(j)) for j in chunk]
            msgs = [pb.signing_input(int(j)) for j in chunk]
            fill = pad - m
            sigs += [b"\x00" * 64] * fill
            msgs += [b""] * fill
            key_idx = np.concatenate([crows, np.zeros(fill, np.int32)])
            telemetry.count("device.ed.tokens", m)
            _pad_telemetry("ed", m, pad)
            h2d = (sum(len(x) for x in sigs)
                   + sum(len(x) for x in msgs) + key_idx.nbytes)
            telemetry.count("h2d.bytes", h2d)
            stats["h2d"] += h2d
            with telemetry.span("dispatch.ed25519"):
                fin = tpued.verify_ed25519_batch_pending(
                    table, sigs, msgs, key_idx)
            pending.append((chunk, m, fin))

    def _verify_one_parsed(self, p,
                           tables: Optional[_KeyTables] = None) -> Any:
        """CPU trial verification of one parsed token (slow path)."""
        t = self._tables if tables is None else tables
        if not self._cpu_fallback:
            return InvalidParameterError(
                "token cannot be dispatched to the device engine and "
                "CPU fallback is disabled")
        last: Optional[Exception] = None
        for i in self._candidate_indices(p, t):
            try:
                verify_parsed(p, t.jwks[i].key)
                try:
                    return p.claims()
                except MalformedTokenError as e:
                    return e
            except InvalidSignatureError as e:
                last = e
        err = InvalidSignatureError(
            "no known key successfully validated the token signature")
        err.__cause__ = last
        return err

    def _verify_batch_objects(self, tokens: Sequence[str]) -> List[Any]:
        n = len(tokens)
        tables = self._tables        # one epoch serves this batch
        results: List[Any] = [None] * n
        parsed_list: List[Optional[ParsedJWS]] = [None] * n
        key_for: List[Optional[int]] = [None] * n

        from ..runtime import prep  # C++ when built, Python fallback

        with telemetry.span("prep"):
            prepped = prep.prepare_batch(tokens)

        for j, p in enumerate(prepped):
            if isinstance(p, Exception):
                results[j] = p
                continue
            parsed_list[j] = p
            cands = self._candidate_indices(p, tables)
            if len(cands) == 1:
                key_for[j] = cands[0]
            elif not cands:
                results[j] = InvalidSignatureError(
                    "no known key successfully validated the token signature"
                )
            # >1 candidate (ambiguous kid / no kid): CPU trial path below.

        buckets: Dict[tuple, List[int]] = {}
        for j, p in enumerate(parsed_list):
            if results[j] is not None or p is None:
                continue
            if key_for[j] is None:
                buckets.setdefault(("cpu",), []).append(j)
            elif p.alg in _RS and tables.rsa_tables:
                buckets.setdefault(("rs", _RS[p.alg]), []).append(j)
            elif p.alg in _PS and tables.rsa_tables:
                buckets.setdefault(("ps", _PS[p.alg]), []).append(j)
            elif p.alg in _ES and _ES[p.alg] in tables.ec_tables:
                buckets.setdefault(("es", p.alg), []).append(j)
            elif p.alg == algs.EdDSA and tables.ed_table is not None:
                buckets.setdefault(("ed",), []).append(j)
            elif p.alg in tables.mldsa_tables:
                buckets.setdefault(("mldsa", p.alg), []).append(j)
            elif p.alg in tables.slhdsa_tables:
                buckets.setdefault(("slhdsa", p.alg), []).append(j)
            else:
                buckets.setdefault(("cpu",), []).append(j)

        for kind, idxs in buckets.items():
            if kind[0] == "cpu":
                self._run_cpu(idxs, parsed_list, results, tables)
            elif kind[0] in ("rs", "ps"):
                self._run_rsa(kind[0], kind[1], idxs, parsed_list,
                              key_for, results, tables)
            elif kind[0] == "es":
                self._run_ec(kind[1], idxs, parsed_list, key_for,
                             results, tables)
            elif kind[0] == "mldsa":
                self._run_mldsa(kind[1], idxs, parsed_list, key_for,
                                results, tables)
            elif kind[0] == "slhdsa":
                self._run_slhdsa(kind[1], idxs, parsed_list, key_for,
                                 results, tables)
            else:
                self._run_ed(idxs, parsed_list, key_for, results,
                             tables)
        if telemetry.active() is not None:
            fams = [_decision.family_for_alg(p.alg) if p is not None
                    else "unknown" for p in parsed_list]
            _decision.record_batch("tpu", results, families=fams)
        return results

    # -- bucket runners ----------------------------------------------------

    def _finish(self, idxs, parsed_list, ok_mask, results):
        for j, ok in zip(idxs, ok_mask):
            if ok:
                try:
                    results[j] = parsed_list[j].claims()
                except MalformedTokenError as e:
                    results[j] = e
            else:
                results[j] = InvalidSignatureError(
                    "no known key successfully validated the token signature"
                )

    def _run_cpu(self, idxs, parsed_list, results, tables=None):
        t = self._tables if tables is None else tables
        if not self._cpu_fallback:
            for j in idxs:
                results[j] = InvalidParameterError(
                    "token cannot be dispatched to the device engine and "
                    "CPU fallback is disabled"
                )
            return
        for j in idxs:
            p = parsed_list[j]
            last: Optional[Exception] = None
            done = False
            for i in self._candidate_indices(p, t):
                try:
                    verify_parsed(p, t.jwks[i].key)
                    results[j] = p.claims()
                    done = True
                    break
                except InvalidSignatureError as e:
                    last = e
            if not done:
                err = InvalidSignatureError(
                    "no known key successfully validated the token signature"
                )
                err.__cause__ = last
                results[j] = err

    def _hashes(self, idxs, parsed_list, hash_name):
        import hashlib

        out = []
        for j in idxs:
            p = parsed_list[j]
            # native-prepped tokens carry the digest already (computed in
            # multithreaded C++ during prepare_batch)
            pre = getattr(p, "digest", None)
            d = pre() if callable(pre) else None
            out.append(d if d else
                       hashlib.new(hash_name, p.signing_input).digest())
        return out

    def _run_rsa(self, kind, hash_name, idxs, parsed_list, key_for,
                 results, tables=None):
        from ..tpu import rsa as tpursa

        t = self._tables if tables is None else tables
        by_cls: Dict[int, List[int]] = {}
        for j in idxs:
            by_cls.setdefault(
                t.rsa_rows[key_for[j]] // _RSA_CLS_STRIDE, []).append(j)
        for cls, cidxs in sorted(by_cls.items()):
            table = t.rsa_tables[cls]
            for lo in range(0, len(cidxs), self._max_chunk):
                chunk = cidxs[lo: lo + self._max_chunk]
                pad = _pad_size(len(chunk), self._max_chunk)
                sigs = [parsed_list[j].signature for j in chunk]
                hashes_ = self._hashes(chunk, parsed_list, hash_name)
                rows = [t.rsa_rows[key_for[j]] % _RSA_CLS_STRIDE
                        for j in chunk]
                fill = pad - len(chunk)
                sigs += [b""] * fill
                hashes_ += [b"\x00" * tpursa.HASH_LEN[hash_name]] * fill
                key_idx = np.asarray(rows + [0] * fill, np.int32)
                if kind == "rs":
                    ok = tpursa.verify_pkcs1v15_batch(
                        table, sigs, hashes_, hash_name, key_idx)
                else:
                    ok = tpursa.verify_pss_batch(
                        table, sigs, hashes_, hash_name, key_idx)
                self._finish(chunk, parsed_list, ok[: len(chunk)],
                             results)

    def _run_ec(self, alg, idxs, parsed_list, key_for, results,
                tables=None):
        from ..tpu import ec as tpuec
        from ..tpu.rsa import HASH_LEN

        t = self._tables if tables is None else tables
        crv = _ES[alg]
        table = t.ec_tables[crv]
        hash_name = algs.HASH_FOR_ALG[alg]
        for lo in range(0, len(idxs), self._max_chunk):
            chunk = idxs[lo: lo + self._max_chunk]
            pad = _pad_size(len(chunk), self._max_chunk)
            sigs = [parsed_list[j].signature for j in chunk]
            hashes_ = self._hashes(chunk, parsed_list, hash_name)
            rows = [t.ec_rows[crv][key_for[j]] for j in chunk]
            fill = pad - len(chunk)
            sigs += [b"\x00" * (2 * table.coord_bytes)] * fill
            hashes_ += [b"\x00" * HASH_LEN[hash_name]] * fill
            key_idx = np.asarray(rows + [0] * fill, np.int32)
            ok = tpuec.verify_ecdsa_batch(table, sigs, hashes_, key_idx)
            self._finish(chunk, parsed_list, ok[: len(chunk)], results)

    def _run_mldsa(self, alg, idxs, parsed_list, key_for, results,
                   tables=None):
        from ..tpu import mldsa as tpumldsa

        t = self._tables if tables is None else tables
        table = t.mldsa_tables[alg]
        p = table.params
        chunk_n = self._chunk_tokens(
            max(1, ((p.l + 1) * N_COEFF * 4 + p.k * N_COEFF + 4) // 2))
        for lo in range(0, len(idxs), chunk_n):
            chunk = idxs[lo: lo + chunk_n]
            pad = _pad_size(len(chunk), chunk_n)
            sigs = [parsed_list[j].signature for j in chunk]
            msgs = [parsed_list[j].signing_input for j in chunk]
            rows = [t.mldsa_rows[alg][key_for[j]] for j in chunk]
            telemetry.count("device.mldsa.tokens", len(chunk))
            _pad_telemetry("mldsa", len(chunk), pad)
            with telemetry.span(f"dispatch.mldsa.{alg}"):
                verify = (tpumldsa.verify_mldsa_fused_pending
                          if tpumldsa.fused_enabled()
                          else tpumldsa.verify_mldsa_pending)
                ok = verify(table, sigs, msgs,
                            np.asarray(rows, np.int32), pad=pad,
                            mesh=self._mesh)()
            self._finish(chunk, parsed_list, ok[: len(chunk)], results)

    def _run_slhdsa(self, alg, idxs, parsed_list, key_for, results,
                    tables=None):
        from ..tpu import slhdsa as tpuslh

        t = self._tables if tables is None else tables
        table = t.slhdsa_tables[alg]
        p = table.params
        chunk_n = self._chunk_tokens(max(1, p.sig_size // 2))
        for lo in range(0, len(idxs), chunk_n):
            chunk = idxs[lo: lo + chunk_n]
            pad = 16
            while pad < len(chunk):
                pad *= 2
            pad = min(pad, chunk_n)
            sigs = [parsed_list[j].signature for j in chunk]
            msgs = [parsed_list[j].signing_input for j in chunk]
            rows = [t.slhdsa_rows[alg][key_for[j]] for j in chunk]
            telemetry.count("device.slhdsa.tokens", len(chunk))
            _pad_telemetry("slhdsa", len(chunk), pad)
            with telemetry.span(f"dispatch.slhdsa.{alg}"):
                ok = tpuslh.verify_slhdsa_pending(
                    table, sigs, msgs, np.asarray(rows, np.int32),
                    pad=pad, mesh=self._mesh)()
            self._finish(chunk, parsed_list, ok[: len(chunk)], results)

    def _run_ed(self, idxs, parsed_list, key_for, results,
                tables=None):
        from ..tpu import ed25519 as tpued

        t = self._tables if tables is None else tables
        table = t.ed_table
        for lo in range(0, len(idxs), self._max_chunk):
            chunk = idxs[lo: lo + self._max_chunk]
            pad = _pad_size(len(chunk), self._max_chunk)
            sigs = [parsed_list[j].signature for j in chunk]
            msgs = [parsed_list[j].signing_input for j in chunk]
            rows = [t.ed_rows[key_for[j]] for j in chunk]
            fill = pad - len(chunk)
            sigs += [b"\x00" * 64] * fill
            msgs += [b""] * fill
            key_idx = np.asarray(rows + [0] * fill, np.int32)
            ok = tpued.verify_ed25519_batch(table, sigs, msgs, key_idx)
            self._finish(chunk, parsed_list, ok[: len(chunk)], results)


class TPURemoteKeySet(KeySet):
    """Remote-JWKS-backed accelerated KeySet (key-rotation aware).

    The device analog of the reference's remote JWKS path
    (jwt/keyset.go:109-122 → coreos RemoteKeySet): keys come from a
    JWKS endpoint and live in device tables; a batch whose tokens
    present UNKNOWN kids triggers at most one refetch + table rebuild,
    and failed signatures against known kids never hit the network
    (forged tokens must not amplify into IdP fetches).

    Table rebuilds re-run the host-side window-table precompute, so
    rotation is expected to be rare relative to batch volume.
    """

    def __init__(self, jwks_url: str, jwks_ca_pem: Optional[str] = None,
                 max_chunk: int = 32768,
                 min_refresh_interval: float = 10.0, mesh=None):
        from .keyset import JSONWebKeySet

        self._remote = JSONWebKeySet(jwks_url, jwks_ca_pem)
        self._max_chunk = max_chunk
        self._min_refresh = min_refresh_interval
        self._mesh = mesh          # propagated to every table rebuild
        self._ks: Optional[TPUBatchKeySet] = None
        self._kids: set = set()
        self._last_refresh = 0.0
        import threading

        self._lock = threading.Lock()

    def _ensure(self, refresh: bool = False) -> TPUBatchKeySet:
        import time

        # Serialize fetch + rebuild: concurrent rotation triggers must
        # not double-fetch or double-build the device tables. Unknown
        # random kids (attacker-controlled) are additionally bounded by
        # a refresh cooldown AND a content check: an unchanged key set
        # never rebuilds tables.
        with self._lock:
            if self._ks is not None and refresh:
                if time.monotonic() - self._last_refresh < self._min_refresh:
                    return self._ks
            elif self._ks is not None:
                return self._ks
            if refresh:
                # Stamp BEFORE the fetch: a failing IdP (slow connect
                # timeout) must also respect the cooldown, or an
                # attacker feeding unknown kids makes every batch block
                # on a doomed fetch while holding the lock.
                self._last_refresh = time.monotonic()
            jwks = self._remote.keys(refresh=refresh)
            kids = {j.kid for j in jwks if j.kid}
            if self._ks is None:
                self._ks = TPUBatchKeySet(jwks, max_chunk=self._max_chunk,
                                          mesh=self._mesh)
                self._kids = kids
            elif kids != self._kids:
                # Hot swap (keyplane epoch bump) instead of a from-
                # scratch keyset: in-flight batches finish on their
                # tables, and the wire-rate EWMA survives the rotation.
                self._ks.swap_keys(jwks)
                self._kids = kids
            return self._ks

    def verify_signature(self, token: str) -> Dict[str, Any]:
        ks = self._ensure()
        try:
            return ks.verify_signature(token)
        except InvalidSignatureError:
            parsed = parse_jws(token)
            if parsed.kid is not None and parsed.kid not in self._kids:
                return self._ensure(refresh=True).verify_signature(token)
            raise

    def verify_batch(self, tokens: Sequence[str]) -> List[Any]:
        return self._verify_rotation_aware(tokens, raw=False)

    def verify_batch_raw(self, tokens: Sequence[str]) -> List[Any]:
        """Raw-claims analog of ``verify_batch`` (the serve default):
        accepted tokens yield their signed payload BYTES, rejects keep
        the dict path's error classes, and the same at-most-one
        rotation refetch applies."""
        return self._verify_rotation_aware(tokens, raw=True)

    def _verify_rotation_aware(self, tokens: Sequence[str],
                               raw: bool) -> List[Any]:
        ks = self._ensure()
        call = ks.verify_batch_raw if raw else ks.verify_batch
        results = call(tokens)
        missed: List[int] = []
        for i, r in enumerate(results):
            if not isinstance(r, InvalidSignatureError):
                continue
            try:
                parsed = parse_jws(tokens[i])
            except Exception:  # noqa: BLE001 - malformed keeps its error
                continue
            if parsed.kid is not None and parsed.kid not in self._kids:
                missed.append(i)
        if missed:
            telemetry.count("jwks.rotation_refetch")
            # A failed refetch (IdP hiccup, network error) must not
            # discard the whole batch's verdicts: behind AdaptiveBatcher
            # one attacker token with a random kid would otherwise fan
            # the exception out to every coalesced caller. Keep the
            # original per-token InvalidSignatureError results instead.
            try:
                ks = self._ensure(refresh=True)
                retry_call = ks.verify_batch_raw if raw else \
                    ks.verify_batch
                retry = retry_call([tokens[i] for i in missed])
            except Exception:  # noqa: BLE001 - network/IdP failure
                telemetry.count("jwks.rotation_refetch_failed")
            else:
                for i, r in zip(missed, retry):
                    results[i] = r
        return results
