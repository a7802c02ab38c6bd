"""Mesh scaling structure: the sharded step must actually shard.

VERDICT r4 #7: when real multi-chip hardware appears the scaling
number should be one command (tools/profile_families.py --mesh N);
what must be pinned NOW, on the virtual CPU mesh, is the STRUCTURE —
each device receives exactly its n/N slice of the batch and the
verdict comes back sharded the same way. A regression that silently
replicates the batch (every chip doing all tokens) or inserts a
stray all-gather would pass the existing accept/reject mesh tests
while destroying scaling; these assertions catch it.
"""

from __future__ import annotations

import numpy as np
import pytest

pytest.importorskip("cryptography", reason=(
    "module-wide fixtures need the cryptography package: "
    "clean skip instead of a collection ERROR on crypto-less hosts"))


import jax

from cap_tpu import testing as captest
from cap_tpu.jwt import algs
from cap_tpu.jwt.jwk import JWK
from cap_tpu.parallel.mesh import DP_AXIS, make_mesh

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs the virtual multi-device mesh")


def _shard_sizes(arr):
    """Batch-axis length of every addressable shard of a device array."""
    return sorted(s.data.shape[-1] if s.data.ndim else 0
                  for s in arr.addressable_shards)


@pytest.mark.parametrize("alg,n_dev", [(algs.ES256, 4), (algs.RS256, 8),
                                       (algs.EdDSA, 4), (algs.PS256, 4)])
def test_packed_verdicts_shard_batch_axis(alg, n_dev):
    from cap_tpu.jwt.tpu_keyset import TPUBatchKeySet, resident_dispatchers

    if len(jax.devices()) < n_dev:
        pytest.skip(f"needs {n_dev} devices")
    mesh = make_mesh(n_dev)
    priv, pub = captest.generate_keys(alg)
    ks = TPUBatchKeySet([JWK(pub, kid="k0")], mesh=mesh)
    toks = [captest.sign_jwt(priv, alg, captest.default_claims(sub=f"s{i}"),
                             kid="k0") for i in range(64)] * 4
    records = []
    n_tok, fns = resident_dispatchers(ks, toks, records_out=records)
    assert n_tok == 256

    # The dispatcher's summed accept count must see every token once.
    for _, fn in fns:
        assert int(fn()) == n_tok

    # The dispatcher's resident record itself must be placed SHARDED
    # (dev_put with a mesh) — a replication regression here would
    # still pass the accept-count check above.
    assert records
    rec0 = records[0]
    rec_sizes = sorted(s.data.shape[0] for s in rec0.addressable_shards)
    assert len(rec_sizes) == n_dev
    assert rec_sizes == [rec0.shape[0] // n_dev] * n_dev, \
        f"dispatcher record not evenly sharded: {rec_sizes}"

    # Structure: the packed verdict array is sharded n/N per device on
    # the batch axis — no replication, no gather back to one device.
    from cap_tpu.tpu import ec as tpuec
    from cap_tpu.tpu import ed25519 as tpued
    from cap_tpu.tpu import rsa as tpursa
    from cap_tpu.runtime.native_binding import prepare_batch_arrays
    from cap_tpu.jwt.tpu_keyset import (
        _pack_es_record, _pack_rsa_record)

    pb = prepare_batch_arrays(toks)
    idx = np.arange(n_tok)
    rows = np.zeros(n_tok, np.int32)
    if alg == algs.ES256:
        table = ks._ec_tables["P-256"]
        rec = _pack_es_record(pb, table, idx, rows, 32, 256)
        ok, _deg = tpuec.verify_es_packed_pending(table, rec, 32, mesh=mesh)
    elif alg == algs.EdDSA:
        table = ks._ed_table
        sigs = [pb.signature(int(j)) for j in idx]
        msgs = [pb.signing_input(int(j)) for j in idx]
        rec = tpued.ed_packed_records(table, sigs, msgs, rows)
        ok = tpued.verify_ed_packed_pending(table, rec, mesh=mesh)
    else:
        table = ks._rsa_tables[0]
        kind = "rs" if alg == algs.RS256 else "ps"
        rec = _pack_rsa_record(pb, table, kind, "sha256", idx, rows, 256)
        verify = (tpursa.verify_rs_packed_pending if kind == "rs"
                  else tpursa.verify_ps_packed_pending)
        ok = verify(table, rec, "sha256", mesh=mesh)

    sizes = _shard_sizes(ok)
    assert len(sizes) == n_dev
    assert sizes == [256 // n_dev] * n_dev, \
        f"verdicts not evenly sharded: {sizes}"
    spec = ok.sharding.spec
    assert DP_AXIS in str(spec), f"verdict not sharded on {DP_AXIS}: {spec}"
    assert bool(np.asarray(ok)[:n_tok].all())


def test_mesh_throughput_scales_with_devices():
    """Dispatch-size sanity: per-device work is n/N — the scaling
    contract a real slice realizes as near-linear throughput."""
    from cap_tpu.jwt.tpu_keyset import TPUBatchKeySet, resident_dispatchers

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    priv, pub = captest.generate_keys(algs.ES256)
    toks = [captest.sign_jwt(priv, algs.ES256,
                             captest.default_claims(sub=f"s{i}"),
                             kid="k0") for i in range(128)] * 2
    shard_per_dev = {}
    for n_dev in (2, 8):
        mesh = make_mesh(n_dev)
        ks = TPUBatchKeySet([JWK(pub, kid="k0")], mesh=mesh)
        from cap_tpu.runtime.native_binding import prepare_batch_arrays
        from cap_tpu.jwt.tpu_keyset import _pack_es_record
        from cap_tpu.tpu import ec as tpuec

        pb = prepare_batch_arrays(toks)
        rec = _pack_es_record(pb, ks._ec_tables["P-256"],
                              np.arange(256), np.zeros(256, np.int32),
                              32, 256)
        ok, _ = tpuec.verify_es_packed_pending(
            ks._ec_tables["P-256"], rec, 32, mesh=mesh)
        shard_per_dev[n_dev] = _shard_sizes(ok)[0]
        assert bool(np.asarray(ok).all())
    # 4x the devices -> each device holds a 4x smaller slice.
    assert shard_per_dev[2] == 4 * shard_per_dev[8]
