"""Compile the main-path kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler installed here compiles for one chip of
a described ``v5e:2x2`` topology (the on-chip-measurement guide §2),
so a kernel the chip's compiler (Mosaic) refuses fails HERE, at no
chip time — interpret-mode parity tests cannot see that. Code that
picks its path from ``jax.default_backend()`` is steered to its TPU
branch by the fixture, exactly as it runs on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load libtpu, and every test worker imports this
file.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

pytest.importorskip("cryptography", reason=(
    "the RSA table fixture needs the cryptography package"))

import jax
import jax.numpy as jnp

LANES = 32768                 # tokens / lanes per program, real width


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(one_chip, monkeypatch):
    """``compile(fn, *args, **static)`` for the described chip: array
    args become shapes on its device. The persistent cache is off (an
    entry compiled for a chip cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *args, **static):
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), args)
        return jax.jit(fn, static_argnames=tuple(static)).lower(
            *shapes, **static).compile()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _zeros(*shape, dtype=np.int32):
    return np.zeros(shape, dtype)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_rs256_packed_rns_program(chip_compile):
    """The headline RS256 program: 8 RSA-2048 keys, 32768 tokens."""
    from cryptography.hazmat.primitives.asymmetric import rsa as crsa

    from cap_tpu.tpu import rns, rsa

    nums = [crsa.generate_private_key(public_exponent=65537,
                                      key_size=2048).public_key()
            .public_numbers() for _ in range(8)]
    table = rsa.RSAKeyTable([(n.n, n.e) for n in nums])
    assert rns.use_rns()
    ctx, rtab = table.rns()
    rec = _zeros(LANES, 2 * table.k + rsa.HASH_LEN["sha256"]
                 + rsa.RS_REC_EXTRA, dtype=np.uint8)
    chip_compile(rsa._rs_packed_rns_impl, rec, table.sizes_dev,
                 table.n_tab, rtab.sig_c, rtab.n_B, rtab.a2_A, rtab.a2_B,
                 k=table.k, hash_name="sha256", ctx=ctx)


@pytest.fixture(scope="module")
def p256():
    from cap_tpu.tpu import ec_rns

    c = ec_rns.ctx_for("P-256")
    return c, c.A.count, c.B.count


def test_pallas_redc(chip_compile, p256):
    from cap_tpu.tpu import pallas_redc

    c, ia, ib = p256
    assert pallas_redc.enabled()
    out = chip_compile(lambda a, b: pallas_redc.redc_fused(c, a, b),
                       _zeros(ia, LANES), _zeros(ib, LANES))
    assert _has_kernel(out)


def test_pallas_madd(chip_compile, p256):
    from cap_tpu.tpu import pallas_madd

    c, ia, ib = p256
    assert pallas_madd.enabled()
    pair = (_zeros(ia, LANES), _zeros(ib, LANES))
    flag = _zeros(LANES, dtype=np.bool_)
    packed = _zeros(max(ia, ib), LANES)
    out = chip_compile(
        lambda X, Y, Z, inf, has, x2, y2: pallas_madd.madd_fused(
            c, X, Y, Z, inf, has, x2, y2, interpret=False),
        pair, pair, pair, flag, flag, packed, packed)
    assert _has_kernel(out)


def test_pallas_madd_ladder(chip_compile, p256):
    from cap_tpu.tpu import pallas_madd

    c, ia, ib = p256
    windows = _zeros(c.n_windows, LANES)
    out = chip_compile(
        lambda tab, d, row0: pallas_madd.ladder_fused(
            c, tab, d, row0, interpret=False),
        _zeros(4096, 2 * max(ia, ib)), windows, windows)
    assert _has_kernel(out)


def test_pallas_edw(chip_compile):
    from cap_tpu.tpu import ed25519_rns, pallas_edw

    c = ed25519_rns.ctx()
    assert pallas_edw.enabled()
    pair = (_zeros(c.A.count, LANES), _zeros(c.B.count, LANES))
    out = chip_compile(
        lambda *p: pallas_edw.edw_madd_fused(c, *p, interpret=False),
        *([pair] * 7))
    assert _has_kernel(out)


@pytest.mark.parametrize("inverse", [False, True], ids=["ntt", "intt"])
def test_pallas_ntt(chip_compile, inverse):
    """ML-DSA's transform: 256 tokens x 16 polynomials of 256."""
    from cap_tpu.tpu import pallas_ntt

    assert pallas_ntt.enabled()
    fn = pallas_ntt.intt_fused if inverse else pallas_ntt.ntt_fused
    out = chip_compile(lambda x: fn(x, interpret=False),
                       _zeros(256, 16, 256, dtype=np.uint32))
    assert _has_kernel(out)


def test_pallas_keccak(chip_compile):
    """The SHAKE permutation over 8192 interleaved lanes."""
    from cap_tpu.tpu import pallas_keccak

    assert pallas_keccak.enabled()
    out = chip_compile(
        lambda s: pallas_keccak.f1600_pallas(s, interpret=False),
        _zeros(8192, 25, 2, dtype=np.uint32))
    assert _has_kernel(out)
