"""The program's spans on the profiler's clock, the keyset's host-stage
spans, the compile counter, thread CPU time, and the packed programs'
stable names.

A ``telemetry.span`` is also a host event of the same name while a JAX
profile captures (the engine installs the hook); these tests read the
events back from the ``.xplane.pb`` the profiler writes.
"""

from __future__ import annotations

import glob
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
from cap_tpu import telemetry
from cap_tpu.jwt import tpu_keyset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _hooks():
    tpu_keyset.install_jax_hooks()


def _host_events(log_dir: str):
    """{line id: [(name, start_ns, end_ns), ...]} of the host planes."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            lines[(plane.name, i)] = [
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in line.events]
    return lines


def _find(lines, name):
    return [(key, s, e) for key, evs in lines.items()
            for n, s, e in evs if n == name]


@pytest.mark.parametrize("recorder", [False, True], ids=["off", "on"])
def test_span_is_a_host_event_nested_in_its_parent(tmp_path, recorder,
                                                   monkeypatch):
    outer, inner = (telemetry.SPAN_BATCHER_DISPATCH,
                    telemetry.SPAN_KEYSET_PACK)
    rec = telemetry.Recorder() if recorder else None
    monkeypatch.setattr(telemetry, "_recorder", rec)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span(outer):
            time.sleep(0.002)
            with telemetry.span(inner):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    lines = _host_events(str(tmp_path))
    (p_line, p0, p1), = _find(lines, outer)
    (c_line, c0, c1), = _find(lines, inner)
    assert c_line == p_line                    # the same thread
    assert p0 <= c0 < c1 <= p1
    assert (c1 - c0) >= 1_000_000              # the 2 ms block
    if recorder:
        assert rec.summary()[inner]["count"] == 1.0


class _CountingAnnotation:
    """Stands in for the profiler's annotation class: records what
    would have been emitted."""

    enabled = False
    made: list = []

    @staticmethod
    def is_enabled():
        return _CountingAnnotation.enabled

    def __init__(self, name):
        _CountingAnnotation.made.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_no_event_without_a_trace(monkeypatch):
    monkeypatch.setattr(telemetry, "_annotation", _CountingAnnotation)
    monkeypatch.setattr(_CountingAnnotation, "made", [])
    for enabled in (False, True):
        monkeypatch.setattr(_CountingAnnotation, "enabled", enabled)
        with telemetry.span(telemetry.SPAN_KEYSET_WAIT):
            pass
        with telemetry.recording():
            with telemetry.span(telemetry.SPAN_KEYSET_FINISH):
                pass
    # nothing while no profile captures; once each while one does
    assert _CountingAnnotation.made == [telemetry.SPAN_KEYSET_WAIT,
                                        telemetry.SPAN_KEYSET_FINISH]


def test_cpu_series_is_within_the_wall_time():
    with telemetry.recording() as rec:
        with telemetry.span(telemetry.SPAN_BATCHER_COLLECT, cpu=True):
            time.sleep(0.02)
            sum(i * i for i in range(20000))
    s = rec.summary()
    wall = s[telemetry.SPAN_BATCHER_COLLECT]["total"]
    cpu = s[telemetry.SPAN_BATCHER_COLLECT + telemetry.CPU_SUFFIX]["total"]
    assert 0.0 < cpu <= wall
    assert wall - cpu >= 0.015                 # the sleep is waiting


def test_compile_counter_names_a_fresh_program():
    def fresh_program_for_counter(x):
        return x * 3 + 1

    with telemetry.recording() as rec:
        jax.jit(fresh_program_for_counter)(np.arange(7, dtype=np.int32))
    c = rec.counters()
    assert c["compile.programs"] >= 1
    assert c["compile.traces"] >= 1
    assert c["compile.fn.jit_fresh_program_for_counter"] == 1
    assert rec.summary()["compile.backend_s"]["count"] >= 1


def test_program_label_is_a_safe_counter_name():
    assert tpu_keyset._program_label("jit(_rs_packed_rns)") \
        == "jit__rs_packed_rns"
    for raw in ("jit(keyJoin)", "a b://c" * 40, ""):
        telemetry.check_name("compile.fn." + tpu_keyset._program_label(raw))


def test_telemetry_imports_without_jax():
    code = ("import sys; from cap_tpu import telemetry; "
            "telemetry.span('x').__enter__(); "
            "assert 'jax' not in sys.modules, 'jax loaded'")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr


KEYSET_SPANS = ("prep.native", telemetry.SPAN_KEYSET_NORMALIZE,
                telemetry.SPAN_KEYSET_ROUTE, telemetry.SPAN_KEYSET_PACK,
                telemetry.SPAN_KEYSET_LAUNCH,
                telemetry.SPAN_KEYSET_WAIT, telemetry.SPAN_KEYSET_FINISH,
                telemetry.SPAN_KEYSET_DECISIONS)


def test_keyset_emits_its_host_stages(tmp_path, monkeypatch):
    pytest.importorskip("cryptography")
    # the chip's RNS engines: on the CPU they also build fastest here
    monkeypatch.setenv("CAP_TPU_RNS", "1")
    from cap_tpu import testing as captest
    from cap_tpu.jwt.jwk import JWK
    from cap_tpu.runtime import prep

    if prep._load_native() is None:
        pytest.skip("native prep not built")
    rs_priv, rs_pub = captest.generate_keys("RS256", rsa_bits=2048)
    es_priv, es_pub = captest.generate_keys("ES256")
    ks = tpu_keyset.TPUBatchKeySet([JWK(rs_pub, kid="rs"),
                                    JWK(es_pub, kid="es")])
    tokens = [captest.sign_jwt(rs_priv, "RS256", captest.default_claims(),
                               kid="rs"),
              captest.sign_jwt(es_priv, "ES256", captest.default_claims(),
                               kid="es")] * 2
    ks.verify_batch_async_raw(tokens)()        # build the programs first
    with telemetry.recording() as rec:
        jax.profiler.start_trace(str(tmp_path))
        try:
            out = ks.verify_batch_async_raw(tokens)()
        finally:
            jax.profiler.stop_trace()
    assert all(isinstance(r, bytes) for r in out)
    lines = _host_events(str(tmp_path))
    summary = rec.summary()
    for name in KEYSET_SPANS:
        assert _find(lines, name), f"{name} not on the profiler's clock"
        assert name in summary, name
    # one pack and one launch per chunk: an RS and an ES chunk
    assert summary[telemetry.SPAN_KEYSET_PACK]["count"] == 2.0
    assert "device.sync" not in summary
    assert "wire.est_mbps" not in summary


def _module_name(fn, *args, **static):
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), args)
    text = jax.jit(fn, static_argnames=tuple(static)).lower(
        *shapes, **static).as_text()
    return re.search(r"module @(\S+)", text).group(1)


def test_packed_programs_carry_their_stable_names():
    pytest.importorskip("cryptography")
    from cryptography.hazmat.primitives.asymmetric import ec as cec
    from cryptography.hazmat.primitives.asymmetric import rsa as crsa

    from cap_tpu.tpu import ec, ed25519, rsa

    nums = crsa.generate_private_key(public_exponent=65537,
                                     key_size=2048).public_key() \
        .public_numbers()
    rt = rsa.RSAKeyTable([(nums.n, nums.e)])
    ctx, rtab = rt.rns()
    rec = np.zeros((128, 2 * rt.k + 32 + rsa.RS_REC_EXTRA), np.uint8)
    rs_name = _module_name(
        rsa._rs_packed_rns_impl, rec, rt.sizes_dev, rt.n_tab, rtab.sig_c,
        rtab.n_B, rtab.a2_A, rtab.a2_B, k=rt.k, hash_name="sha256",
        ctx=ctx)
    et = ec.ECKeyTable("P-256", [
        cec.generate_private_key(cec.SECP256R1()).public_key()])
    cp = et.curve
    etab = et.rns()
    rec = np.zeros((128, 2 * cp.coord_bytes + 32 + ec.ES_REC_EXTRA),
                   np.uint8)
    es_name = _module_name(
        ec._es_packed_rns_impl, rec, etab.tab,
        tuple(cp.device_consts()[4:9]), crv=cp.name, nbits=cp.nbits,
        wbits=etab.ctx.w_bits, k=cp.k, cb=cp.coord_bytes, hlen=32,
        ladder=ec.resolve_ladder(None))
    assert rsa.PROGRAM in rs_name and ec.PROGRAM in es_name
    rs_family = {f.__name__ for f in (rsa._rs_packed_rns_impl,
                                      rsa._rs_packed_limb_impl)}
    es_family = {f.__name__ for f in (ec._es_packed_rns_impl,
                                      ec._es_packed_limb_impl)}
    others = {f.__name__ for f in (rsa._ps_packed_rns_impl,
                                   rsa._ps_packed_limb_impl,
                                   ed25519._ed_packed_rns_impl,
                                   ed25519._ed_packed_limb_impl)}
    assert all(rsa.PROGRAM in n for n in rs_family)
    assert all(ec.PROGRAM in n for n in es_family)
    assert all(rsa.PS_PROGRAM in n for n in
               (rsa._ps_packed_rns_impl.__name__,
                rsa._ps_packed_limb_impl.__name__))
    for const, own in ((rsa.PROGRAM, rs_family), (ec.PROGRAM, es_family)):
        for name in (rs_family | es_family | others) - own:
            assert const not in name, (const, name)
