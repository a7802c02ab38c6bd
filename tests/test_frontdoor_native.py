"""Native relay front door: parity pin + relay e2e + counters.

The routing-decision parity pin is the load-bearing test: the C++
fast path (``cap_frontdoor_probe_route``) must make bit-identical
owner decisions to the Python :class:`ConsistentHashRing` twin —
across ring sizes, membership change, and breaker trips — exactly the
twin stance the DRR scheduler pins with ``cap_drr_*``. The relay
e2e section then drives every CVB1 frame family through a live
:class:`NativeFrontDoorServer` over real in-process workers and gates
the exact-counting contract (``frontdoor.lookups ==
affinity_hits + affinity_misses``) through the split fast/slow path.
"""

import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

from cap_tpu import telemetry
from cap_tpu.fleet import ConsistentHashRing, FrontDoor
from cap_tpu.fleet.frontdoor import (NativeFrontDoorServer,
                                     native_frontdoor_enabled)
from cap_tpu.fleet.worker_main import StubKeySet
from cap_tpu.jwt.keyset import KeySet
from cap_tpu.serve import protocol as P
from cap_tpu.serve import vcache as V
from cap_tpu.serve.worker import VerifyWorker

try:
    from cap_tpu.serve import native_serve
    _HAVE = bool(getattr(native_serve.load(), "cap_fd_ok", False))
except Exception:  # noqa: BLE001 - any load failure → skip module
    _HAVE = False

pytestmark = pytest.mark.skipif(
    not _HAVE, reason="native front-door chain unavailable")

HARD_TIMEOUT_S = 120


@pytest.fixture(autouse=True)
def _hard_timeout():
    def on_alarm(signum, frame):
        raise TimeoutError(
            f"frontdoor-native test exceeded {HARD_TIMEOUT_S}s")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(HARD_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _bare_frontdoor(n_pools=2, **kw):
    return FrontDoor([[("127.0.0.1", 1 + i)] for i in range(n_pools)],
                     **kw)


def _gateway(fd, **kw):
    kw.setdefault("refresh_s", 0.05)
    return NativeFrontDoorServer(fd, **kw)


# ---------------------------------------------------------------------------
# the parity pin: native owner decision == Python ring twin, bit-exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_pools,vnodes", [(1, 64), (2, 64), (3, 16),
                                            (5, 64)])
def test_probe_route_parity_pin(n_pools, vnodes):
    """Randomized digests through the native ring lookup vs the
    Python twin: owner decisions must be bit-identical, including
    the breaker-trip (-1 = slow path) and membership-change cases."""
    fd = _bare_frontdoor(n_pools, vnodes=vnodes)
    # refresh_s huge: the test owns the live flags below
    gw = _gateway(fd, refresh_s=999.0)
    try:
        rng = random.Random(0xF00D + n_pools)
        digests = [rng.randbytes(16) for _ in range(300)]
        ring = fd._ring
        want = [ring.primary(d) for d in digests]
        assert gw.probe_route(digests) == want

        # breaker trip: dead owner → -1 (the frame would slow-path),
        # every other decision UNCHANGED
        dead = n_pools - 1
        gw._lib.cap_frontdoor_set_live(gw._h, dead, 0)
        want_dead = [-1 if w == dead else w for w in want]
        assert gw.probe_route(digests) == want_dead
        gw._lib.cap_frontdoor_set_live(gw._h, dead, 1)
        assert gw.probe_route(digests) == want
    finally:
        gw.close(deadline_s=5.0)


def test_probe_route_membership_change_parity():
    """Re-staging a grown ring re-pins parity: the native decision
    tracks the NEW ring exactly, and only segments the new pool owns
    moved (the consistent-hash property, through the native path)."""
    fd2 = _bare_frontdoor(2)
    gw2 = _gateway(fd2, refresh_s=999.0)
    try:
        rng = random.Random(29)
        digests = [rng.randbytes(16) for _ in range(300)]
        before = gw2.probe_route(digests)
        assert before == [fd2._ring.primary(d) for d in digests]
    finally:
        gw2.close(deadline_s=5.0)
    fd3 = _bare_frontdoor(3)
    gw3 = _gateway(fd3, refresh_s=999.0)
    try:
        after = gw3.probe_route(digests)
        assert after == [fd3._ring.primary(d) for d in digests]
        moved = [(b, a) for b, a in zip(before, after) if b != a]
        assert moved and all(a == 2 for _b, a in moved), \
            "membership change must move only the new pool's segments"
    finally:
        gw3.close(deadline_s=5.0)


def test_probe_route_point_math_matches_bisect():
    """The ring-point math itself: big-endian u64 of digest[:8] +
    upper_bound == Python int.from_bytes + bisect_right, pinned on
    crafted edge digests (all-zero, all-ff, exact point values)."""
    fd = _bare_frontdoor(3)
    gw = _gateway(fd, refresh_s=999.0)
    try:
        ring = fd._ring
        edges = [bytes(16), b"\xff" * 16]
        for pt in ring._points[:8]:
            edges.append(pt.to_bytes(8, "big") + bytes(8))
            edges.append((pt - 1).to_bytes(8, "big") + bytes(8))
        assert gw.probe_route(edges) == [ring.primary(d)
                                         for d in edges]
    finally:
        gw.close(deadline_s=5.0)


# ---------------------------------------------------------------------------
# relay e2e over live workers: every frame family, exact counters
# ---------------------------------------------------------------------------


def _two_workers(**kw):
    w0 = VerifyWorker(StubKeySet(), target_batch=64, max_wait_ms=1.0,
                      **kw)
    w1 = VerifyWorker(StubKeySet(), target_batch=64, max_wait_ms=1.0,
                      **kw)
    return w0, w1


def _connect(gw):
    s = socket.create_connection(gw.address, timeout=10.0)
    s.settimeout(30.0)
    return s, P.FrameReader(s)


def test_relay_e2e_all_frame_families_and_exact_counters():
    rec = telemetry.enable()
    rec.reset()
    w0, w1 = _two_workers()
    gw = None
    try:
        fd = FrontDoor([[w0.address], [w1.address]],
                       fallback=StubKeySet(),
                       client_kw={"attempt_timeout": 5.0,
                                  "total_deadline": 10.0})
        gw = _gateway(fd)
        s, r = _connect(gw)
        toks = [f"relay-{i}.ok" for i in range(24)] + ["relay-bad"]
        for crc, trace in ((False, None), (True, None),
                          (False, "ab12cd34")):
            P.send_request(s, toks, crc=crc, trace=trace)
            ftype, entries, tr = r.recv_frame_ex()
            want = (P.T_VERIFY_RESP_TRACE if trace
                    else P.T_VERIFY_RESP_CRC if crc
                    else P.T_VERIFY_RESP)
            assert ftype == want and tr == trace
            assert [e[0] for e in entries] == [0] * 24 + [1]
            for t, (st, payload) in zip(toks[:24], entries[:24]):
                assert json.loads(payload) == {"sub": t}
        P.send_ping(s)
        ftype, _ = r.recv_frame()
        assert ftype == P.T_PONG
        s.close()
        time.sleep(0.3)           # let the counter fold tick

        st = gw.stats()
        c = st["counters"]
        assert st["frontdoor_chain"] == "native"
        # THE exact-counting contract through the split path
        assert c["frontdoor.lookups"] == \
            c["frontdoor.affinity_hits"] \
            + c["frontdoor.affinity_misses"]
        assert c["frontdoor.lookups"] >= 75
        assert c["frontdoor.native.relays"] > 0
        assert c["frontdoor.native.proto_errors"] == 0
        assert c["frontdoor.native.upstream_fails"] == 0
        assert c["frontdoor.native.dropped_posts"] == 0
        # native fast path only ever counts lookups == hits
        assert c["frontdoor.native.lookups"] \
            == c["frontdoor.native.hits"]
        assert c.get("vcache.stale_accepts", 0) == 0
    finally:
        if gw is not None:
            gw.close(deadline_s=5.0)   # closes fd too
        w0.close(5)
        w1.close(5)
        telemetry.disable()


def test_relay_splices_single_owner_frames_and_holds_seq_order():
    """Pipelined single-token frames: single-owner plain requests
    splice through verbatim (zero re-encode), and responses come back
    in strict submission order even though two pools answer at
    different speeds."""
    w0, w1 = _two_workers()
    gw = None
    try:
        fd = FrontDoor([[w0.address], [w1.address]],
                       fallback=StubKeySet())
        gw = _gateway(fd)
        s, r = _connect(gw)
        n = 40
        toks = [f"seq-{i}.a.ok" for i in range(n)]
        for t in toks:
            P.send_request(s, [t])
        for t in toks:
            ftype, entries = r.recv_frame()
            assert ftype == P.T_VERIFY_RESP
            assert entries[0][0] == 0
            assert json.loads(entries[0][1]) == {"sub": t}, \
                "responses out of submission order"
        s.close()
        nc = gw.native_counters()
        assert nc["frontdoor.native.splices"] >= n // 2
        # every token either relayed natively or (overload gate) went
        # through the Python slow path — none double-counted or lost
        assert nc["frontdoor.native.relay_tokens"] \
            + nc["frontdoor.native.slow_tokens"] == n
    finally:
        if gw is not None:
            gw.close(deadline_s=5.0)
        w0.close(5)
        w1.close(5)


def test_control_frames_slow_path_stats_keys_peer_shm():
    """Control frames drain to Python and each gets EXACTLY one
    response: STATS serves the gateway doc, KEYS fans out to every
    pool (both workers converge on the pushed epoch), peer-fill and
    shm-attach are refused with proper error acks."""
    w0, w1 = _two_workers()
    gw = None
    try:
        fd = FrontDoor([[w0.address], [w1.address]])
        gw = _gateway(fd)
        s, r = _connect(gw)
        P.send_stats_request(s)
        ftype, entries = r.recv_frame()
        assert ftype == P.T_STATS_RESP
        doc = json.loads(entries[0][1])
        assert doc["frontdoor_chain"] == "native"
        assert doc["frontdoor"]["routing"] == "affinity"

        P.send_keys_push(s, {"keys": []}, epoch=5)
        ftype, entries = r.recv_frame()
        assert ftype == P.T_KEYS_ACK and entries[0][0] == 0
        assert json.loads(entries[0][1])["epoch"] == 5
        assert w0.key_epoch == 5 and w1.key_epoch == 5
        assert gw.key_epoch == 5

        P.send_peer_fill(s, {"op": "export", "max_entries": 10})
        ftype, entries = r.recv_frame()
        assert ftype == P.T_PEER_ACK and entries[0][0] == 1

        P.send_shm_attach(s, "/bogus/ring")
        ftype, entries = r.recv_frame()
        assert ftype == P.T_SHM_ACK and entries[0][0] == 1

        # still serving verifies on the same conn afterwards
        P.send_request(s, ["after-control.ok"])
        ftype, entries = r.recv_frame()
        assert ftype == P.T_VERIFY_RESP and entries[0][0] == 0
        s.close()
        assert gw.native_counters()[
            "frontdoor.native.dropped_posts"] == 0
    finally:
        if gw is not None:
            gw.close(deadline_s=5.0)
        w0.close(5)
        w1.close(5)


def test_dead_pool_upstream_fail_then_breaker_pushdown():
    """One pool's endpoint is dead from the start: the first relay
    that routes there fails upstream (connect refused) and the WHOLE
    frame re-dispatches through the Python slow path — which trips the
    breaker, the refresh thread pushes live=0 down, and later frames
    classify as dead-pool BEFORE any relay is attempted. Zero wrong
    verdicts, zero lost submissions throughout."""
    w0, w1 = _two_workers()
    gw = None
    try:
        # a port nothing listens on (bound then released)
        with socket.socket() as probe:
            probe.bind(("127.0.0.1", 0))
            dead_port = probe.getsockname()[1]
        fd = FrontDoor([[w0.address], [w1.address],
                        [("127.0.0.1", dead_port)]],
                       fallback=StubKeySet(),
                       client_kw={"attempt_timeout": 1.0,
                                  "total_deadline": 5.0,
                                  "max_rounds": 1,
                                  "breaker_threshold": 1,
                                  "breaker_reset_s": 60.0})
        gw = _gateway(fd)
        s, r = _connect(gw)
        toks = [f"death-{i}.ok" for i in range(48)]
        for rep in range(6):
            P.send_request(s, toks)
            ftype, entries = r.recv_frame()
            assert ftype == P.T_VERIFY_RESP
            assert len(entries) == 48, "lost submissions"
            assert [e[0] for e in entries] == [0] * 48, \
                f"wrong verdict with dead pool (rep {rep})"
            time.sleep(0.12)          # let breaker → set_live settle
        s.close()
        c = gw.stats()["counters"]
        assert c["frontdoor.lookups"] == \
            c["frontdoor.affinity_hits"] \
            + c["frontdoor.affinity_misses"]
        # rep 1 hit the upstream-fail election; once the breaker
        # pushed live=0 down, frames classified dead-pool at the edge
        assert c["frontdoor.native.upstream_fails"] > 0
        assert c.get("frontdoor.native.slow.upstream_fail", 0) > 0
        assert c.get("frontdoor.native.slow.dead_pool", 0) > 0
        assert c["frontdoor.reroutes"] > 0 \
            or c["frontdoor.fallback_tokens"] > 0
    finally:
        if gw is not None:
            gw.close(deadline_s=5.0)
        w0.close(5)
        w1.close(5)


def test_malformed_frame_severs_connection_not_gateway():
    w0, w1 = _two_workers()
    gw = None
    try:
        fd = FrontDoor([[w0.address], [w1.address]])
        gw = _gateway(fd)
        s, r = _connect(gw)
        s.sendall(b"\x00" * 64)           # bad magic
        assert s.recv(1) == b"", "reader must sever on bad magic"
        s.close()
        # the gateway itself survives and keeps serving
        s2, r2 = _connect(gw)
        P.send_request(s2, ["survivor.ok"])
        ftype, entries = r2.recv_frame()
        assert ftype == P.T_VERIFY_RESP and entries[0][0] == 0
        s2.close()
        assert gw.native_counters()[
            "frontdoor.native.proto_errors"] >= 1
    finally:
        if gw is not None:
            gw.close(deadline_s=5.0)
        w0.close(5)
        w1.close(5)


# ---------------------------------------------------------------------------
# kill switch + worker_main wiring
# ---------------------------------------------------------------------------


def test_kill_switch_env(monkeypatch):
    monkeypatch.delenv("CAP_FRONTDOOR_NATIVE", raising=False)
    assert native_frontdoor_enabled()
    for off in ("0", "false", "no", "off"):
        monkeypatch.setenv("CAP_FRONTDOOR_NATIVE", off)
        assert not native_frontdoor_enabled()
    monkeypatch.setenv("CAP_FRONTDOOR_NATIVE", "1")
    assert native_frontdoor_enabled()


def test_native_gate_requires_affinity_routing():
    fd = _bare_frontdoor(2, routing="rr")
    with pytest.raises(ValueError):
        NativeFrontDoorServer(fd)
    fd.close()


def _boot_gateway_proc(pool_port, env_extra=None, chain="auto"):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    p = subprocess.Popen(
        [sys.executable, "-m", "cap_tpu.fleet.worker_main",
         "--keyset", f"frontdoor:pool=127.0.0.1:{pool_port}",
         "--obs-port", "-1", "--frontdoor-chain", chain],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env)
    line = p.stdout.readline().strip()
    kv = dict(f.split("=", 1) for f in line.split()[1:])
    return p, kv


def test_worker_main_gateway_chain_selection():
    """The deployable gateway: ``--frontdoor-chain auto`` runs native,
    the CAP_FRONTDOOR_NATIVE=0 kill switch forces the python gate, and
    both report honestly on the ready line."""
    w0 = VerifyWorker(StubKeySet(), target_batch=64, max_wait_ms=1.0)
    procs = []
    try:
        port = w0.address[1]
        p1, kv1 = _boot_gateway_proc(port)
        procs.append(p1)
        assert kv1.get("frontdoor_chain") == "native", kv1
        p2, kv2 = _boot_gateway_proc(
            port, env_extra={"CAP_FRONTDOOR_NATIVE": "0"})
        procs.append(p2)
        assert kv2.get("frontdoor_chain") == "python", kv2
        # both gates serve identical verdicts
        for kv in (kv1, kv2):
            s = socket.create_connection(
                ("127.0.0.1", int(kv["port"])), timeout=10.0)
            s.settimeout(30.0)
            P.send_request(s, ["gate.ok", "gate.bad"])
            ftype, entries = P.FrameReader(s).recv_frame()
            assert [e[0] for e in entries] == [0, 1], kv
            s.close()
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        w0.close(5)


# ---------------------------------------------------------------------------
# one answer per token, whatever the route: the slow path relays the
# owning worker's own bytes, as the native splice does
# ---------------------------------------------------------------------------


class _KidKeySet(KeySet):
    """A CPU verifier that routes by ``kid``: a kid it does not hold is
    an ``UnknownKeyIDError``, so every reject kind below has its own
    error class on the wire."""

    def __init__(self, keys):
        self._keys = dict(keys)

    def verify_signature(self, token):
        from cap_tpu.errors import UnknownKeyIDError
        from cap_tpu.jwt.jose import parse_jws
        from cap_tpu.jwt.verify import verify_parsed

        parsed = parse_jws(token)
        key = self._keys.get(parsed.kid)
        if key is None:
            raise UnknownKeyIDError("no key matches kid")
        verify_parsed(parsed, key)
        return parsed.claims()


def _seeded_tokens(seed=0x9068, n_ok=24):
    """Keys from the seed; ``n_ok`` accepts, then one token of each
    reject kind: a forged signature, ``alg=none`` and an unknown kid."""
    from cryptography.hazmat.primitives.asymmetric import ec

    from cap_tpu.testing import b64url_encode, sign_jwt

    priv = ec.derive_private_key(seed, ec.SECP256R1())
    other = ec.derive_private_key(seed + 1, ec.SECP256R1())
    claims = {"iss": "https://as.example.com/", "aud": "rs",
              "client_id": "c1", "iat": 1767225600, "exp": 2082758400}
    ok = [sign_jwt(priv, "ES256", dict(claims, sub=f"u{i}", jti=f"j{i}"),
                   kid="as-2026a") for i in range(n_ok)]
    h, p, sig = ok[0].split(".")
    forged = f"{h}.{p}.{sig[:-4]}AAAA"
    none = (b64url_encode(json.dumps({"alg": "none", "typ": "at+jwt"})
                          .encode()) + "." + p + ".")
    unknown = sign_jwt(other, "ES256", dict(claims, sub="x", jti="x"),
                       kid="as-2099z")
    keyset = _KidKeySet({"as-2026a": priv.public_key()})
    return ok + [forged, none, unknown], keyset


def _kid_workers(keyset, n=2, **kw):
    return [VerifyWorker(keyset, target_batch=64, max_wait_ms=1.0, **kw)
            for _ in range(n)]


def _ask(address, tokens, crc=False):
    s = socket.create_connection(address, timeout=10.0)
    s.settimeout(30.0)
    try:
        P.send_request(s, tokens, crc=crc)
        return P.FrameReader(s).recv_frame()
    finally:
        s.close()


def _direct(fd, workers, tokens):
    """Each token asked alone of the worker that owns it on the ring."""
    out = []
    for t in tokens:
        owner = fd._ring.primary(V.token_digest(t))
        out.append(_ask(workers[owner].address, [t])[1][0])
    return out


def test_slow_path_answers_with_the_owning_workers_bytes():
    """Accepts, a forged signature, ``alg=none`` and an unknown kid:
    the slow path's answer for each token is, byte for byte, what the
    native splice relays and what the owning worker answers directly —
    in a frame drained to Python, and in frames that a dead pool sends
    there for real (``slow.upstream_fail``, then ``slow.dead_pool``)."""
    tokens, keyset = _seeded_tokens()
    workers = _kid_workers(keyset)
    gw = gw_dead = None
    try:
        fd = FrontDoor([[w.address] for w in workers])
        gw = _gateway(fd)
        want = _direct(fd, workers, tokens)
        assert [e[0] for e in want] == [0] * 24 + [1, 1, 1]
        heads = [e[1].split(b":")[0] for e in want[24:]]
        assert b"RemoteVerifyError" not in heads
        assert heads[2] == b"UnknownKeyIDError"

        # the native fast path: one token a frame, spliced verbatim
        s, r = _connect(gw)
        spliced = []
        for t in tokens:
            P.send_request(s, [t])
            spliced.append(r.recv_frame()[1][0])
        s.close()
        assert spliced == want
        assert gw.native_counters()["frontdoor.native.slow_frames"] == 0

        # one frame drained to the slow path, for each frame family
        for ftype in (P.T_VERIFY_REQ, P.T_VERIFY_REQ_CRC):
            frame = _frame(tokens, crc=ftype == P.T_VERIFY_REQ_CRC)
            resp = gw._handle_slow(frame, ftype, len(tokens))
            _ft, entries, _tr, _c = P.parse_frame_bytes(resp)
            assert entries == want, ftype

        # a dead third pool: the frames leave the fast path for real
        with socket.socket() as probe:
            probe.bind(("127.0.0.1", 0))
            dead_port = probe.getsockname()[1]
        fd_dead = FrontDoor([[w.address] for w in workers]
                            + [[("127.0.0.1", dead_port)]],
                            client_kw={"attempt_timeout": 1.0,
                                       "total_deadline": 5.0,
                                       "max_rounds": 1,
                                       "breaker_threshold": 1,
                                       "breaker_reset_s": 60.0})
        gw_dead = _gateway(fd_dead)
        s, r = _connect(gw_dead)
        for rep in range(4):
            P.send_request(s, tokens)
            ftype, entries = r.recv_frame()
            assert ftype == P.T_VERIFY_RESP
            assert entries == want, rep
            time.sleep(0.12)          # breaker → set_live settles
        s.close()
        c = gw_dead.stats()["counters"]
        assert c.get("frontdoor.native.slow.upstream_fail", 0) > 0
        assert c.get("frontdoor.native.slow.dead_pool", 0) > 0
        assert c["frontdoor.slow.raw_entries"] == 4 * len(tokens)
    finally:
        for g in (gw, gw_dead):
            if g is not None:
                g.close(deadline_s=5.0)
        for w in workers:
            w.close(5)


def _frame(tokens, crc=False):
    """A verify request frame's bytes, as a client sends them."""
    class _Sink:
        data = b""

        def sendall(self, b):
            self.data += b

    sink = _Sink()
    P.send_request(sink, tokens, crc=crc)
    return sink.data


def test_slow_path_relays_a_throttled_reject_as_the_worker_sent_it():
    """Admission sheds on the workers: the slow path's answer carries
    the worker's own ``ThrottledError`` entry, byte for byte, and the
    front door still counts it as throttled."""
    from cap_tpu.fleet.router import FleetClient

    rec = telemetry.enable()
    rec.reset()
    tokens, keyset = _seeded_tokens(n_ok=12)
    workers = _kid_workers(keyset, serve_native=False,
                           admit_rate=1e-4, admit_burst=2)
    gw = None
    sent = []
    real = FleetClient.verify_batch_wire

    def spy(self, toks):
        out = real(self, toks)
        sent.extend(out)
        return out

    try:
        fd = FrontDoor([[w.address] for w in workers])
        gw = _gateway(fd)
        FleetClient.verify_batch_wire = spy
        resp = gw._handle_slow(_frame(tokens), P.T_VERIFY_REQ,
                               len(tokens))
        _ft, entries, _tr, _c = P.parse_frame_bytes(resp)
        assert sorted(entries) == sorted(tuple(v) for v in sent)
        thr = [e for e in entries if e[1].startswith(b"ThrottledError: ")]
        # each worker's tenant bucket admits two, then sheds
        assert len(thr) == len(tokens) - 4
        assert all(e[0] == 1 for e in thr)
        assert fd.counters()["frontdoor.throttled_tokens"] == len(thr)
    finally:
        FleetClient.verify_batch_wire = real
        if gw is not None:
            gw.close(deadline_s=5.0)
        for w in workers:
            w.close(5)
        telemetry.disable()


def test_slow_path_rejects_classify_by_their_own_reason():
    """``decision.classify`` reads a reject that came through the slow
    path by the worker's reason, and so does the front door's own
    decision fold."""
    from cap_tpu.obs import decision
    from cap_tpu.serve.client import RemoteVerifyError

    rec = telemetry.enable()
    rec.reset()
    tokens, keyset = _seeded_tokens(n_ok=4)
    workers = _kid_workers(keyset)
    gw = None
    try:
        fd = FrontDoor([[w.address] for w in workers])
        gw = _gateway(fd)
        resp = gw._handle_slow(_frame(tokens), P.T_VERIFY_REQ,
                               len(tokens))
        _ft, entries, _tr, _c = P.parse_frame_bytes(resp)
        reasons = [decision.classify(RemoteVerifyError(e[1].decode()))
                   for e in entries[4:]]
        assert reasons[0] == decision.REASON_BAD_SIGNATURE
        assert reasons[2] == decision.REASON_UNKNOWN_KID
        assert decision.REASON_INTERNAL not in reasons
        c = rec.counters()
        assert c.get("decision.frontdoor.accept") == 4
        for reason in set(reasons):
            assert c.get(f"decision.frontdoor.reject.{reason}") \
                == reasons.count(reason), reason
        assert not any(k.startswith("decision.frontdoor.reject.internal")
                       for k in c)
    finally:
        if gw is not None:
            gw.close(deadline_s=5.0)
        for w in workers:
            w.close(5)
        telemetry.disable()


def test_frontdoor_verify_batch_still_returns_claims_and_exceptions():
    """Python callers keep their contract: claims dicts and exceptions,
    the same verdicts ``verify_batch_wire`` carries as wire entries; the
    terminal fallback encodes its own verdicts as a worker does."""
    from cap_tpu.fleet.router import decode_verdict
    from cap_tpu.serve.client import RemoteVerifyError

    tokens, keyset = _seeded_tokens(n_ok=4)
    workers = _kid_workers(keyset)
    try:
        fd = FrontDoor([[w.address] for w in workers])
        want = _direct(fd, workers, tokens)
        got = fd.verify_batch(tokens)
        assert [type(g) for g in got] == \
            [dict] * 4 + [RemoteVerifyError] * 3
        assert got[:4] == [decode_verdict(P.RawVerdict(*e))
                           for e in want[:4]]
        assert [str(g).encode() for g in got[4:]] == \
            [e[1] for e in want[4:]]
        assert [tuple(v) for v in fd.verify_batch_wire(tokens)] == want
        fd.close()
    finally:
        for w in workers:
            w.close(5)
    # every pool gone: the fallback answers in the workers' encoding
    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        dead = probe.getsockname()[1]
    fd = FrontDoor([[("127.0.0.1", dead)]], fallback=keyset,
                   client_kw={"attempt_timeout": 1.0, "max_rounds": 1,
                              "total_deadline": 2.0})
    try:
        assert [tuple(v) for v in fd.verify_batch_wire(tokens)] == want
        assert fd.counters()["frontdoor.fallback_tokens"] == len(tokens)
    finally:
        fd.close()


def test_pool_token_counters_sum_to_the_tokens_relayed():
    """``frontdoor.pool.<id>.tokens`` counts what each pool was sent on
    either path: the native relay's slot folded into the slow path's
    count, each pool exactly its ring share, the sum every token."""
    tokens, keyset = _seeded_tokens(n_ok=40)
    workers = _kid_workers(keyset, n=3)
    gw = None
    try:
        fd = FrontDoor([[w.address] for w in workers])
        gw = _gateway(fd)
        owned = [0] * 3
        for t in tokens:
            owned[fd._ring.primary(V.token_digest(t))] += 1
        s, r = _connect(gw)
        P.send_request(s, tokens)            # fast path, split by owner
        r.recv_frame()
        for t in tokens[:5]:
            P.send_request(s, [t])           # fast path, spliced
            r.recv_frame()
        s.close()
        gw._handle_slow(_frame(tokens), P.T_VERIFY_REQ, len(tokens))
        nc = gw.native_counters()
        assert nc["frontdoor.native.slow_frames"] == 0
        c = gw.stats()["counters"]
        per = [c.get(f"frontdoor.pool.{i}.tokens", 0) for i in range(3)]
        first = [0] * 3
        for t in tokens[:5]:
            first[fd._ring.primary(V.token_digest(t))] += 1
        assert per == [2 * o + f for o, f in zip(owned, first)]
        assert sum(per) == nc["frontdoor.native.relay_tokens"] \
            + c["frontdoor.slow.raw_entries"] == 2 * len(tokens) + 5
    finally:
        if gw is not None:
            gw.close(deadline_s=5.0)
        for w in workers:
            w.close(5)


def test_stats_frame_does_not_wait_behind_slow_path_verify_frames():
    """A STATS frame drained after a slow-path verify frame is answered
    while that frame still waits on its pool, and its counters show the
    frame drained (``slow.overload``) but not yet answered
    (``slow.raw_entries`` 0); the verify frame's answer then has the
    bytes the native relay gave the same tokens."""
    workers = [VerifyWorker(StubKeySet(batch_ms=800), target_batch=64,
                            max_wait_ms=1.0) for _ in range(2)]
    gw = None
    try:
        # a bounded-load constant so small that an owner with anything
        # in flight sends the next frame to the slow path
        fd = FrontDoor([[w.address] for w in workers], spill_factor=0.01)
        gw = _gateway(fd)
        tokens = [f"t{i}.ok" for i in range(16)] + ["bad.sig"]
        sa, ra = _connect(gw)
        sb, rb = _connect(gw)
        ss, rs = _connect(gw)
        P.send_request(sa, tokens)           # native relay, held 0.8 s
        time.sleep(0.15)
        P.send_request(sb, tokens)           # owners busy: slow path
        time.sleep(0.15)
        t0 = time.monotonic()
        P.send_stats_request(ss)
        ftype, entries = rs.recv_frame()
        waited = time.monotonic() - t0
        assert ftype == P.T_STATS_RESP
        c = json.loads(entries[0][1])["counters"]
        assert c.get("frontdoor.native.slow.overload") == 1
        assert c.get("frontdoor.slow.raw_entries") == 0
        assert waited < 0.5, waited
        fast, slow = ra.recv_frame()[1], rb.recv_frame()[1]
        assert slow == fast and [e[0] for e in slow] == [0] * 16 + [1]
        assert gw.stats()["counters"]["frontdoor.slow.raw_entries"] \
            == len(tokens)
        for s in (sa, sb, ss):
            s.close()
    finally:
        if gw is not None:
            gw.close(deadline_s=5.0)
        for w in workers:
            w.close(5)
