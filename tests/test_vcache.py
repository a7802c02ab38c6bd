"""Verdict cache + in-flight replay dedup (ROADMAP #3).

Correctness-preserving contract: the cache tier may change how FAST a
verdict is produced, never WHICH verdict — pinned here as unit clamps
(exp/nbf/epoch/grace/TTL/terminal-reject rules), batcher dedup
fan-out, both serve chains end-to-end, the FleetClient tier, and a
randomized mixed parity sweep (expiring tokens crossing ``exp``
mid-run, an epoch swap mid-run) asserting bit-identical verdicts AND
serve-surface decision-reason counters with the cache on vs off.
"""

import base64
import hashlib
import json
import threading
import time

import pytest

from cap_tpu import telemetry
from cap_tpu.errors import (
    InvalidSignatureError,
    MalformedTokenError,
    UnknownKeyIDError,
)
from cap_tpu.serve.protocol import ProtocolError
from cap_tpu.serve import AdaptiveBatcher, VerifyClient, VerifyWorker
from cap_tpu.serve import vcache as V
from cap_tpu.serve.client import RemoteVerifyError


def _payload(claims):
    return base64.urlsafe_b64encode(
        json.dumps(claims).encode()).rstrip(b"=").decode()


def _tok(name, ok=True, **claims):
    """A stub-verifiable token whose middle segment carries real
    claims (the vcache parses exp/nbf out of it)."""
    mid = _payload(claims) if claims else "e30"
    return f"{name}.{mid}.{'ok' if ok else 'bad'}"


class CountingStub:
    """Suffix-determined verdicts; records every engine-visible token
    (the dedup/cache assertions read ``seen``)."""

    def __init__(self):
        self.seen = []
        self.lock = threading.Lock()
        self.key_epoch = 0

    def swap_keys(self, jwks, epoch=None, grace_s=0.0):
        self.key_epoch = (self.key_epoch + 1 if epoch is None
                          else int(epoch))
        return self.key_epoch

    def verify_batch(self, tokens):
        with self.lock:
            self.seen.extend(tokens)
        return [{"sub": t} if t.endswith(".ok")
                else InvalidSignatureError("bad sig") for t in tokens]


# ---------------------------------------------------------------------------
# unit: the cache itself
# ---------------------------------------------------------------------------


def test_digest_definition_is_sha256_16():
    assert V.DIGEST_LEN == 16
    assert V.token_digest("abc") == hashlib.sha256(b"abc").digest()[:16]
    assert V.token_digest(b"abc") == V.token_digest("abc")


def test_roundtrip_and_counter_exactness():
    vc = V.VerdictCache()
    d = V.token_digest("t.ok")
    assert vc.get(d) is V.MISS
    assert vc.insert(d, {"sub": "t"}, token="t.ok", epoch=None)
    assert vc.get(d) == {"sub": "t"}
    st = vc.stats()
    assert st["vcache.lookups"] == 2
    assert st["vcache.hits"] + st["vcache.misses"] == \
        st["vcache.lookups"]
    assert st["vcache.stale_accepts"] == 0


def test_exp_clamp_never_serves_past_exp():
    vc = V.VerdictCache()
    now = time.time()
    tok = _tok("e", exp=now + 0.2)
    d = V.token_digest(tok)
    assert vc.insert(d, {"sub": "e", "exp": now + 0.2}, token=tok,
                     epoch=None)
    assert vc.get(d) != V.MISS
    time.sleep(0.25)
    assert vc.get(d) is V.MISS          # expired → miss, re-verify
    # already-expired claims never insert at all
    assert not vc.insert(d, {"exp": now - 1}, token=tok, epoch=None)


def test_nbf_clamp():
    vc = V.VerdictCache()
    d = V.token_digest("n")
    assert vc.insert(d, {"nbf": time.time() + 30}, token="n",
                     epoch=None)
    assert vc.get(d) is V.MISS          # not yet valid → engine decides


def test_exp_parsed_from_token_payload_for_raw_bytes():
    vc = V.VerdictCache()
    tok = _tok("p", exp=time.time() - 1)
    d = V.token_digest(tok)
    # raw-claims accept whose bytes do not parse as JSON with exp:
    # the clamp falls back to the token's own payload segment
    assert not vc.insert(d, b"not-json", token=tok, epoch=None)


def test_epoch_bump_invalidates_and_grace_retains():
    vc = V.VerdictCache()
    vc.set_epoch(1)
    d = V.token_digest("g.ok")
    vc.insert(d, b'{"sub":"g"}', token="g.ok", epoch=1)
    # bump with grace: previous-epoch entries survive the window
    vc.bump_epoch(2, grace_s=0.3)
    assert vc.get(d) != V.MISS
    time.sleep(0.35)
    assert vc.get(d) is V.MISS
    # two epochs behind is invalid even inside a fresh grace window
    vc.insert(d, b"x", token="g.ok", epoch=2)
    vc.bump_epoch(3, grace_s=5.0)
    assert vc.get(d) != V.MISS          # prev epoch, in grace
    vc.bump_epoch(4, grace_s=5.0)
    assert vc.get(d) is V.MISS          # 2 behind now
    assert vc.stats()["vcache.epoch_bumps"] == 3


def test_bump_same_epoch_is_noop():
    vc = V.VerdictCache()
    vc.set_epoch(5)
    d = V.token_digest("s.ok")
    vc.insert(d, b"v", token="s.ok", epoch=5)
    vc.bump_epoch(5)
    assert vc.get(d) != V.MISS
    assert vc.stats()["vcache.epoch_bumps"] == 0


def test_insert_racing_a_rotation_is_dropped():
    vc = V.VerdictCache()
    vc.set_epoch(1)
    d = V.token_digest("r.ok")
    vc.bump_epoch(2)
    # verified under epoch 1, rotation landed before the fill
    assert not vc.insert(d, b"v", token="r.ok", epoch=1)
    assert vc.get(d) is V.MISS


def test_only_terminal_rejects_cached():
    vc = V.VerdictCache()
    assert vc.cacheable(InvalidSignatureError("x"))
    assert vc.cacheable(MalformedTokenError("x"))
    assert vc.cacheable({"sub": "a"})
    assert not vc.cacheable(UnknownKeyIDError("x"))   # refresh may fix
    assert not vc.cacheable(ProtocolError("x"))       # transport
    assert not vc.cacheable(TimeoutError("x"))
    d = V.token_digest("u")
    assert not vc.insert(d, UnknownKeyIDError("x"), token="u",
                         epoch=None)
    assert vc.stats()["vcache.insert_skips"] == 1


def test_bounded_eviction():
    vc = V.VerdictCache(capacity=32, shards=4)
    for i in range(200):
        vc.insert(V.token_digest(f"t{i}"), b"v", token=f"t{i}",
                  epoch=None)
    assert vc.size() <= 32
    st = vc.stats()
    assert st["vcache.evictions"] >= 200 - 32
    assert st["vcache.inserts"] == 200


def test_ttl_bound_for_expless_tokens():
    vc = V.VerdictCache(max_ttl_s=0.2)
    d = V.token_digest("ttl.ok")
    vc.insert(d, b"v", token="ttl.ok", epoch=None)
    assert vc.get(d) != V.MISS
    time.sleep(0.25)
    assert vc.get(d) is V.MISS


def test_lookup_batch_uses_supplied_digests_and_falls_back():
    vc = V.VerdictCache()
    toks = ["a.ok", "b.ok"]
    d0 = V.token_digest("a.ok")
    vc.insert(d0, b"va", token="a.ok", epoch=None)
    # supplied digest for a, zero/None for b (native zero-row path)
    hits, miss_idx, digs = vc.lookup_batch(toks, digests=[d0, None])
    assert hits[0] == b"va" and miss_idx == [1]
    assert digs[1] == V.token_digest("b.ok")


# ---------------------------------------------------------------------------
# the batched fill against the per-entry insert it replaced
# ---------------------------------------------------------------------------

NOW = 1_700_000_000.0
FUT = NOW + 100
PAST = NOW - 1


def _ref_exp_nbf(verdict, token):
    """The per-entry exp/nbf reader, frozen as the batched fill's
    reference."""
    claims = None
    if isinstance(verdict, dict):
        claims = verdict
    elif isinstance(verdict, (bytes, bytearray, memoryview)):
        try:
            claims = json.loads(bytes(verdict))
        except (ValueError, UnicodeDecodeError):
            claims = None
    if claims is None and isinstance(token, str):
        parts = token.split(".")
        if len(parts) >= 2:
            seg = parts[1]
            try:
                pad = "=" * (-len(seg) % 4)
                claims = json.loads(base64.urlsafe_b64decode(seg + pad))
            except (ValueError, UnicodeDecodeError):
                claims = None
    if not isinstance(claims, dict):
        return (None, None)

    def _num(v):
        return float(v) if isinstance(v, (int, float)) \
            and not isinstance(v, bool) else None

    return (_num(claims.get("exp")), _num(claims.get("nbf")))


def _ref_insert(vc, digest, verdict, token, epoch, now):
    """The per-entry insert, frozen: one counter fold, one lock and
    one eviction scan per entry."""
    if not vc.cacheable(verdict) or epoch != vc._epoch:
        vc._count({"vcache.insert_skips": 1})
        return False
    exp, nbf = _ref_exp_nbf(verdict, token) \
        if not isinstance(verdict, BaseException) else (None, None)
    if exp is not None and now >= exp:
        vc._count({"vcache.insert_skips": 1})
        return False
    until = now + vc._max_ttl
    if exp is not None and exp < until:
        until = exp
    e = (verdict, nbf if nbf is not None else 0.0, until, epoch, exp)
    s = digest[0] & (vc._n_shards - 1)
    evicted = 0
    with vc._locks[s]:
        shard = vc._shards[s]
        if digest not in shard and len(shard) >= vc._cap_per_shard:
            shard.pop(next(iter(shard)))
            evicted = 1
        shard[digest] = e
    vc._count({"vcache.inserts": 1, "vcache.evictions": evicted})
    return True


def _snapshot(vc):
    """Every shard's entries in order, and the counters the reference
    keeps; repr, so a NaN exp compares equal to itself."""
    st = vc.stats()
    st.pop("vcache.fill_fallbacks")
    return repr([list(s.items()) for s in vc._shards]), st


def _tok_with(**claims):
    return f"h.{_payload(claims)}.s"


_BIG = b"9" * 400      # an int json.loads reads and float() refuses

FILL_CASES = {
    "int_exp_nbf": [b'{"sub":"a","exp":%d,"nbf":%d}' % (FUT, PAST)],
    "float_exp": [b'{"exp":%r,"nbf":%r}' % (FUT + 0.25, PAST - 0.5)],
    "exp_1e400": [b'{"exp":1e400}', b'{"exp":-1e400}'],
    "exp_true": [b'{"exp":true}', b'{"exp":false,"nbf":true}'],
    "exp_string": [b'{"exp":"%d"}' % PAST],
    "exp_null": [b'{"exp":null,"nbf":null}'],
    "exp_absent": [b'{"sub":"x"}', b"{}"],
    "exp_container": [b'{"exp":[%d],"nbf":{"t":%d}}' % (PAST, FUT)],
    "duplicate_exp": [b'{"exp":%d,"exp":%d}' % (PAST, FUT),
                      b'{"exp":%d,"exp":%d}' % (FUT, PAST),
                      b'{"exp":%d,"exp":"x"}' % PAST],
    "escaped_exp_key": [b'{"e\\u0078p":%d}' % PAST,
                        b'{"\\u006ebf":%d,"exp":%d}' % (FUT, FUT)],
    "nested_exp": [b'{"a":{"exp":%d,"nbf":%d}}' % (PAST, FUT)],
    "non_object": [(b"[1,2]", _tok_with(exp=PAST)), b'"s"', b"7"],
    "invalid_bytes_token_segment": [
        (b"not-json", _tok_with(exp=PAST)),
        (b"{bad", _tok_with(exp=FUT, nbf=FUT)), (b"", "a.b.c")],
    "bigint_exp": [b'{"exp":1%s}' % (b"0" * 20),
                   b'{"nbf":-1%s}' % (b"0" * 30)],
    "nan_exp": [b'{"exp":NaN}', b'{"nbf":NaN,"exp":%d}' % FUT],
    "dict_verdicts": [{"sub": "d", "exp": FUT, "nbf": PAST},
                      {"exp": PAST}, {"exp": True}, {"exp": FUT + 0.5},
                      {"sub": "no-exp"}],
    "exceptions": [InvalidSignatureError("bad"), MalformedTokenError("m"),
                   UnknownKeyIDError("k"), TimeoutError("t")],
    "already_expired": [b'{"exp":%d}' % PAST, b'{"exp":%r}' % NOW],
    "nbf_in_future": [b'{"exp":%d,"nbf":%d}' % (FUT, FUT - 1)],
    "overflow": [b'{"exp":%s}' % _BIG, b'{"nbf":-%s}' % _BIG,
                 {"exp": int(_BIG)}, b'{"exp":%d}' % FUT],
}


def _ref_insert_all(vc, digests, verdicts, tokens, epoch):
    n = 0
    for d, v, t in zip(digests, verdicts, tokens):
        try:
            n += _ref_insert(vc, d, v, t, epoch, NOW)
        except OverflowError:
            # the one repair: the per-entry insert raised out of the
            # whole batch here; the fill skips that entry instead
            vc._count({"vcache.insert_skips": 1})
    return n


@pytest.fixture(params=["native", "no_extension"])
def fill_mode(request, monkeypatch):
    if request.param == "native":
        assert V._native_exp_nbf() is not None, "native runtime unbuilt"
    else:
        monkeypatch.setattr(V, "_native_exp_nbf", lambda: None)
    return request.param


@pytest.mark.parametrize("case", sorted(FILL_CASES))
def test_insert_batch_matches_per_entry_insert(case, fill_mode):
    """Three batches into a small cache, each filled both ways: eight
    fillers (the shards fill up), the case's entries (all new while
    full: the one-pass eviction), then fillers and the case again
    (cached digests and repeats while full: the replayed order)."""
    case_items = [v if isinstance(v, tuple) else (v, _tok_with(exp=PAST))
                  for v in FILL_CASES[case]]
    keys = [f"{case}-{i}" for i in range(len(case_items))]
    fill = [(b'{"exp":%d}' % FUT, f"a.b{i}.c") for i in range(8)]
    fill_keys = [f"fill-{i}" for i in range(8)]
    ref = V.VerdictCache(capacity=8, shards=2, max_ttl_s=300)
    got = V.VerdictCache(capacity=8, shards=2, max_ttl_s=300)
    for vc in (ref, got):
        vc.set_epoch(3)
    for ks, items in ((fill_keys, fill), (keys, case_items),
                      (fill_keys[:3] + keys + keys, fill[:3] + case_items
                       + case_items)):
        digests = [V.token_digest(k) for k in ks]
        verdicts = [v for v, _ in items]
        tokens = [t for _, t in items]
        n_ref = _ref_insert_all(ref, digests, verdicts, tokens, 3)
        assert got.insert_batch(digests, verdicts, tokens, epoch=3,
                                now=NOW) == n_ref
        assert _snapshot(got) == _snapshot(ref)


@pytest.mark.parametrize("case", ["epoch_mismatch", "one_by_one"])
def test_insert_batch_matches_per_entry_insert_whole_batch(case,
                                                           fill_mode):
    """A racing rotation skips the batch whole; and ``insert`` (one
    entry of the batched path) matches the reference entry by entry."""
    verdicts = [v for c in sorted(FILL_CASES) for v in FILL_CASES[c]]
    verdicts = [v[0] if isinstance(v, tuple) else v for v in verdicts]
    digests = [V.token_digest(f"w{i}") for i in range(len(verdicts))]
    tokens = [_tok_with(exp=FUT)] * len(verdicts)
    ref = V.VerdictCache(capacity=16, shards=4)
    got = V.VerdictCache(capacity=16, shards=4)
    epoch = 3 if case == "one_by_one" else 2
    for vc in (ref, got):
        vc.set_epoch(3)
    _ref_insert_all(ref, digests, verdicts, tokens, epoch)
    if case == "one_by_one":
        for d, v, t in zip(digests, verdicts, tokens):
            got.insert(d, v, token=t, epoch=epoch, now=NOW)
    else:
        assert got.insert_batch(digests, verdicts, tokens, epoch=epoch,
                                now=NOW) == 0
    assert _snapshot(got) == _snapshot(ref)


def _one_shard(cap):
    vc = V.VerdictCache(capacity=cap, shards=1)
    old = [V.token_digest(f"old{i}") for i in range(cap)]
    vc.insert_batch(old, [b'{"n":%d}' % i for i in range(cap)],
                    epoch=None)
    return vc, old


@pytest.mark.parametrize("k", [1, 3, 4, 6])
def test_fill_evicts_exactly_the_k_oldest(k, monkeypatch):
    vc, old = _one_shard(4)
    folds = []
    real = vc._count
    monkeypatch.setattr(vc, "_count", lambda inc: (folds.append(inc),
                                                    real(inc)))
    new = [V.token_digest(f"new{i}") for i in range(k)]
    # k - 1 payloads the native scan declines (not JSON): the counter
    # says how many, in the batch's one fold
    verdicts = [b"not-json"] * (k - 1) + [b'{"exp":%d}' % (time.time()
                                                           + 60)]
    assert vc.insert_batch(new, verdicts, epoch=None) == k
    assert folds == [{"vcache.inserts": k, "vcache.insert_skips": 0,
                      "vcache.evictions": k,
                      "vcache.fill_fallbacks": k - 1}]
    assert list(vc._shards[0]) == (old + new)[k:]
    assert vc.stats()["vcache.evictions"] == k
    assert vc.stats()["vcache.fill_fallbacks"] == k - 1


def test_reinsert_keeps_its_place_and_evicts_nothing():
    vc, old = _one_shard(4)
    assert vc.insert_batch([old[1], old[1]], [b"x", b"y"], epoch=None) == 2
    assert list(vc._shards[0]) == old
    assert vc._shards[0][old[1]][0] == b"y"
    assert vc.stats()["vcache.evictions"] == 0
    # a present digest behind a new one in a full shard: the new one
    # evicts the oldest first, exactly as one-at-a-time inserts do
    new = V.token_digest("new")
    assert vc.insert_batch([new, old[0]], [b"n", b"o"], epoch=None) == 2
    assert list(vc._shards[0]) == [old[2], old[3], new, old[0]]
    assert vc.stats()["vcache.evictions"] == 2


def test_concurrent_fills_and_lookups_keep_the_books():
    """Fills from more threads than cores, with lookups between, under
    a short switch interval: every cached digest is distinct, so each
    insert is still cached or was evicted — a lost update breaks it."""
    import sys

    vc = V.VerdictCache(capacity=256, shards=4)
    exp = b'{"exp":%d}' % (time.time() + 600)
    errors = []

    def work(t):
        try:
            for b in range(40):
                digs = [V.token_digest(f"s{t}-{b}-{i}") for i in range(64)]
                assert vc.insert_batch(digs, [exp] * 64, epoch=None) == 64
                vc.lookup_batch([None] * 64, digests=digs)
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads) and not errors
    st = vc.stats()
    assert st["vcache.inserts"] == 16 * 40 * 64
    assert st["vcache.clamp_drops"] == 0
    assert st["vcache.size"] == st["vcache.inserts"] - \
        st["vcache.evictions"] <= 256


def test_import_entries_evicts_the_oldest():
    vc, old = _one_shard(4)
    vc.set_epoch(None)
    far = time.time() + 3600
    rows = [[V.token_digest(f"peer{i}").hex(), "eyJzdWIiOiJ4In0=", 0.0,
             far, None] for i in range(3)]
    assert vc.import_entries(rows, epoch=None) == 3
    assert list(vc._shards[0]) == old[3:] + [
        V.token_digest(f"peer{i}") for i in range(3)]
    st = vc.stats()
    assert st["vcache.evictions"] == 3 and st["vcache.peer_fills"] == 3


# ---------------------------------------------------------------------------
# batcher: in-flight replay dedup
# ---------------------------------------------------------------------------


def test_dedup_verifies_once_and_fans_out():
    ks = CountingStub()
    b = AdaptiveBatcher(ks, target_batch=64, max_wait_ms=20.0,
                        dedup=True)
    try:
        p1 = b.submit_nowait(["d.ok", "d.ok", "x.bad"])
        p2 = b.submit_nowait(["d.ok", "y.ok"])
        p1.event.wait(5)
        p2.event.wait(5)
        assert p1.results[0] == {"sub": "d.ok"}
        assert p1.results[1] == {"sub": "d.ok"}
        assert isinstance(p1.results[2], InvalidSignatureError)
        assert p2.results == [{"sub": "d.ok"}, {"sub": "y.ok"}]
        # the engine saw each distinct token ONCE per flush
        assert sorted(ks.seen) == sorted(["d.ok", "x.bad", "y.ok"]) \
            or ks.seen.count("d.ok") < 3   # (split flushes tolerated)
    finally:
        b.close(5)


def test_dedup_off_sends_everything():
    ks = CountingStub()
    b = AdaptiveBatcher(ks, target_batch=64, max_wait_ms=20.0,
                        dedup=False)
    try:
        p = b.submit_nowait(["d.ok", "d.ok", "d.ok"])
        p.event.wait(5)
        assert ks.seen.count("d.ok") == 3
    finally:
        b.close(5)


def test_dedup_async_pipeline_path():
    from cap_tpu.fleet.worker_main import StubKeySet as FleetStub

    ks = FleetStub(pipeline=1)
    b = AdaptiveBatcher(ks, target_batch=64, max_wait_ms=20.0,
                        dedup=True)
    try:
        p = b.submit_nowait(["a.ok"] * 8 + ["b.bad"] * 2)
        p.event.wait(5)
        assert p.results[:8] == [{"sub": "a.ok"}] * 8
        assert all(isinstance(r, InvalidSignatureError)
                   for r in p.results[8:])
    finally:
        b.close(5)


def test_dedup_counts_fanout():
    rec = telemetry.enable()
    rec.reset()
    ks = CountingStub()
    b = AdaptiveBatcher(ks, target_batch=64, max_wait_ms=20.0,
                        dedup=True)
    try:
        p = b.submit_nowait(["z.ok"] * 10)
        p.event.wait(5)
        assert rec.counters().get("batcher.dedup_fanout", 0) == 9
    finally:
        b.close(5)
        telemetry.disable()


# ---------------------------------------------------------------------------
# worker end-to-end (python chain; native chain below, build-gated)
# ---------------------------------------------------------------------------


def _drive(worker, seq):
    host, port = worker.address
    out = []
    with VerifyClient(host, port) as c:
        for batch in seq:
            out.append(c.verify_batch(batch))
    return out


def _norm(results):
    """Comparable verdict form: claims dict or (reject class head)."""
    out = []
    for batch in results:
        out.append([str(r).split(":", 1)[0] if isinstance(r, Exception)
                    else r for r in batch])
    return out


def _serve_decisions(rec):
    return {k: v for k, v in rec.counters().items()
            if k.startswith("decision.serve.")}


def _run_sweep(serve_native, vcache, seq, rotate_at=None):
    """One sweep run → (normalized verdicts, serve decision counters).

    rotate_at: batch index before which an epoch swap is applied —
    the mid-run invalidation leg of the parity pin."""
    rec = telemetry.enable()
    rec.reset()
    ks = CountingStub()
    w = VerifyWorker(ks, target_batch=128, max_wait_ms=2.0,
                     serve_native=serve_native, vcache=vcache)
    try:
        if serve_native and w.serve_chain != "native":
            pytest.skip("native serve chain unavailable")
        host, port = w.address
        out = []
        with VerifyClient(host, port) as c:
            for i, batch in enumerate(seq):
                if rotate_at is not None and i == rotate_at:
                    w.apply_keys({}, 2)
                out.append(c.verify_batch(batch))
        return _norm(out), _serve_decisions(rec)
    finally:
        w.close(10)
        telemetry.disable()


def _mixed_sequence(n_batches=24, seed=7):
    """Randomized repeat-heavy mix: hot tokens, rejects, an expiring
    token whose exp lands mid-run."""
    import random

    rng = random.Random(seed)
    exp_soon = time.time() + 0.8
    pool = ([_tok(f"hot{i}", ok=True, exp=time.time() + 3600)
             for i in range(4)]
            + [_tok(f"bad{i}", ok=False) for i in range(2)]
            + [_tok("expiring", ok=True, exp=exp_soon)])
    seq = []
    for _ in range(n_batches):
        seq.append([rng.choice(pool)
                    for _ in range(rng.randrange(1, 6))])
    return seq


@pytest.mark.parametrize("serve_native", [False, True])
def test_parity_cache_on_vs_off_mixed_sweep(serve_native):
    """The acceptance pin: bit-identical verdicts AND decision-reason
    counters, cache on vs off, incl. exp crossing + epoch swap."""
    seq = _mixed_sequence()
    on_verdicts, on_dec = _run_sweep(serve_native, True, seq,
                                     rotate_at=12)
    off_verdicts, off_dec = _run_sweep(serve_native, False, seq,
                                       rotate_at=12)
    assert on_verdicts == off_verdicts
    assert on_dec == off_dec


def test_worker_cache_hits_and_all_hit_fast_path():
    rec = telemetry.enable()
    rec.reset()
    ks = CountingStub()
    w = VerifyWorker(ks, target_batch=64, max_wait_ms=2.0,
                     vcache=True)
    try:
        out = _drive(w, [["h.x.ok", "r.x.bad"],
                         ["h.x.ok", "r.x.bad"],
                         ["h.x.ok"]])
        assert out[0][0] == {"sub": "h.x.ok"}
        assert isinstance(out[1][1], RemoteVerifyError)
        assert out[2][0] == out[0][0]
        c = rec.counters()
        assert c.get("vcache.hits", 0) >= 3
        assert c["vcache.lookups"] == c["vcache.hits"] \
            + c["vcache.misses"]
        # repeats never reached the engine
        assert ks.seen.count("h.x.ok") == 1
        assert ks.seen.count("r.x.bad") == 1
        # decision records fired for EVERY response, hit or miss
        dec = _serve_decisions(rec)
        assert dec["decision.serve.accept"] == 3
        assert dec["decision.serve.reject.bad_signature"] == 2
    finally:
        w.close(10)
        telemetry.disable()


def test_worker_epoch_swap_invalidates_cache():
    rec = telemetry.enable()
    rec.reset()
    ks = CountingStub()
    w = VerifyWorker(ks, max_wait_ms=2.0, vcache=True)
    try:
        _drive(w, [["e.x.ok"], ["e.x.ok"]])
        assert ks.seen.count("e.x.ok") == 1
        w.apply_keys({}, 9)
        _drive(w, [["e.x.ok"]])
        # rotation dropped the cached verdict → engine re-verified
        assert ks.seen.count("e.x.ok") == 2
        assert rec.counters().get("vcache.epoch_bumps", 0) == 1
        assert rec.counters().get("vcache.stale_accepts", 0) == 0
    finally:
        w.close(10)
        telemetry.disable()


def test_vcache_off_switch(monkeypatch):
    monkeypatch.setenv("CAP_SERVE_VCACHE", "0")
    ks = CountingStub()
    w = VerifyWorker(ks, max_wait_ms=2.0)
    try:
        assert w._vcache is None
        _drive(w, [["o.x.ok"], ["o.x.ok"]])
        assert ks.seen.count("o.x.ok") == 2
    finally:
        w.close(10)


# ---------------------------------------------------------------------------
# native chain: digest cross-parity (C sha256 == Python hashlib)
# ---------------------------------------------------------------------------


def _native_available():
    try:
        from cap_tpu.serve import native_serve

        return bool(getattr(native_serve.load(), "cap_vc_ok", False))
    except Exception:  # noqa: BLE001
        return False


@pytest.mark.skipif(not _native_available(),
                    reason="native serve runtime unavailable")
def test_native_reader_digests_match_python_hashing():
    rec = telemetry.enable()
    rec.reset()
    ks = CountingStub()
    w = VerifyWorker(ks, max_wait_ms=2.0, serve_native=True,
                     vcache=True)
    try:
        assert w.serve_chain == "native"
        assert w._native._native_digests
        tok = "nd.x.ok"
        _drive(w, [[tok]])
        # the cache was filled under the C-computed digest; a lookup
        # by the PYTHON digest must hit — the two definitions agree
        assert w._vcache.get(V.token_digest(tok)) is not V.MISS
        _drive(w, [[tok]])
        assert ks.seen.count(tok) == 1
        assert rec.counters().get("vcache.hits", 0) >= 1
    finally:
        w.close(10)
        telemetry.disable()


# ---------------------------------------------------------------------------
# client tier (FleetClient)
# ---------------------------------------------------------------------------


def test_fleet_client_tier_short_circuits_before_wire():
    from cap_tpu.fleet.worker_main import StubKeySet as FleetStub

    rec = telemetry.enable()
    rec.reset()
    ks = CountingStub()
    w = VerifyWorker(ks, max_wait_ms=2.0, vcache=False)
    try:
        from cap_tpu.fleet import FleetClient

        cl = FleetClient([w.address], fallback=FleetStub(),
                         vcache=True)
        o1 = cl.verify_batch(["fc.x.ok", "fb.x.bad"])
        o2 = cl.verify_batch(["fc.x.ok", "fb.x.bad"])
        assert o1[0] == o2[0] == {"sub": "fc.x.ok"}
        assert type(o1[1]) is type(o2[1])
        # the repeat never crossed the wire
        assert ks.seen.count("fc.x.ok") == 1
        snap = cl.snapshot()
        assert snap["vcache"]["vcache.hits"] == 2
        # router decision counters fired per CALL, hit or miss
        dec = {k: v for k, v in rec.counters().items()
               if k.startswith("decision.router.")}
        assert dec["decision.router.accept"] == 2
    finally:
        w.close(10)
        telemetry.disable()


def test_fleet_client_bare_endpoint_ttl_configurable(monkeypatch):
    """Bare-endpoint clients have NO pool-epoch visibility: the hard
    TTL is their only rotation bound. CAP_CLIENT_VCACHE_TTL makes it
    configurable (default 30 s unchanged); past the TTL the epoch-less
    entry EXPIRES and the next call goes back to the engine."""
    from cap_tpu.fleet import FleetClient
    from cap_tpu.fleet.worker_main import StubKeySet as FleetStub

    ks = CountingStub()
    w = VerifyWorker(ks, max_wait_ms=2.0, vcache=False)
    try:
        # default path: 30 s (unchanged from r14)
        cl = FleetClient([w.address], fallback=FleetStub(),
                         vcache=True)
        assert cl._vcache._max_ttl == 30.0
        # pool-backed clients keep their long TTL (epoch clamp covers
        # them) — the env knob must not touch that path
        monkeypatch.setenv("CAP_CLIENT_VCACHE_TTL", "0.3")
        cl = FleetClient([w.address], fallback=FleetStub(),
                         vcache=True)
        assert cl._vcache._max_ttl == 0.3
        cl.verify_batch(["ttl.x.ok"])
        cl.verify_batch(["ttl.x.ok"])
        assert ks.seen.count("ttl.x.ok") == 1     # hit inside TTL
        time.sleep(0.35)
        cl.verify_batch(["ttl.x.ok"])             # epoch-less expiry
        assert ks.seen.count("ttl.x.ok") == 2
        st = cl.snapshot()["vcache"]
        assert st["vcache.stale_accepts"] == 0
        # a broken value falls back to the default, never to forever
        monkeypatch.setenv("CAP_CLIENT_VCACHE_TTL", "bogus")
        cl = FleetClient([w.address], vcache=True)
        assert cl._vcache._max_ttl == 30.0
        monkeypatch.setenv("CAP_CLIENT_VCACHE_TTL", "0")
        cl = FleetClient([w.address], vcache=True)
        assert cl._vcache._max_ttl > 0
    finally:
        w.close(10)


def test_fleet_client_tier_parity_on_vs_off():
    from cap_tpu.fleet import FleetClient
    from cap_tpu.fleet.worker_main import StubKeySet as FleetStub

    ks = CountingStub()
    w = VerifyWorker(ks, max_wait_ms=2.0, vcache=False)
    try:
        seq = _mixed_sequence(n_batches=10, seed=3)
        outs = {}
        for state in (True, False):
            cl = FleetClient([w.address], fallback=FleetStub(),
                             vcache=state)
            outs[state] = _norm([cl.verify_batch(b) for b in seq])
        assert outs[True] == outs[False]
    finally:
        w.close(10)


# ---------------------------------------------------------------------------
# dedup preserves per-request trace timelines (acceptance: capstat
# --trace reassembles a deduped member's timeline end-to-end)
# ---------------------------------------------------------------------------


def test_deduped_members_keep_their_trace_timelines():
    import sys
    import os

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    from tools import capstat

    rec = telemetry.enable()
    rec.reset()
    ks = CountingStub()
    # big window so both traced submissions coalesce into ONE flush
    w = VerifyWorker(ks, target_batch=4096, max_wait_ms=120.0,
                     vcache=True)
    try:
        host, port = w.address
        tids = []
        results = []

        def one():
            with telemetry.trace() as tid:
                tids.append(tid)
                with VerifyClient(host, port) as c:
                    from cap_tpu.serve import protocol as P

                    P.send_request(c._sock, ["tr.x.ok"], trace=tid)
                    ftype, entries, echo = \
                        c._reader.recv_frame_ex()
                    results.append((ftype, entries, echo))

        th = [threading.Thread(target=one) for _ in range(2)]
        for t in th:
            t.start()
        for t in th:
            t.join(15)
        assert len(results) == 2
        # the engine verified the duplicate ONCE
        assert ks.seen.count("tr.x.ok") == 1
        flight = [{"trace": e.get("trace"), "spans": e.get("spans", [])}
                  for e in rec.flight_slowest()]
        for tid in tids:
            spans = capstat.reassemble_trace(
                tid, [{"flight": [e for e in flight
                                  if e["trace"] == tid]}])
            names = {s["name"] for s in spans}
            # end-to-end: wire dequeue + batcher fill present for BOTH
            # members even though they shared one verify
            assert telemetry.SPAN_WORKER_DEQUEUE in names, \
                (tid, names)
            assert telemetry.SPAN_BATCHER_FILL in names, (tid, names)
    finally:
        w.close(10)
        telemetry.disable()
