"""Native telemetry plane: the bit-exact parity contract (ISSUE 8).

The plane (runtime/native/telemetry_native.cpp) folds the serve
surface's decision accounting in C. The hard requirement under test:
counters, histogram bucket counts, and decision-ring sample positions
must be BIT-IDENTICAL to the Python fold (obs/decision.record_batch)
— pinned here by a fuzz sweep that runs an adversarial header corpus,
every error class in the taxonomy, and ≥1k random mixed batches
through both recorders, comparing counter maps and ring entries after
every batch. Plus: the graceful-degradation matrix
(CAP_SERVE_NATIVE_OBS=0, plane-less .so → Python fold) and the
cross-chain equality gate (same load on the python chain and the
native chain must produce identical decision counters).
"""

import base64
import inspect
import json
import random
import time

import pytest

from cap_tpu import errors as errors_mod
from cap_tpu import telemetry
from cap_tpu.fleet.worker_main import StubKeySet
from cap_tpu.obs import decision
from cap_tpu.serve.client import VerifyClient
from cap_tpu.serve.worker import VerifyWorker

try:
    from cap_tpu.serve import native_serve
    HAVE_TEL = bool(getattr(native_serve.load(), "cap_tel_ok", False))
except Exception:  # noqa: BLE001 - no compiler / unbuildable
    HAVE_TEL = False

needs_tel = pytest.mark.skipif(
    not HAVE_TEL, reason="native telemetry plane not built "
    "(no compiler on this host?)")


def make_plane():
    return native_serve.NativeTelemetryPlane()


# ---------------------------------------------------------------------------
# registry pins: the index vocabularies the native plane counts by
# ---------------------------------------------------------------------------

def test_reason_index_covers_registry_in_fixed_order():
    assert set(decision.REASON_INDEX) == set(decision.REASON_CLASSES)
    assert len(decision.REASON_INDEX) == len(decision.REASON_CLASSES)
    # order is native ABI: spot-pin the ends so a reorder cannot slip
    assert decision.REASON_INDEX[0] == decision.REASON_MALFORMED
    assert decision.REASON_INDEX[-1] == decision.REASON_INTERNAL


def test_latency_bucket_index_matches_labels():
    for lat in (None, 0.0, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1,
                0.5, 1.0, 7.0):
        idx = decision.latency_bucket_index(lat)
        assert decision.LAT_BUCKET_INDEX[idx] == \
            decision.latency_bucket(lat)


def test_reason_index_matches_classify_for_all_error_classes():
    for _, cls in inspect.getmembers(errors_mod, inspect.isclass):
        if not issubclass(cls, errors_mod.CapError):
            continue
        err = cls("x")
        assert decision.REASON_INDEX[decision.reason_index(err)] == \
            decision.classify(err)


@needs_tel
def test_layout_handshake_enables_plane():
    assert native_serve.load().cap_tel_ok


# ---------------------------------------------------------------------------
# histogram bucket parity: lower_bound over the SAME bounds must place
# every value in the SAME bucket bisect_left picks
# ---------------------------------------------------------------------------

@needs_tel
def test_histogram_bucket_counts_bit_identical():
    plane = make_plane()
    try:
        h = telemetry.Histogram()
        rng = random.Random(13)
        vals = [rng.uniform(0.1, 10.0) ** rng.uniform(-8.0, 8.0)
                for _ in range(4000)]
        # edges: exact bounds, zero, negatives, overflow, min/max
        vals += [0.0, -3.5, 1e-9, telemetry._HIST_LO, telemetry._HIST_HI,
                 5e9, telemetry.BUCKET_BOUNDS[0],
                 telemetry.BUCKET_BOUNDS[17],
                 telemetry.BUCKET_BOUNDS[-1]]
        for v in vals:
            h.add(v)
            plane.observe(native_serve.NativeTelemetryPlane
                          .SERIES_NAMES.index("serve.native.request_s"),
                          v)
        st = plane._hist_state(0)
        assert st["buckets"] == {str(i): c for i, c
                                 in enumerate(h.counts) if c}
        assert st["count"] == h.count
        assert st["min"] == h.vmin and st["max"] == h.vmax
        # and the state merges like any recorder series
        merged = telemetry.merge_snapshots([
            {"series": {"s": st}}, {"series": {"s": st}}])
        assert merged["series"]["s"]["count"] == 2 * h.count
    finally:
        plane.destroy()


# ---------------------------------------------------------------------------
# THE parity sweep: malformed corpus + full taxonomy + random batches
# ---------------------------------------------------------------------------

def _b64(obj) -> str:
    return base64.urlsafe_b64encode(
        json.dumps(obj).encode()).rstrip(b"=").decode()


def adversarial_segs():
    """Header segments covering every classification outcome: valid
    families, bad base64, bad JSON, non-dict JSON, missing/odd alg,
    kid variants, empty, oversize, non-ASCII."""
    return [
        _b64({"alg": "ES256", "kid": "k1"}),
        _b64({"alg": "ES384"}),
        _b64({"alg": "RS256", "kid": "longish-kid-" + "x" * 40}),
        _b64({"alg": "PS512", "kid": ""}),
        _b64({"alg": "EdDSA", "kid": "ed-key"}),
        _b64({"alg": "ML-DSA-44", "kid": "pq1"}),
        _b64({"alg": "ML-DSA-87"}),
        _b64({"alg": "HS256", "kid": "hmac"}),      # family "other"
        _b64({"alg": 5, "kid": "numeric-alg"}),     # alg not a string
        _b64({"kid": "no-alg"}),
        _b64([1, 2, 3]),                            # non-dict JSON
        _b64({"alg": "ES256", "kid": 123}),         # kid not a string
        "!!!!not-base64!!!!",
        "eyJhbGciOiJFUzI1Ni",                       # truncated b64
        base64.urlsafe_b64encode(b"\xff\xfe\x00ug").decode(),  # not JSON
        "",                                         # empty segment
        "x" * 1500,                                 # over the 1024 bound
        "ünïcode-segment",                          # non-ASCII
        "A",                                        # 1 char (bad length)
    ]


def taxonomy_rejects():
    out = []
    for _, cls in sorted(inspect.getmembers(errors_mod,
                                            inspect.isclass)):
        if issubclass(cls, errors_mod.CapError):
            out.append(cls(f"{cls.__name__} happened"))
    out += [ConnectionError("conn"), TimeoutError("slow"),
            OSError("io"), ValueError("odd")]  # unmapped → internal
    return out


def _run_both(batches):
    """Run the same batch stream through record_batch (fresh recorder)
    and through the native plane (classify → learn → fold → pump into
    a second fresh recorder); assert counters (decision AND tenant),
    per-tenant latency series, and decision rings are identical after
    EVERY batch — the r13 contract extended to the tenant plane."""
    rec_py = telemetry.Recorder()
    rec_nat = telemetry.Recorder()
    plane = make_plane()
    try:
        for bi, (results, tokens, lat, trace) in enumerate(batches):
            with telemetry.recording(rec_py):
                decision.record_batch("serve", results, tokens=tokens,
                                      latency_s=lat, trace=trace)
            plane.fold_batch(results, tokens=tokens, latency_s=lat,
                             trace=trace)
            plane.pump(rec_nat)
            py_c = {k: v for k, v in rec_py.counters().items()
                    if k.startswith(("decision.", "tenant."))}
            nat_c = {k: v for k, v in plane.counters().items()
                     if k.startswith(("decision.", "tenant."))}
            assert py_c == nat_c, f"counter divergence at batch {bi}"
            py_s = {k: v for k, v
                    in rec_py.snapshot()["series"].items()
                    if k.startswith("tenant.")}
            nat_s = {k: v for k, v
                     in plane.snapshot()["series"].items()
                     if k.startswith("tenant.")}
            assert py_s == nat_s, \
                f"tenant series divergence at batch {bi}"
            assert rec_py.decisions() == rec_nat.decisions(), \
                f"ring divergence at batch {bi}"
    finally:
        plane.destroy()


@needs_tel
def test_parity_sweep_malformed_corpus_and_taxonomy():
    segs = adversarial_segs()
    rejects = taxonomy_rejects()
    batches = []
    # one batch per adversarial segment, mixed verdicts
    for i, seg in enumerate(segs):
        tokens = [f"{seg}.p{i}.sig", f"{seg}.q{i}.sig"]
        batches.append(([{"sub": "a"}, rejects[i % len(rejects)]],
                        tokens, 0.002, None))
    # one batch carrying the ENTIRE error taxonomy at once
    tokens = [f"{segs[i % len(segs)]}.t{i}.s"
              for i in range(len(rejects))]
    batches.append((list(rejects), tokens, 0.5, "ab12cd34ab12cd34"))
    # tokens=None (family unknown) and empty batch
    batches.append(([{"ok": 1}, rejects[0]], None, None, None))
    batches.append(([], [], 0.1, None))
    # non-string tokens ride the guarded walk on both sides
    batches.append(([{"ok": 1}, b"bytes-are-rejected-shape"],
                    ["tok.ok", 1234], 0.01, None))
    _run_both(batches)


@needs_tel
def test_parity_sweep_random_mixed_batches():
    """≥1k random batches: random sizes, verdict mixes, header pools,
    latencies, traces — counters and ring positions must stay
    bit-identical throughout."""
    rng = random.Random(0xCAB)
    segs = adversarial_segs()
    segs += [_b64({"alg": "ES256", "kid": f"k{i}"}) for i in range(24)]
    rejects = taxonomy_rejects()
    lats = [None, 0.0004, 0.004, 0.04, 0.4, 4.0]
    batches = []
    for i in range(1100):
        n = rng.randrange(0, 24)
        results = []
        tokens = []
        for j in range(n):
            seg = rng.choice(segs)
            tokens.append(f"{seg}.{i}-{j}.sig")
            if rng.random() < 0.35:
                results.append(rng.choice(rejects))
            elif rng.random() < 0.5:
                results.append(b'{"raw":1}')
            else:
                results.append({"sub": f"s{j}"})
        trace = f"{rng.randrange(1 << 32):08x}" \
            if rng.random() < 0.3 else None
        use_tokens = tokens if rng.random() < 0.9 else None
        batches.append((results, use_tokens, rng.choice(lats), trace))
    _run_both(batches)


@needs_tel
def test_parity_sweep_tenant_attribution():
    """Tenant extension of the parity contract (ISSUE 14 acceptance):
    issuer-bearing tokens — a stable multi-tenant mix, a unique-issuer
    overflow flood past the table cap, adversarial payloads (missing /
    non-string / overlong issuers, undecodable segments) — produce
    bit-identical per-tenant counters AND latency histograms through
    both folds, after every batch."""
    rng = random.Random(0x7E4A47)
    rejects = taxonomy_rejects()

    def tok(i, iss, suffix="sig"):
        hdr = _b64({"alg": "ES256", "kid": f"ten-{i}"})
        return f"{hdr}.{_b64({'iss': iss})}.{suffix}"

    # stable tenants + a flood that overflows the bounded table
    stable = [tok(i, f"https://idp-{i}.example") for i in range(12)]
    flood = [tok(1000 + i, f"https://flood-{i}.unique.example")
             for i in range(decision.TENANT_CAP + 30)]
    adversarial = [
        _b64({"alg": "ES256", "kid": "t-noiss"}) + "."
        + _b64({"sub": "x"}) + ".s",                    # no iss
        _b64({"alg": "RS256", "kid": "t-numiss"}) + "."
        + _b64({"iss": 99}) + ".s",                     # non-str iss
        _b64({"alg": "ES256", "kid": "t-longiss"}) + "."
        + _b64({"iss": "x" * 1500}) + ".s",             # overlong iss
        _b64({"alg": "ES256", "kid": "t-badpay"}) + ".!!!.s",
        "no-dots-at-all",
    ]
    pool = stable + adversarial
    lats = [None, 0.0004, 0.004, 0.04, 0.4]
    batches = []
    for i in range(300):
        n = rng.randrange(1, 16)
        tokens = [rng.choice(pool) for _ in range(n)]
        if i % 3 == 0:       # flood pressure in a third of batches
            tokens += [flood[rng.randrange(len(flood))]
                       for _ in range(rng.randrange(1, 6))]
        results = [rng.choice(rejects) if rng.random() < 0.4
                   else {"s": j} for j in range(len(tokens))]
        batches.append((results, tokens, rng.choice(lats), None))
    _run_both(batches)
    # the flood hit the bounded table: "other" traffic was folded and
    # the exact equation held through BOTH folds (checked per batch)
    assert decision.TENANTS.size() == decision.TENANT_CAP
    # the table is process-global: leave it empty for the tests that
    # run after this one in the same process
    decision._HDR_CACHE.clear()
    decision.TENANTS.reset()


@needs_tel
def test_exemplar_ring_overflow_keeps_newest_256():
    """More than MAX_DECISION_ENTRIES exemplars between pumps: both
    sides keep the NEWEST 256 (deque(maxlen) vs native FIFO drop)."""
    rec_py = telemetry.Recorder()
    rec_nat = telemetry.Recorder()
    plane = make_plane()
    try:
        seg = _b64({"alg": "ES256", "kid": "ring"})
        # 300 batches of 17 accepts -> >256 sampled entries, no pump
        for i in range(300):
            results = [{"s": 1}] * 17
            tokens = [f"{seg}.{i}-{j}.x" for j in range(17)]
            with telemetry.recording(rec_py):
                decision.record_batch("serve", results, tokens=tokens,
                                      latency_s=0.002)
            plane.fold_batch(results, tokens=tokens, latency_s=0.002)
        drained = 0
        while True:
            n = plane.pump(rec_nat)
            drained += n
            if not n:
                break
        assert drained <= telemetry.MAX_DECISION_ENTRIES
        assert rec_py.decisions() == rec_nat.decisions()
        assert plane.counters()["serve.native.exemplar_drops"] > 0
    finally:
        plane.destroy()


# ---------------------------------------------------------------------------
# e2e: the chain wires the plane — and degrades gracefully without it
# ---------------------------------------------------------------------------

def _drive(worker, n=6):
    host, port = worker.address
    with VerifyClient(host, port) as cl:
        for i in range(n):
            out = cl.verify_batch([f"w{i}-a.ok", f"w{i}-b.ok",
                                   f"w{i}-c.bad"])
            assert len(out) == 3
    time.sleep(0.3)


@needs_tel
def test_chain_decision_counters_equal_across_chains():
    """The cross-chain gate: identical load on the python chain and
    the native chain (plane on) must produce IDENTICAL serve-surface
    decision counters — obs costs less natively, never counts
    differently."""
    telemetry.enable()
    telemetry.active().reset()
    w = VerifyWorker(StubKeySet(), max_wait_ms=1.0)  # python chain
    try:
        _drive(w)
        py_counters = {
            k: v for k, v in w.stats()["counters"].items()
            if k.startswith("decision.serve.")}
    finally:
        w.close(deadline_s=10)
        telemetry.disable()

    telemetry.enable(telemetry.Recorder())
    w = VerifyWorker(StubKeySet(), serve_native=True, max_wait_ms=1.0)
    try:
        assert w.serve_chain == "native"
        assert w._native.obs_plane is not None
        _drive(w)
        st = w.stats()
        nat_counters = {
            k: v for k, v in st["counters"].items()
            if k.startswith("decision.serve.")}
        assert nat_counters == py_counters
        # the merged snapshot carries them too (scrape/postmortem path)
        assert {k: v for k, v
                in st["snapshot"]["counters"].items()
                if k.startswith("decision.serve.")} == py_counters
        # the plane's series merged in and summarized
        assert "serve.native.request_s" in st["series"]
        # exemplars landed in the recorder's ring via the pump
        rec = telemetry.active()
        assert any(d.get("surface") == "serve"
                   for d in rec.decisions())
        # nothing double-counted: the recorder itself holds NO native
        # decision counters (they live in the plane)
        assert not any(k.startswith("decision.serve.")
                       for k in rec.counters())
    finally:
        w.close(deadline_s=10)
        telemetry.disable()


@needs_tel
def test_native_obs_env_kill_switch_falls_back_to_python_fold(
        monkeypatch):
    """CAP_SERVE_NATIVE_OBS=0: native chain still serves, the decision
    fold runs in Python, counters land in the recorder as before."""
    monkeypatch.setenv("CAP_SERVE_NATIVE_OBS", "0")
    telemetry.enable(telemetry.Recorder())
    w = VerifyWorker(StubKeySet(), serve_native=True, max_wait_ms=1.0)
    try:
        assert w.serve_chain == "native"
        assert w._native.obs_plane is None
        _drive(w, n=3)
        rec = telemetry.active()
        counters = rec.counters()
        assert counters.get("decision.serve.accept") == 6
        assert counters.get(
            "decision.serve.reject.bad_signature") == 3
        assert w._obs_gauges()["serve.native.obs_plane"] == 0.0
    finally:
        w.close(deadline_s=10)
        telemetry.disable()


@needs_tel
def test_obs_off_means_no_plane_and_no_decision_counters():
    """Telemetry disabled: the plane never attaches and the serve
    chain does zero decision accounting (the obs-off bench point)."""
    telemetry.disable()
    w = VerifyWorker(StubKeySet(), serve_native=True, max_wait_ms=1.0)
    try:
        assert w.serve_chain == "native"
        assert w._native.obs_plane is None
        _drive(w, n=2)
        st = w.stats()
        assert not any(k.startswith("decision.")
                       for k in st["counters"])
    finally:
        w.close(deadline_s=10)


@needs_tel
def test_occupancy_counters_equal_across_chains():
    """r22 randomized parity sweep: the same seeded frame plan driven
    sequentially through each chain must land BIT-EQUAL occupancy
    dispatch/interval counters — sequential blocking drives make every
    frame exactly one flush, so N frames == N dispatches == N stub
    intervals on both chains, timing-independent. (Flush-reason NAMES
    legitimately differ — "timeout" python, "handoff" native — so the
    equality set is the dispatch/interval counters; the flush EQUATION
    is asserted per chain instead.)"""
    from cap_tpu.obs import occupancy

    rng = random.Random(22)
    plan = [[f"occ{i}-{j}.ok" for j in range(rng.randint(1, 4))]
            for i in range(12)]

    def run(native):
        occupancy.reset()
        telemetry.enable(telemetry.Recorder())
        w = VerifyWorker(StubKeySet(), serve_native=native,
                         max_wait_ms=1.0)
        try:
            if native:
                assert w.serve_chain == "native"
            host, port = w.address
            with VerifyClient(host, port) as cl:
                for frame in plan:
                    assert len(cl.verify_batch(frame)) == len(frame)
            time.sleep(0.3)
            st = w.stats()
            return dict(st["counters"]), set(st["series"])
        finally:
            w.close(deadline_s=10)
            telemetry.disable()
            occupancy.reset()

    py_c, _ = run(native=False)
    nat_c, nat_series = run(native=True)

    eq = ("device.dispatches", "device.stub.intervals")
    assert {k: py_c.get(k) for k in eq} \
        == {k: nat_c.get(k) for k in eq} \
        == {"device.dispatches": len(plan),
            "device.stub.intervals": len(plan)}
    for c in (py_c, nat_c):
        flush_sum = sum(v for k, v in c.items()
                        if k.startswith("batcher.flush."))
        assert flush_sum == c.get("batcher.flushes") \
            == c.get("device.dispatches")
        assert c.get("device.wall_us", 0) > 0
        assert 0 <= c.get("device.busy_us", 0) <= c["device.wall_us"]
    # native ring-wait handshake held: measured series, zero fallbacks
    assert nat_c.get("serve.native.occ_fallbacks", 0) == 0
    assert "queue.ring_wait_s" in nat_series


@needs_tel
def test_ring_hwm_gauge_resets_on_scrape():
    telemetry.enable(telemetry.Recorder())
    w = VerifyWorker(StubKeySet(), serve_native=True, max_wait_ms=1.0)
    try:
        _drive(w, n=4)
        g = w._obs_gauges()
        assert "serve.native.ring_hwm" in g
        assert g["serve.native.ring_hwm"] >= 0.0
        # the scrape rearmed the mark at live depth (idle now → ~0)
        assert w._native.ring_hwm(reset=False) <= \
            g["serve.native.ring_hwm"]
    finally:
        w.close(deadline_s=10)
        telemetry.disable()
