"""Decision records, SLO engine, postmortems, bench-trend sentinel.

Tier-1 and dependency-free (stub engines, no crypto, no jax): the
decision/reason layer (cap_tpu.obs.decision) including the
wire-roundtrip parity that makes four-surface reason accounting
structural, the SLO burn-rate engine and ``capstat --slo`` exit
codes, the postmortem writer/reader/renderer, and the BENCH series
regression sentinel."""

import inspect
import json
import os
import socket
import time
import urllib.request

import pytest

from cap_tpu import errors as errors_mod
from cap_tpu import telemetry
from cap_tpu.errors import CapError, InvalidSignatureError
from cap_tpu.fleet import FleetClient
from cap_tpu.fleet.worker_main import StubKeySet
from cap_tpu.obs import decision, postmortem, slo
from cap_tpu.serve import obs as obs_mod
from cap_tpu.serve.client import RemoteVerifyError
from cap_tpu.serve.worker import VerifyWorker
from tools import bench_trend, capstat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.disable()
    yield
    telemetry.disable()


def _error_classes():
    """Every concrete CapError subclass defined in cap_tpu/errors.py."""
    return [cls for _, cls in inspect.getmembers(errors_mod,
                                                 inspect.isclass)
            if issubclass(cls, CapError)]


# ---------------------------------------------------------------------------
# reason taxonomy: coverage + doc pin
# ---------------------------------------------------------------------------

def test_reason_table_covers_whole_error_taxonomy():
    """Pin: every sentinel error class maps to a registered reason —
    a new error class added without a reason mapping fails here (same
    pattern as the SPAN_NAMES doc pin)."""
    for cls in _error_classes():
        assert cls.__name__ in decision.REASON_FOR_ERROR, \
            f"{cls.__name__} missing from REASON_FOR_ERROR"
    for name, reason in decision.REASON_FOR_ERROR.items():
        assert reason in decision.REASON_CLASSES, (name, reason)


def test_observability_doc_pins_reason_table():
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as f:
        doc = f.read()
    for reason in sorted(decision.REASON_CLASSES):
        assert f"`{reason}`" in doc, \
            f"reason class {reason} missing from docs/OBSERVABILITY.md"


@pytest.mark.parametrize("cls", _error_classes(),
                         ids=lambda c: c.__name__)
def test_wire_roundtrip_reason_parity(cls):
    """Satellite pin (the dependency-free core of four-surface
    parity): an error INSTANCE and its CVB1 wire form — the
    ``"<Class>: <message>"`` payload the worker sends, seen by the
    router as RemoteVerifyError — classify to the SAME reason."""
    err = cls()
    direct = decision.classify(err)
    wire_payload = f"{type(err).__name__}: {err}"
    assert decision.classify(RemoteVerifyError(wire_payload)) == direct
    assert direct in decision.REASON_CLASSES


def test_classify_specifics():
    assert decision.classify(InvalidSignatureError()) == "bad_signature"
    assert decision.classify(
        errors_mod.UnknownKeyIDError()) == "unknown_kid"
    assert decision.classify(errors_mod.ExpiredTokenError()) == "expired"
    assert decision.classify(
        errors_mod.MalformedTokenError()) == "malformed"
    assert decision.classify(ConnectionResetError()) == "transport"
    assert decision.classify(socket.timeout()) == "transport"
    assert decision.classify(ValueError("x")) == "internal"
    # unknown remote class name degrades to internal, never raises
    assert decision.classify(
        RemoteVerifyError("SomethingNewError: ?")) == "internal"


def test_family_and_kid_extraction():
    rs = "eyJhbGciOiJSUzI1NiIsImtpZCI6ImswIn0.e30.c2ln"
    fam, kid = decision.token_family_kid(rs)
    assert fam == "rs"
    assert kid == decision.hash_kid("k0")
    assert len(kid) == 12 and kid != "k0"
    assert decision.token_family_kid("garbage")[0] == "unknown"
    assert decision.token_family_kid("a.ok") == ("unknown", None)
    assert decision.family_for_alg("ES512") == "es"
    assert decision.family_for_alg("EdDSA") == "ed"
    assert decision.family_for_alg("HS256") == "other"


def test_latency_buckets():
    assert decision.latency_bucket(None) == "na"
    assert decision.latency_bucket(0.0005) == "lt1ms"
    assert decision.latency_bucket(0.5) == "lt1s"
    assert decision.latency_bucket(3.0) == "ge1s"


# ---------------------------------------------------------------------------
# recording: counters, ring, redaction
# ---------------------------------------------------------------------------

def test_record_batch_counters_and_ring():
    with telemetry.recording() as rec:
        with telemetry.trace() as tid:
            decision.record_batch(
                "serve",
                [{"sub": "a"}, InvalidSignatureError(), b"raw-ok"],
                tokens=["eyJhbGciOiJSUzI1NiJ9.e30.c2ln", "x.bad",
                        "eyJhbGciOiJFUzI1NiJ9.e30.c2ln"],
                latency_s=0.002)
        c = rec.counters()
        assert c["decision.serve.accept"] == 2
        assert c["decision.serve.reject.bad_signature"] == 1
        assert c["decision.serve.family.rs"] == 1
        assert c["decision.serve.family.es"] == 1
        ring = rec.decisions()
        assert ring, "first occurrences must be ring-sampled"
        for entry in ring:
            assert entry["surface"] == "serve"
            assert entry["lat"] == "lt10ms"
            assert entry["trace"] == tid
        reject = next(e for e in ring if e["verdict"] == "reject")
        assert reject["reason"] == "bad_signature"


def test_record_batch_noop_when_telemetry_off():
    decision.record_batch("serve", [InvalidSignatureError()],
                          tokens=["a.b"])   # must not raise, no recorder


def test_decision_ring_is_bounded():
    with telemetry.recording() as rec:
        for i in range(10_000):
            decision.record_batch("serve", [{"s": 1}])
        assert len(rec.decisions()) <= telemetry.MAX_DECISION_ENTRIES


def test_checked_entry_rejects_token_material():
    with pytest.raises(ValueError):
        decision._checked_entry({"family": "eyJhbGciOiJSUzI1NiJ9"})
    with pytest.raises(ValueError):
        decision._checked_entry({"reason": "a" * 100})


def test_checked_entry_space_rule_is_str_isspace():
    """The redaction check's whitespace rule rejects exactly the
    characters str.isspace() calls whitespace, over all of Unicode."""
    for cp in range(0x110000):
        ch = chr(cp)
        assert bool(decision._SPACE.search(f"a{ch}b")) == ch.isspace(), cp
    with pytest.raises(ValueError):
        decision._checked_entry({"family": "r\u2003s"})


def test_counter_names_pass_redaction_check():
    """Every counter key the layer can emit survives check_name."""
    for surface in decision.SURFACES:
        for reason in decision.REASON_CLASSES:
            telemetry.check_name(f"decision.{surface}.reject.{reason}")
        for fam in decision.FAMILIES:
            telemetry.check_name(f"decision.{surface}.family.{fam}")
        telemetry.check_name(f"decision.{surface}.accept")


# ---------------------------------------------------------------------------
# end-to-end stub parity: serve vs router over the wire
# ---------------------------------------------------------------------------

def test_serve_router_decision_parity_end_to_end():
    """A mixed batch through worker + FleetClient: the serve and
    router surfaces count identical accept/reject-by-reason totals —
    the rejection crossed the wire as RemoteVerifyError and still
    incremented the same reason class."""
    worker = VerifyWorker(StubKeySet(), target_batch=8, max_wait_ms=1.0)
    try:
        with telemetry.recording() as rec:
            cl = FleetClient([worker.address], fallback=StubKeySet(),
                             rr_seed=0)
            out = cl.verify_batch(["a.ok", "b.bad", "c.ok", "d.bad",
                                   "e.bad"])
            assert len(out) == 5
            rollup = decision.surface_totals(rec.counters())
        assert rollup["serve"]["accept"] == 2
        assert rollup["serve"]["reject.bad_signature"] == 3
        assert rollup["router"]["accept"] == 2
        assert rollup["router"]["reject.bad_signature"] == 3
    finally:
        worker.close()


def test_oracle_surface_records_decisions():
    """The KeySet base class (CPU-oracle surface) records decisions
    for any subclass that only implements verify_signature."""
    from cap_tpu.jwt.keyset import KeySet

    class _Stub(KeySet):
        def verify_signature(self, token):
            if token.endswith(".ok"):
                return {"sub": token}
            raise InvalidSignatureError("nope")

    with telemetry.recording() as rec:
        out = _Stub().verify_batch(["a.ok", "b.bad"])
        assert len(out) == 2
        rollup = decision.surface_totals(rec.counters())
    assert rollup["oracle"]["accept"] == 1
    assert rollup["oracle"]["reject.bad_signature"] == 1


def test_obs_server_decisions_endpoint():
    srv = obs_mod.ObsServer()
    try:
        with telemetry.recording():
            decision.record_batch("serve", [InvalidSignatureError()],
                                  tokens=["x.y"])
            host, port = srv.address
            with urllib.request.urlopen(
                    f"http://{host}:{port}/decisions", timeout=5) as r:
                body = json.load(r)
        assert body["decisions"][0]["reason"] == "bad_signature"
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# SLO engine
# ---------------------------------------------------------------------------

def test_slo_parse_rules_and_errors():
    rules = slo.parse_rules("""
    # comment
    wv   counter decision.wrong_verdicts max 0
    fb   ratio fleet.fallback_tokens / worker.tokens max 0.05 burn 2
    p99  quantile batcher.flush p99 max 0.5
    """)
    assert [r.kind for r in rules] == ["counter", "ratio", "quantile"]
    assert rules[1].burn_threshold == 2.0
    with pytest.raises(slo.SLOError):
        slo.parse_rules("broken gibberish line")
    with pytest.raises(slo.SLOError):
        slo.parse_rules("x ratio a / b maximum 0.1")


def test_slo_counter_and_quantile_rules():
    rec = telemetry.Recorder()
    rec.count("decision.wrong_verdicts", 0)
    for _ in range(50):
        rec.observe("batcher.flush", 0.01)
    rules = slo.parse_rules(
        "wv counter decision.wrong_verdicts max 0\n"
        "p99 quantile batcher.flush p99 max 0.5")
    res = slo.evaluate_once(rec.snapshot(), rules)
    assert all(r["ok"] for r in res)
    rec.count("decision.wrong_verdicts", 1)
    for _ in range(5):
        rec.observe("batcher.flush", 30.0)
    res = slo.evaluate_once(rec.snapshot(), rules)
    assert not res[0]["ok"] and not res[1]["ok"]
    assert slo.any_breach(res)
    assert "BREACH" in slo.format_results(res)


def test_slo_multiwindow_burn_semantics():
    """Sustained burn breaches; a short spike the long window already
    absorbed does not (the multi-window discipline)."""
    rules = slo.parse_rules(
        "fb ratio fleet.fallback_tokens / worker.tokens max 0.01")
    sustained = slo.SLOEngine(rules, windows=(60, 300))
    t = 0.0
    sustained.observe({"counters": {"worker.tokens": 0}}, now=t)
    sustained.observe(
        {"counters": {"fleet.fallback_tokens": 100,
                      "worker.tokens": 5000}}, now=t + 240)
    res = sustained.evaluate(
        {"counters": {"fleet.fallback_tokens": 300,
                      "worker.tokens": 10000}}, now=t + 299)
    assert not res[0]["ok"], res

    spike = slo.SLOEngine(rules, windows=(60, 300))
    spike.observe({"counters": {"worker.tokens": 0}}, now=t)
    spike.observe({"counters": {"fleet.fallback_tokens": 0,
                                "worker.tokens": 990_000}}, now=t + 250)
    res = spike.evaluate(
        {"counters": {"fleet.fallback_tokens": 300,
                      "worker.tokens": 1_000_000}}, now=t + 300)
    assert res[0]["ok"], res


def test_slo_default_rules_parse():
    rules = slo.default_rules()
    names = [r.name for r in rules]
    assert "wrong_verdicts" in names
    assert "oracle_fallback" in names


# ---------------------------------------------------------------------------
# capstat --slo against a live stub worker (acceptance bar)
# ---------------------------------------------------------------------------

def test_capstat_slo_exit_codes_live_fleet(tmp_path, capsys):
    """capstat --slo over a live stub worker: clean rules exit 0,
    an injected breach exits nonzero — the pageable CI/cron shape."""
    worker = VerifyWorker(StubKeySet(), target_batch=8, max_wait_ms=1.0,
                          obs_port=0)
    try:
        with telemetry.recording():
            cl = FleetClient([worker.address], fallback=StubKeySet(),
                             rr_seed=0)
            for i in range(3):
                cl.verify_batch([f"s{i}.ok", f"s{i}.bad"])
            host, port = worker.obs_address
            ep = f"{host}:{port}"
            rc_default = capstat.main(["--slo", ep])
            # Injected breach: this fleet HAS rejections, so a zero
            # rejection budget must burn.
            rules = tmp_path / "slo.rules"
            rules.write_text(
                "no_rejects counter "
                "decision.serve.reject.bad_signature max 0\n")
            rc_breach = capstat.main(["--slo-rules", str(rules), ep])
    finally:
        worker.close()
    out = capsys.readouterr().out
    assert rc_default == 0, out
    assert rc_breach == 2, out
    assert "BREACH" in out
    assert "decisions[serve]" in out      # verdict rollup rendered


def test_capstat_slo_unparseable_rules_fail_loudly(tmp_path):
    worker = VerifyWorker(StubKeySet(), obs_port=0)
    try:
        host, port = worker.obs_address
        bad = tmp_path / "bad.rules"
        bad.write_text("not a rule at all\n")
        with pytest.raises(slo.SLOError):
            capstat.main(["--slo-rules", str(bad), f"{host}:{port}"])
    finally:
        worker.close()


# ---------------------------------------------------------------------------
# postmortems: writer, scrub, renderer, capstat --postmortem
# ---------------------------------------------------------------------------

def test_postmortem_write_read_render(tmp_path, capsys):
    path = str(tmp_path / "pm.json")
    with telemetry.recording() as rec:
        rec.count("worker.tokens", 7)
        rec.trace_span("ab12cd34ab12cd34", "batcher.fill", 1.0, 0.25)
        rec.flight("ab12cd34ab12cd34", 0.25)
        decision.record_batch("serve", [InvalidSignatureError()],
                              tokens=["t.bad"])
        w = postmortem.PostmortemWriter(
            path, interval_s=0.05,
            stats_fn=lambda: {"queued_tokens": 2,
                              "inflight_batches": 1})
        time.sleep(0.15)
        w.close("sigterm-drain")
    doc = postmortem.read_postmortem(path)
    assert doc["reason"] == "sigterm-drain"
    assert doc["snapshot"]["counters"]["worker.tokens"] == 7
    assert doc["flight"][0]["trace"] == "ab12cd34ab12cd34"
    assert doc["decisions"][0]["reason"] == "bad_signature"
    assert doc["stats"]["queued_tokens"] == 2
    rendered = postmortem.render_postmortem(doc)
    assert "sigterm-drain" in rendered
    assert "decisions[serve]" in rendered
    # capstat --postmortem renders the same file
    assert capstat.main(["--postmortem", path]) == 0
    out = capsys.readouterr().out
    assert "postmortem pid=" in out and "ab12cd34ab12cd34" in out
    # missing file: error exit, not traceback
    assert capstat.main(["--postmortem", str(tmp_path / "nope")]) == 1


def test_postmortem_scrub_redacts_token_shapes():
    doc = postmortem._scrub({
        "note": "eyJhbGciOiJSUzI1NiJ9.e30.c2ln",
        "long": "x" * 1000,
        "nested": [{"ok": "fine", "bad": "xx eyJzdWIiOiJhIn0 yy"}],
        "n": 3,
    })
    assert doc["note"] == "[redacted]"
    assert doc["long"] == "[redacted]"
    assert doc["nested"][0]["bad"] == "[redacted]"
    assert doc["nested"][0]["ok"] == "fine" and doc["n"] == 3


def test_postmortem_survives_failing_stats_fn(tmp_path):
    path = str(tmp_path / "pm.json")

    def boom():
        raise RuntimeError("stats source is the thing that crashed")

    postmortem.write_postmortem(
        path, postmortem.build_postmortem("crash", boom))
    doc = postmortem.read_postmortem(path)
    assert "stats_error" in doc and doc["reason"] == "crash"


# ---------------------------------------------------------------------------
# stalled scraper: the obs server's short-timeout handler threads
# ---------------------------------------------------------------------------

def test_obs_server_stalled_scraper_does_not_block(tmp_path):
    """A scraper that connects and never sends a request must neither
    block other scrapes nor hold its handler thread past the timeout."""
    srv = obs_mod.ObsServer(handler_timeout_s=0.5)
    try:
        host, port = srv.address
        stalled = socket.create_connection((host, port), timeout=5)
        stalled.send(b"GET /metrics")        # partial request, no CRLF
        # Healthy scrapes keep answering promptly while it hangs.
        for _ in range(3):
            t0 = time.monotonic()
            with urllib.request.urlopen(
                    f"http://{host}:{port}/healthz", timeout=5) as r:
                assert json.load(r)["ok"]
            assert time.monotonic() - t0 < 2.0
        # The server times the stalled connection out and closes it.
        stalled.settimeout(5.0)
        deadline = time.monotonic() + 5.0
        closed = False
        while time.monotonic() < deadline:
            try:
                if stalled.recv(4096) == b"":
                    closed = True
                    break
            except (ConnectionError, socket.timeout, OSError):
                closed = True
                break
        assert closed, "stalled scraper connection never closed"
        stalled.close()
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# bench-trend sentinel
# ---------------------------------------------------------------------------

SERIES_FIXTURE = os.path.join(REPO, "tests", "data", "bench_series")


def test_bench_trend_selftest_and_real_series():
    assert bench_trend.selftest(REPO) == []
    assert bench_trend.selftest(SERIES_FIXTURE) == []
    series = bench_trend.load_series(SERIES_FIXTURE)
    assert len(series) >= 5
    assert bench_trend.check_series(series) == [], \
        "committed BENCH series must pass clean"
    assert bench_trend.check_multichip(
        bench_trend.load_multichip(REPO)) == []


def test_bench_trend_flags_injected_regression():
    series = bench_trend._synthetic([100.0, 100.0, 100.0, 85.0])
    findings = bench_trend.check_series(series)
    assert findings and "-15.0%" in findings[0]


def test_bench_trend_weather_annotation():
    series = bench_trend._synthetic([100.0, 100.0])
    series.append((3, {"value": 50.0, "stall_intervals": 4,
                       "stall_seconds": 60.0}))
    findings = bench_trend.check_series(series)
    assert findings and "weather" in findings[0]


def test_bench_trend_requires_self_describing_records():
    series = [(5, {"value": 100.0}), (6, {"value": 100.0})]
    findings = bench_trend.check_self_describing(series)
    assert any("decisions" in f for f in findings)
    series = [(6, {"value": 100.0, "decisions": {}, "slo": []})]
    assert bench_trend.check_self_describing(series) == []
