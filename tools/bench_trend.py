#!/usr/bin/env python3
"""bench-trend: the BENCH_r*.json regression sentinel.

The perf trajectory lives in committed round records (BENCH_rNN.json,
MULTICHIP_rNN.json, BENCH_SERVE_rNN.json) that, until now, only a
human reading docs/PERF.md would compare. This tool parses the whole
series (a repo with no BENCH_r* series reports "no series" and checks
the rest) and FAILS (exit 1) when the LATEST round regresses any tracked metric by
more than ``THRESHOLD`` (10%) against the BEST of the up-to-3
preceding rounds — best-of-3 because single rounds ride host-link
weather (a round-3 headline dropped 38% on wire stalls alone and
recovered; the best-of window absorbs that without absorbing a real
regression).

Tracked metrics (all higher-is-better; latency/wire fields are
published weather, not tracked — see docs/PERF.md on stalls):

- ``value``              — the honest end-to-end headline rate
- ``value_peak``         — best pipelined interval
- ``resident_mixed_vps`` — engine speed with records device-resident
                           (weather-independent: THE regression signal)
- ``serve_fleet``        — bench_serve fleet-mode value, when present
- ``resident_mldsa44_vps`` — post-quantum engine rate (ML-DSA-44
                           resident lanes), tracked from round 11 on

A second series, ``BENCH_SERVE_r*.json`` (the serve-chain records
tools/bench_stages.py + bench_serve.py produce, committed from round
12 on), tracks the native serve chain:

- ``serve_native_vps``          — native-chain single-worker serve
                                  rate, device stubbed (higher better)
- ``stage_python_us_per_token`` — Python-side serial cost per served
                                  token with the native chain on
                                  (LOWER is better — inverted check)
- ``zipf_cached_vps``           — end-to-end fleet rate on the Zipf
                                  90%-repeat mix with the verdict
                                  cache ON (higher better; round 14+)

MULTICHIP records are checked structurally: the latest round must
still report ``ok`` (rc 0) on the same-or-larger device count.

Also verifies the latest BENCH record is SELF-DESCRIBING per this
round's contract: carries ``decisions`` (reason-keyed counters) and
``slo`` (objective evaluation) once the record is from round ≥ 6 —
earlier rounds predate the fields and are exempt.

``--selftest`` exercises the detector on synthetic series (including
an injected 15% regression over the real series) and exits nonzero if
the detector misbehaves — wired before the real check in
``make bench-trend`` so a broken sentinel cannot silently pass CI.
"""

from __future__ import annotations

import argparse
import copy
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

THRESHOLD = 0.10          # >10% below best-of-window = regression
WINDOW = 3                # best of the last 3 preceding rounds
TRACKED = ("value", "value_peak", "resident_mixed_vps", "serve_fleet",
           "resident_mldsa44_vps",
           # second PQ family (r17): the resident SLH-DSA hash-forest
           # rate — higher is better, tracked like the ML-DSA number
           "resident_slhdsa128s_vps")
# serve-chain series (BENCH_SERVE_r*.json): metric → higher_is_better
SERVE_TRACKED = {"serve_native_vps": True,
                 "stage_python_us_per_token": False,
                 # full-observability native chain (native telemetry
                 # plane on): us/token, lower is better — the r13
                 # "obs on at wire speed" contract must not erode
                 "serve_native_obs_us_per_token": False,
                 # verdict-cache tier: end-to-end Zipf(0.9-repeat)
                 # fleet rate with the cache ON (higher is better) —
                 # the r14 memory-speed-repeats contract
                 "zipf_cached_vps": True,
                 # OIDC verify-AND-validate, device-stubbed, native
                 # claims-rule engine on (higher is better) — the r15
                 # wire-speed-validation contract (bench_stages.py
                 # claims row; chip-host bench.py emits the real-
                 # ladder analog under "oidc")
                 "oidc_native_vps": True,
                 # front-door tier: end-to-end multi-pool fleet rate
                 # on the Zipf 90%-repeat mix with digest-affinity
                 # routing (higher is better) — the r16 fleet-wide
                 # verdict-tier contract (bench_serve multi-pool mode)
                 "fleet_affinity_vps": True,
                 # zero-copy ingest: closed-loop serve rate over the
                 # shared-memory ring transport, device stubbed
                 # (higher is better) — the r18 recv+copy-elimination
                 # contract (bench_stages transport column /
                 # bench_serve CAP_SERVE_TRANSPORTS mode)
                 "shm_vps": True,
                 # tenant fairness: the WELL-BEHAVED tenant's
                 # verified/s under a flooding tenant with the fair
                 # plane on (DRR + admission; higher is better) — the
                 # r20 enforcement contract (bench_serve
                 # CAP_SERVE_FLOOD mode)
                 "fairness_vps": True,
                 # router tier at wire speed: the native relay
                 # gateway's closed-loop rate on the pinned Zipf
                 # multi-pool workload (higher is better) — the r21
                 # zero-copy front-door contract (bench_serve
                 # CAP_FRONTDOOR_CHAINS gateway arms)
                 "fleet_native_vps": True,
                 # pipeline occupancy: fraction of bench wall time the
                 # engine spent inside dispatch intervals on the
                 # pinned serve workload (higher is better) — the r22
                 # queueing-delay-plane contract; a drop means the
                 # pipeline grew bubbles even if throughput held
                 "device_occupancy": True}
# Rounds from this PR onward must embed decision/SLO fields.
SELF_DESCRIBING_FROM_ROUND = 6


def load_series(repo: str = REPO) -> List[Tuple[int, Dict[str, Any]]]:
    """[(round, parsed-metric-dict)] for every BENCH_rNN.json, in
    round order. Records whose bench errored (no parsed dict) carry
    an empty dict — they participate as gaps, not as zeros."""
    out = []
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = doc.get("parsed")
        out.append((int(m.group(1)),
                    parsed if isinstance(parsed, dict) else {}))
    return sorted(out)


def load_multichip(repo: str = REPO) -> List[Tuple[int, Dict[str, Any]]]:
    out = []
    for path in sorted(glob.glob(os.path.join(repo,
                                              "MULTICHIP_r*.json"))):
        m = re.search(r"MULTICHIP_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                out.append((int(m.group(1)), json.load(f)))
        except (OSError, ValueError):
            continue
    return sorted(out)


def load_serve_series(repo: str = REPO) -> List[Tuple[int,
                                                      Dict[str, Any]]]:
    """[(round, record)] for every BENCH_SERVE_rNN.json, in order."""
    out = []
    for path in sorted(glob.glob(os.path.join(repo,
                                              "BENCH_SERVE_r*.json"))):
        m = re.search(r"BENCH_SERVE_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                out.append((int(m.group(1)), json.load(f)))
        except (OSError, ValueError):
            continue
    return sorted(out)


def check_serve_series(series: List[Tuple[int, Dict[str, Any]]],
                       threshold: float = THRESHOLD,
                       window: int = WINDOW) -> List[str]:
    """Regressions in the serve-chain series; handles the
    lower-is-better metric by inverting the comparison."""
    if len(series) < 2:
        return []
    latest_round, latest = series[-1]
    prior = series[:-1][-window:]
    findings = []
    for metric, higher in SERVE_TRACKED.items():
        vals = [(rnd, d.get(metric)) for rnd, d in prior
                if isinstance(d.get(metric), (int, float))]
        if not vals:
            continue
        best_round, best = (max(vals, key=lambda t: t[1]) if higher
                            else min(vals, key=lambda t: t[1]))
        now = latest.get(metric)
        if not isinstance(now, (int, float)):
            findings.append(
                f"SERVE r{latest_round:02d}: tracked metric {metric!r} "
                f"disappeared (best r{best_round:02d}={best:.3f})")
            continue
        drop = (1.0 - now / best) if higher else (now / best - 1.0)
        if drop > threshold:
            findings.append(
                f"SERVE r{latest_round:02d}: {metric} = {now:.3f}, "
                f"{drop * 100:.1f}% worse than best-of-last-"
                f"{len(prior)} (r{best_round:02d}={best:.3f})")
    return findings


def metric_value(parsed: Dict[str, Any], metric: str) -> Optional[float]:
    if metric == "serve_fleet":
        v = parsed.get("serve_fleet_value")
    else:
        v = parsed.get(metric)
    if isinstance(v, (int, float)) and v > 0:
        return float(v)
    return None


def check_series(series: List[Tuple[int, Dict[str, Any]]],
                 threshold: float = THRESHOLD,
                 window: int = WINDOW) -> List[str]:
    """Regression findings for the LATEST round vs best-of-window.

    A metric absent from the latest record is only a finding when a
    previous round DID report it (a tracked number silently vanishing
    is itself a regression signal); metrics absent everywhere are
    skipped (older series predate them).
    """
    if len(series) < 2:
        return []
    latest_round, latest = series[-1]
    prior = series[:-1][-window:]
    findings = []
    for metric in TRACKED:
        best, best_round = None, None
        for rnd, parsed in prior:
            v = metric_value(parsed, metric)
            if v is not None and (best is None or v > best):
                best, best_round = v, rnd
        if best is None:
            continue
        now = metric_value(latest, metric)
        if now is None:
            findings.append(
                f"r{latest_round:02d}: tracked metric {metric!r} "
                f"disappeared (best r{best_round:02d}={best:.1f})")
            continue
        drop = 1.0 - now / best
        if drop > threshold:
            weather = ""
            if latest.get("stall_intervals"):
                weather = (f"  [weather: {latest['stall_intervals']} "
                           f"stall intervals, "
                           f"{latest.get('stall_seconds', 0)}s — "
                           "check resident_mixed_vps before blaming "
                           "the engine]")
            findings.append(
                f"r{latest_round:02d}: {metric} = {now:.1f}, "
                f"-{drop * 100:.1f}% vs best-of-last-{len(prior)} "
                f"(r{best_round:02d}={best:.1f}, threshold "
                f"{threshold * 100:.0f}%){weather}")
    return findings


def check_multichip(series: List[Tuple[int, Dict[str, Any]]]
                    ) -> List[str]:
    if not series:
        return []
    rnd, latest = series[-1]
    findings = []
    if latest.get("skipped"):
        return []
    if not latest.get("ok", False) or latest.get("rc", 1) != 0:
        findings.append(f"MULTICHIP r{rnd:02d}: not ok "
                        f"(rc={latest.get('rc')})")
    prev_devs = [d.get("n_devices", 0) for _, d in series[:-1]
                 if not d.get("skipped")]
    if prev_devs and latest.get("n_devices", 0) < max(prev_devs):
        findings.append(
            f"MULTICHIP r{rnd:02d}: device count shrank "
            f"({latest.get('n_devices')} < {max(prev_devs)})")
    return findings


def check_self_describing(series: List[Tuple[int, Dict[str, Any]]]
                          ) -> List[str]:
    """Round ≥ SELF_DESCRIBING_FROM_ROUND records must carry the
    decision/SLO embedding (bench.py writes them from this PR on)."""
    if not series:
        return []
    rnd, latest = series[-1]
    if rnd < SELF_DESCRIBING_FROM_ROUND or not latest:
        return []
    findings = []
    for field in ("decisions", "slo"):
        if field not in latest:
            findings.append(
                f"r{rnd:02d}: BENCH record is not self-describing — "
                f"missing {field!r} (bench.py must embed it)")
    return findings


# ---------------------------------------------------------------------------
# selftest: the detector must detect
# ---------------------------------------------------------------------------


def _synthetic(values: List[Optional[float]]
               ) -> List[Tuple[int, Dict[str, Any]]]:
    return [(i + 1, {} if v is None else {"value": v})
            for i, v in enumerate(values)]


def selftest(repo: str = REPO) -> List[str]:
    problems = []

    # 1. flat series: clean
    if check_series(_synthetic([100.0, 101.0, 99.0, 100.0])):
        problems.append("flat synthetic series flagged")
    # 2. 16% drop vs best-of-3: must flag
    if not check_series(_synthetic([100.0, 95.0, 98.0, 84.0])):
        problems.append("16% synthetic regression NOT flagged")
    # 3. drop >10% vs best but window slid past the peak: best-of-3
    #    looks at the last 3 only, so an old peak cannot page forever
    if check_series(_synthetic([200.0, 100.0, 100.0, 100.0, 95.0])):
        problems.append("stale-peak comparison leaked past the window")
    # 4. metric disappearing: must flag
    gone = _synthetic([100.0, 100.0])
    gone.append((3, {"value_peak": 5.0}))
    if not any("disappeared" in f for f in check_series(gone)):
        problems.append("vanished tracked metric NOT flagged")
    # 4b. serve series: higher-is-better drop and lower-is-better RISE
    #     must both flag; a clean pair must not
    sv = [(11, {"serve_native_vps": 1e6,
                "stage_python_us_per_token": 0.8,
                "serve_native_obs_us_per_token": 0.9}),
          (12, {"serve_native_vps": 1e6,
                "stage_python_us_per_token": 0.8,
                "serve_native_obs_us_per_token": 0.9})]
    if check_serve_series(sv):
        problems.append("flat serve series flagged")
    if not check_serve_series(
            [sv[0], (12, {"serve_native_vps": 0.8e6,
                          "stage_python_us_per_token": 0.8,
                          "serve_native_obs_us_per_token": 0.9})]):
        problems.append("serve vps regression NOT flagged")
    if not check_serve_series(
            [sv[0], (12, {"serve_native_vps": 1e6,
                          "stage_python_us_per_token": 1.0,
                          "serve_native_obs_us_per_token": 0.9})]):
        problems.append("us/token REGRESSION (rise) NOT flagged")
    if not check_serve_series(
            [sv[0], (12, {"serve_native_vps": 1e6,
                          "stage_python_us_per_token": 0.8,
                          "serve_native_obs_us_per_token": 1.2})]):
        problems.append("obs us/token REGRESSION (rise) NOT flagged")
    # a round that predates the obs metric must not flag when the
    # NEXT round introduces it (absent-everywhere-before is not a
    # disappearance)
    if check_serve_series(
            [(11, {"serve_native_vps": 1e6,
                   "stage_python_us_per_token": 0.8}),
             sv[1]]):
        problems.append("introducing the obs metric flagged")
    # 4c. verdict-cache Zipf headline: a drop must flag, introducing
    #     the metric must not, and it vanishing must flag
    zc = [(13, {"serve_native_vps": 1e6}),
          (14, {"serve_native_vps": 1e6, "zipf_cached_vps": 5e5})]
    if check_serve_series(zc):
        problems.append("introducing zipf_cached_vps flagged")
    if not check_serve_series(
            [zc[1], (15, {"serve_native_vps": 1e6,
                          "zipf_cached_vps": 3e5})]):
        problems.append("zipf_cached_vps regression NOT flagged")
    if not any("disappeared" in f for f in check_serve_series(
            [zc[1], (15, {"serve_native_vps": 1e6})])):
        problems.append("vanished zipf_cached_vps NOT flagged")
    # 4d. oidc_native_vps (r15): introducing must not flag; a drop
    #     and a disappearance must
    oc = [(14, {"serve_native_vps": 1e6}),
          (15, {"serve_native_vps": 1e6, "oidc_native_vps": 3e5})]
    if check_serve_series(oc):
        problems.append("introducing oidc_native_vps flagged")
    if not check_serve_series(
            [oc[1], (16, {"serve_native_vps": 1e6,
                          "oidc_native_vps": 2e5})]):
        problems.append("oidc_native_vps regression NOT flagged")
    if not any("disappeared" in f for f in check_serve_series(
            [oc[1], (16, {"serve_native_vps": 1e6})])):
        problems.append("vanished oidc_native_vps NOT flagged")
    # 4e. fleet_affinity_vps (r16): introducing must not flag; a drop
    #     and a disappearance must
    fa = [(15, {"serve_native_vps": 1e6}),
          (16, {"serve_native_vps": 1e6, "fleet_affinity_vps": 4e4})]
    if check_serve_series(fa):
        problems.append("introducing fleet_affinity_vps flagged")
    if not check_serve_series(
            [fa[1], (17, {"serve_native_vps": 1e6,
                          "fleet_affinity_vps": 2e4})]):
        problems.append("fleet_affinity_vps regression NOT flagged")
    if not any("disappeared" in f for f in check_serve_series(
            [fa[1], (17, {"serve_native_vps": 1e6})])):
        problems.append("vanished fleet_affinity_vps NOT flagged")
    # 4e2. shm_vps (r18): introducing must not flag; a drop and a
    #      disappearance must
    sm = [(17, {"serve_native_vps": 1e6}),
          (18, {"serve_native_vps": 1e6, "shm_vps": 2e6})]
    if check_serve_series(sm):
        problems.append("introducing shm_vps flagged")
    if not check_serve_series(
            [sm[1], (19, {"serve_native_vps": 1e6,
                          "shm_vps": 1e6})]):
        problems.append("shm_vps regression NOT flagged")
    if not any("disappeared" in f for f in check_serve_series(
            [sm[1], (19, {"serve_native_vps": 1e6})])):
        problems.append("vanished shm_vps NOT flagged")
    # 4e3. fairness_vps (r20): introducing must not flag; a drop and
    #      a disappearance must
    fv = [(19, {"serve_native_vps": 1e6}),
          (20, {"serve_native_vps": 1e6, "fairness_vps": 5e4})]
    if check_serve_series(fv):
        problems.append("introducing fairness_vps flagged")
    if not check_serve_series(
            [fv[1], (21, {"serve_native_vps": 1e6,
                          "fairness_vps": 3e4})]):
        problems.append("fairness_vps regression NOT flagged")
    if not any("disappeared" in f for f in check_serve_series(
            [fv[1], (21, {"serve_native_vps": 1e6})])):
        problems.append("vanished fairness_vps NOT flagged")
    # 4e4. fleet_native_vps (r21): introducing must not flag; a drop
    #      and a disappearance must
    fn = [(20, {"serve_native_vps": 1e6}),
          (21, {"serve_native_vps": 1e6, "fleet_native_vps": 2e5})]
    if check_serve_series(fn):
        problems.append("introducing fleet_native_vps flagged")
    if not check_serve_series(
            [fn[1], (22, {"serve_native_vps": 1e6,
                          "fleet_native_vps": 1e5})]):
        problems.append("fleet_native_vps regression NOT flagged")
    if not any("disappeared" in f for f in check_serve_series(
            [fn[1], (22, {"serve_native_vps": 1e6})])):
        problems.append("vanished fleet_native_vps NOT flagged")
    # 4e5. device_occupancy (r22): introducing must not flag; a drop
    #      (pipeline grew bubbles) and a disappearance must
    oc2 = [(21, {"serve_native_vps": 1e6}),
           (22, {"serve_native_vps": 1e6, "device_occupancy": 0.4})]
    if check_serve_series(oc2):
        problems.append("introducing device_occupancy flagged")
    if not check_serve_series(
            [oc2[1], (23, {"serve_native_vps": 1e6,
                           "device_occupancy": 0.3})]):
        problems.append("device_occupancy regression NOT flagged")
    if not any("disappeared" in f for f in check_serve_series(
            [oc2[1], (23, {"serve_native_vps": 1e6})])):
        problems.append("vanished device_occupancy NOT flagged")
    # 4f. resident_slhdsa128s_vps (r17, BENCH series): introducing
    #     must not flag; a drop and a disappearance must
    def _pq(vals):
        return [(i + 16, ({} if v is None else
                          {"value": 100.0,
                           "resident_slhdsa128s_vps": v})
                 if v != "absent" else {"value": 100.0})
                for i, v in enumerate(vals)]

    if check_series(_pq(["absent", 5000.0])):
        problems.append("introducing resident_slhdsa128s_vps flagged")
    if not check_series(_pq(["absent", 5000.0, 3000.0])):
        problems.append(
            "resident_slhdsa128s_vps regression NOT flagged")
    if not any("disappeared" in f
               for f in check_series(_pq(["absent", 5000.0,
                                          "absent"]))):
        problems.append("vanished resident_slhdsa128s_vps NOT flagged")
    # 5. the repo's series (when it has one) with a 15% regression
    #    injected into a copy of the newest record: must flag (the
    #    acceptance-bar case)
    real = load_series(repo)
    if len(real) >= 2:
        injected = copy.deepcopy(real)
        rnd, parsed = injected[-1]
        bumped = dict(parsed)
        for metric in TRACKED:
            v = metric_value(parsed, metric)
            if v is not None:
                bumped[metric if metric != "serve_fleet"
                       else "serve_fleet_value"] = v * 0.85
        injected[-1] = (rnd, bumped)
        if not check_series(injected):
            problems.append(
                "15% regression injected into the real series NOT "
                "flagged")
        # 6. and the real series itself must evaluate (clean or not,
        #    deterministically — no exceptions)
        check_series(real)
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_trend",
        description="flag >10% regressions in the BENCH_r*.json series")
    ap.add_argument("--selftest", action="store_true",
                    help="exercise the detector on synthetic series")
    ap.add_argument("--repo", default=REPO)
    ap.add_argument("--threshold", type=float, default=THRESHOLD)
    args = ap.parse_args(argv)

    if args.selftest:
        problems = selftest(args.repo)
        if problems:
            for p in problems:
                print(f"bench-trend SELFTEST FAIL: {p}",
                      file=sys.stderr)
            return 1
        print("bench-trend selftest OK: detector flags synthetic and "
              "injected regressions, passes flat series")
        return 0

    series = load_series(args.repo)
    if not series:
        print("bench-trend: no BENCH_r*.json series found")
    findings = (check_series(series, threshold=args.threshold)
                + check_multichip(load_multichip(args.repo))
                + check_self_describing(series)
                + check_serve_series(load_serve_series(args.repo),
                                     threshold=args.threshold))
    rounds = ", ".join(f"r{r:02d}" for r, _ in series)
    if findings:
        for f in findings:
            print(f"bench-trend REGRESSION: {f}", file=sys.stderr)
        return 1
    if series:
        latest_round, latest = series[-1]
        vals = {m: metric_value(latest, m) for m in TRACKED}
        print(f"bench-trend OK: {rounds}; r{latest_round:02d} tracked "
              + " ".join(f"{m}={v:.0f}" for m, v in vals.items()
                         if v is not None)
              + f"; no metric >{args.threshold * 100:.0f}% below "
                f"best-of-last-{WINDOW}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
