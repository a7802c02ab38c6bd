#!/usr/bin/env python3
"""Serving-path benchmark: throughput vs per-REQUEST p99 latency.

What a user of the framework actually experiences (VERDICT r2 #5): N
concurrent CVB1 clients stream small verify requests at a VerifyWorker
whose AdaptiveBatcher owns the latency/throughput tradeoff; this sweeps
``max_wait_ms`` operating points and reports, per point, sustained
verifies/sec and request-latency quantiles.

Env knobs: CAP_SERVE_CLIENTS (32), CAP_SERVE_REQ_TOKENS (64),
CAP_SERVE_SECONDS (12 per point), CAP_SERVE_WAITS ("1,5,20"),
CAP_SERVE_TARGET_BATCH (8192).

ZIPF TOKEN MIX (``CAP_SERVE_ZIPF=s``): request tokens are drawn from a
Zipf(s) distribution over the unique pool instead of contiguous
windows — the repeat-heavy traffic shape real ingress has (the same
bearer token arriving hundreds of times inside its lifetime), and the
measurement harness ROADMAP item #3's verdict cache needs.
``CAP_SERVE_ZIPF_POOL=N`` bounds the sampled pool (the repeat-rate
knob: smaller pool → higher repeat rate). The pool's rank→token
permutation is computed ONCE in the parent from a pinned seed
(``CAP_SERVE_ZIPF_SEED``, default 1234) and shipped to every driver
process, so repeat_rate is exact and comparable across every
``CAP_SERVE_FLEET`` / chain / vcache arm. The BENCH json reports
tokens sent vs unique vs repeats per point.

VERDICT-CACHE A/B (fleet mode, ``CAP_SERVE_VCACHES="on,off"``): every
(size, chain) arm runs once per cache state (workers spawned with
CAP_SERVE_VCACHE=1/0), each point records its worker-side
``cache`` counters (lookups/hits/misses/evictions/dedup_fanout/
stale_accepts), and the headline gains ``zipf_cached_vps`` /
``zipf_uncached_vps`` and their ratio — the §Round 14 measurement of
ROADMAP #3's ≥5×-at-90%-repeat bar.

SERVE-CHAIN COMPARISON (fleet mode, ``CAP_SERVE_CHAINS=
"python,native"``): every fleet size runs once per listed chain
(workers spawned with CAP_SERVE_NATIVE=0/1), and the headline gains
``serve_native_vps`` / ``serve_python_vps`` and their ratio — the
host-saturation A/B docs/PERF.md §Round 12 records.

MULTI-POOL FRONT-DOOR MODE (``CAP_SERVE_POOLS=N``): N fresh
``WorkerPool`` "hosts" behind :class:`cap_tpu.fleet.FrontDoor`
drivers, one run per routing arm in ``CAP_SERVE_ROUTING``
("affinity,rr" — consistent-hash digest affinity vs round-robin),
arms interleaved over ``CAP_SERVE_REPS``. ``CAP_SERVE_POOL_WORKERS``
sizes each pool, ``CAP_SERVE_VCACHE_CAP`` bounds each worker's
verdict cache (the fleet-scale regime: corpus >> one worker's cache),
``CAP_SERVE_SPILL`` sets the bounded-load constant. Headline:
``fleet_affinity_vps`` / ``fleet_rr_vps`` + ratio (§Round 16,
tracked by bench_trend).

FLEET MODE (``CAP_SERVE_FLEET="1,2"``): instead of one in-process
worker, spin a ``WorkerPool`` per listed size under the single-owner
placement model (one worker process per device group — NO chip
sharing, fixing the VERDICT r5 shared-chip extrapolation) and drive it
with ``FleetClient`` processes. Reports per-size throughput and the
scaling ratio of the largest over the smallest size. Fleet knobs:
``CAP_SERVE_FLEET_KEYSET`` (worker ``--keyset`` spec; default
``stub:batch_ms=1,token_us=300`` — simulated device occupancy that
sleeps WITHOUT the GIL so cross-process overlap is real even on a
1-core host, sized so the WORKER is the bottleneck (the regime a
fleet exists for; at ~100 µs/token and below, this host's single
core saturates on the Python serve+client chains first and the
measurement stops being about placement); use ``jwks:<path>`` for
real engines on real hardware).

Prints one JSON line on stdout: per-point results + the best-throughput
point's p99 as the headline fields.
"""

import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _fleet_platform(keyset_spec: str):
    """Placement platform for a fleet: real-engine workers take the
    chip unless the caller pinned ``JAX_PLATFORMS=cpu`` (the tests);
    stub workers use no device. Read from the environment — the
    parent never imports JAX, or it would hold the chip its workers
    need."""
    from cap_tpu.fleet.pool import DEVICE_SPECS

    if not keyset_spec.startswith(DEVICE_SPECS):
        return None
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return "cpu"
    if "jax" in sys.modules:
        raise RuntimeError("fleet parent imported jax: it would hold "
                           "the chip its workers need")
    return "tpu"


def _fixtures(n_unique: int = 16384):
    from cap_tpu import testing as T

    return T.headline_fixtures(n_unique)


def _quantile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    return sorted_vals[max(0, math.ceil(q * len(sorted_vals)) - 1)]


def _zipf_cfg():
    """(s, pool) from the env, or None — shipped to client procs."""
    s = os.environ.get("CAP_SERVE_ZIPF")
    if not s:
        return None
    return (float(s), int(os.environ.get("CAP_SERVE_ZIPF_POOL", 0)))


def _zipf_pool_indices(n_tokens, zipf):
    """The SHARED Zipf pool: rank→token-index permutation, computed
    ONCE in the parent from a pinned seed (``CAP_SERVE_ZIPF_SEED``,
    default 1234) and shipped to every driver process. Every client in
    every arm (fleet size × serve chain × vcache) then hammers the
    IDENTICAL hot-token set, so ``repeat_rate`` in the json is exact
    and comparable across ``CAP_SERVE_FLEET`` arms — drivers must
    never regenerate the pool per process."""
    import numpy as np

    if zipf is None:
        return None
    _, pool = zipf
    n = min(pool or n_tokens, n_tokens)
    seed = int(os.environ.get("CAP_SERVE_ZIPF_SEED", "1234"))
    return np.random.RandomState(seed).permutation(n_tokens)[:n]


def _zipf_picker(tokens, req_tokens, seed, zipf, pool_idx=None):
    """Request generator state for the Zipf token mix: returns
    ``pick() -> (token_list, index_array)``. Rank→token mapping is the
    parent's shared pinned permutation (``pool_idx``) so every client
    hammers the SAME hot tokens — that is what makes the mix
    cacheable."""
    import numpy as np

    zs, pool = zipf
    perm = (np.asarray(pool_idx) if pool_idx is not None
            else _zipf_pool_indices(len(tokens), zipf))
    n = len(perm)
    w = np.arange(1, n + 1, dtype=np.float64) ** -zs
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    rng = np.random.RandomState(seed * 7919 + 17)

    def pick():
        idx = perm[np.searchsorted(cdf, rng.random_sample(req_tokens))]
        return [tokens[i] for i in idx], idx

    return pick


def _client_proc(host, port, tokens, req_tokens, depth, start_at,
                 seconds, seed, outq, zipf=None, pool_idx=None):
    """One client PROCESS: its own interpreter, so response decoding
    never shares the worker's (or other clients') GIL — in-process
    client threads cap the whole bench at one core of json parsing
    (measured: ~15k verifies/s regardless of depth or batch knobs)."""
    from collections import deque

    from cap_tpu.serve.client import VerifyClient

    # generous timeout: first flushes of a fresh shape bucket can hit
    # an XLA compile (tens of seconds) before the cache warms
    cl = VerifyClient(host, port, timeout=180.0)
    t0s: deque = deque()
    lats = []
    done = 0
    sent = 0
    used = set()
    picker = _zipf_picker(tokens, req_tokens, seed, zipf,
                          pool_idx=pool_idx) if zipf else None
    while time.time() < start_at:
        time.sleep(0.005)
    deadline = time.time() + seconds

    def gen():
        nonlocal sent
        rng = seed * 7919 + 17
        while time.time() < deadline:
            t0s.append(time.perf_counter())
            if picker is not None:
                toks, idx = picker()
                used.update(idx.tolist())
                sent += len(toks)
                yield toks
                continue
            rng = (rng * 1103515245 + 12345) & 0x7FFFFFFF
            lo = rng % max(1, len(tokens) - req_tokens)
            sent += req_tokens
            used.update(range(lo, lo + req_tokens))
            yield tokens[lo: lo + req_tokens]

    err = None
    try:
        # depth > 1: the client keeps frames in flight, so request
        # latency includes pipeline queueing — the honest number a
        # pipelining caller experiences.
        for out in cl.verify_stream(gen(), depth=depth):
            in_window = time.time() < deadline
            lats.append(time.perf_counter() - t0s.popleft())
            bad = sum(1 for r in out if isinstance(r, Exception))
            assert bad == 0, f"unexpected failures: {bad}"
            if in_window:
                done += len(out)
    except BaseException as e:  # noqa: BLE001 - reported to the parent
        err = f"{type(e).__name__}: {e}"
    finally:
        cl.close()
        # ALWAYS report, error or not — a silent child death would
        # stall the parent's collection for its full timeout
        outq.put((done, lats, err, sent, used))


def run_point(keyset, tokens, max_wait_ms: float, n_clients: int,
              req_tokens: int, seconds: float,
              target_batch: int, depth: int = 1) -> dict:
    import multiprocessing as mp

    from cap_tpu.serve.worker import VerifyWorker

    worker = VerifyWorker(keyset, target_batch=target_batch,
                          max_wait_ms=max_wait_ms)
    host, port = worker.address
    zipf = _zipf_cfg()
    pool_idx = _zipf_pool_indices(len(tokens), zipf)
    # spawn (not fork): children must never inherit live TPU/jax state
    ctx = mp.get_context("spawn")
    outq = ctx.Queue()
    start_at = time.time() + max(4.0, n_clients * 0.15)  # spawn lag
    procs = [ctx.Process(
        target=_client_proc,
        args=(host, port, tokens, req_tokens, depth, start_at,
              seconds, i, outq, zipf, pool_idx), daemon=True)
        for i in range(n_clients)]
    for p in procs:
        p.start()
    total = 0
    lats = []
    errors = []
    sent_total = 0
    used_union: set = set()
    try:
        for _ in procs:
            d, ls, err, sent, used = outq.get(timeout=seconds + 300)
            total += d
            lats.extend(ls)
            sent_total += sent
            used_union |= used
            if err:
                errors.append(err)
        for p in procs:
            p.join(timeout=30)
    finally:
        worker.close()
    if errors:
        raise RuntimeError(f"client processes failed: {errors[:3]}")

    lats.sort()
    pt = {
        "max_wait_ms": max_wait_ms,
        "clients": n_clients,
        "req_tokens": req_tokens,
        "pipeline_depth": depth,
        "serve_chain": worker.serve_chain,
        "throughput": round(total / seconds, 1),
        "requests": len(lats),
        "p50_ms": round(_quantile(lats, 0.50) * 1e3, 1),
        "p95_ms": round(_quantile(lats, 0.95) * 1e3, 1),
        "p99_ms": round(_quantile(lats, 0.99) * 1e3, 1),
    }
    pt.update(_mix_fields(zipf, sent_total, used_union))
    return pt


def _mix_fields(zipf, sent_total: int, used_union: set) -> dict:
    """Unique-vs-repeat accounting for the BENCH json (exact: the
    union of every client's sampled indices)."""
    unique = len(used_union)
    out = {
        "tokens_sent": sent_total,
        "tokens_unique": unique,
        "tokens_repeat": max(0, sent_total - unique),
        "repeat_rate": (round(1.0 - unique / sent_total, 4)
                        if sent_total else None),
    }
    if zipf:
        out["zipf_s"], out["zipf_pool"] = zipf[0], zipf[1] or None
    return out


def _fleet_client_proc(endpoints, tokens, req_tokens, start_at, seconds,
                       seed, outq, zipf=None, pool_idx=None):
    """One closed-loop FleetClient PROCESS (own interpreter)."""
    from cap_tpu.fleet import FleetClient

    cl = FleetClient(endpoints, attempt_timeout=30.0,
                     total_deadline=120.0)
    lats = []
    done = 0
    sent = 0
    used = set()
    picker = _zipf_picker(tokens, req_tokens, seed, zipf,
                          pool_idx=pool_idx) if zipf else None
    rng = seed * 7919 + 17
    while time.time() < start_at:
        time.sleep(0.005)
    deadline = time.time() + seconds
    err = None
    try:
        while time.time() < deadline:
            if picker is not None:
                toks, idx = picker()
                used.update(idx.tolist())
            else:
                rng = (rng * 1103515245 + 12345) & 0x7FFFFFFF
                lo = rng % max(1, len(tokens) - req_tokens)
                toks = tokens[lo: lo + req_tokens]
                used.update(range(lo, lo + req_tokens))
            sent += len(toks)
            t0 = time.perf_counter()
            out = cl.verify_batch(toks)
            lats.append(time.perf_counter() - t0)
            bad = sum(1 for r in out if isinstance(r, Exception))
            assert bad == 0, f"unexpected failures: {bad}"
            done += len(out)
    except BaseException as e:  # noqa: BLE001 - reported to the parent
        err = f"{type(e).__name__}: {e}"
    finally:
        outq.put((done, lats, err, sent, used))


def _native_drive(endpoints, tokens, req_tokens, seconds, n_clients,
                  depth=32):
    """Drive every endpoint with the NATIVE closed-loop driver
    (cap_bench_drive: pipelined plain CVB1 frames, sent and parsed in
    C threads) — client cost leaves the measurement, so the number is
    the fleet's serve capacity, not the Python client chain's
    (CAP_SERVE_DRIVER=native)."""
    import ctypes
    import threading

    import numpy as np

    from cap_tpu.serve import native_serve

    lib = native_serve.load()
    encoded = [t.encode() for t in tokens]
    blob = np.frombuffer(b"".join(encoded), np.uint8)
    offs = np.zeros(len(encoded) + 1, np.int64)
    np.cumsum([len(e) for e in encoded], out=offs[1:])
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    conns_per = max(1, n_clients // max(1, len(endpoints)))
    outs = []

    def drive(host, port):
        out_tokens = np.zeros(1, np.int64)
        out_reqs = np.zeros(1, np.int64)
        lib.cap_bench_drive(            # releases the GIL for the run
            host.encode(), port, blob.ctypes.data_as(u8p),
            offs.ctypes.data_as(i64p), len(encoded), req_tokens,
            depth, seconds, conns_per,
            out_tokens.ctypes.data_as(i64p),
            out_reqs.ctypes.data_as(i64p))
        outs.append((int(out_tokens[0]), int(out_reqs[0])))

    threads = [threading.Thread(target=drive, args=ep, daemon=True)
               for ep in endpoints]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return (sum(o[0] for o in outs), sum(o[1] for o in outs))


def run_fleet_point(n_workers: int, keyset_spec: str, tokens,
                    n_clients: int, req_tokens: int, seconds: float,
                    max_wait_ms: float, target_batch: int,
                    serve_chain=None, vcache=None) -> dict:
    """Throughput of an n-worker fleet under single-owner placement.

    serve_chain: None (inherit the environment) or "python"/"native" —
    workers spawn with CAP_SERVE_NATIVE forced accordingly, for the
    chain A/B the §Round 12 host-saturation comparison needs.
    vcache: None (inherit) or "on"/"off" — the verdict-cache A/B arm
    (CAP_SERVE_VCACHE forced in the workers) the §Round 14
    cached-vs-uncached Zipf comparison needs."""
    import multiprocessing as mp

    from cap_tpu.fleet import WorkerPool

    env_extra = {}
    if serve_chain is not None:
        env_extra["CAP_SERVE_NATIVE"] = \
            "1" if serve_chain == "native" else "0"
    if vcache is not None:
        env_extra["CAP_SERVE_VCACHE"] = "1" if vcache == "on" else "0"
    # CAP_SERVE_TELEMETRY=0: workers run with the observability layer
    # off — isolates the serve chain in the A/B (decision accounting
    # costs the same on both chains and dominates once the native
    # chain is on; PERF.md §Round 12)
    if os.environ.get("CAP_SERVE_TELEMETRY", "1") == "0":
        env_extra["CAP_FLEET_TELEMETRY"] = "0"
    pool = WorkerPool(n_workers, keyset_spec=keyset_spec,
                      platform=_fleet_platform(keyset_spec),
                      target_batch=target_batch, max_wait_ms=max_wait_ms,
                      ping_interval=1.0, env_extra=env_extra)
    try:
        if not pool.wait_all_ready(120.0):
            raise RuntimeError("fleet did not come up")
        endpoints = sorted(pool.endpoints().values())
        chains = pool.serve_chains()
        zipf = _zipf_cfg()
        pool_idx = _zipf_pool_indices(len(tokens), zipf)
        driver = os.environ.get("CAP_SERVE_DRIVER", "python")
        total, lats, errors = 0, [], []
        sent_total = 0
        used_union: set = set()
        if driver == "native":
            # C closed-loop drivers: measures fleet SERVE capacity
            # (no request-latency quantiles — the driver counts, it
            # does not time individual requests)
            total, _n_req = _native_drive(endpoints, tokens,
                                          req_tokens, seconds,
                                          n_clients)
            sent_total = total
        else:
            ctx = mp.get_context("spawn")
            outq = ctx.Queue()
            start_at = time.time() + max(4.0, n_clients * 0.15)
            procs = [ctx.Process(
                target=_fleet_client_proc,
                args=(endpoints, tokens, req_tokens, start_at, seconds,
                      i, outq, zipf, pool_idx), daemon=True)
                for i in range(n_clients)]
            for p in procs:
                p.start()
            for _ in procs:
                d, ls, err, sent, used = outq.get(timeout=seconds + 300)
                total += d
                lats.extend(ls)
                sent_total += sent
                used_union |= used
                if err:
                    errors.append(err)
            for p in procs:
                p.join(timeout=30)
        if errors:
            raise RuntimeError(f"fleet clients failed: {errors[:3]}")
        merged = pool.stats_merged()
        stats = merged["workers"]
        agg = merged["aggregate"]
        served = {wid: (s or {}).get("counters", {}).get(
            "worker.tokens", 0) for wid, s in stats.items()}
    finally:
        pool.close()
    lats.sort()
    pt = {
        "n_workers": n_workers,
        "keyset_spec": keyset_spec,
        "clients": n_clients,
        "req_tokens": req_tokens,
        # what each worker ANNOUNCED on its ready line (ground truth:
        # a native request that fell back shows up as python here)
        "serve_chains": {str(w): c for w, c in sorted(chains.items())},
        # True when the workers' decision fold ran on the NATIVE
        # telemetry plane (detected from plane-only counters in the
        # merged scrape — not from the requested knob, so a silent
        # obs fallback shows up as false in the record)
        "native_obs": any(k.startswith("serve.native.hdr_cache")
                          for k in (agg.get("counters") or {})),
        # verdict-cache arm + exact worker-side cache accounting for
        # this point (merged scrape counters — hit rate of the serve
        # tier, not the drivers')
        "vcache": vcache or "env",
        "cache": {
            "lookups": (agg.get("counters") or {}).get(
                "vcache.lookups", 0),
            "hits": (agg.get("counters") or {}).get("vcache.hits", 0),
            "misses": (agg.get("counters") or {}).get(
                "vcache.misses", 0),
            "evictions": (agg.get("counters") or {}).get(
                "vcache.evictions", 0),
            "dedup_fanout": (agg.get("counters") or {}).get(
                "batcher.dedup_fanout", 0),
            "stale_accepts": (agg.get("counters") or {}).get(
                "vcache.stale_accepts", 0),
        },
        "driver": driver,
        "throughput": round(total / seconds, 1),
        "requests": len(lats),
        "p50_ms": round(_quantile(lats, 0.50) * 1e3, 1),
        "p99_ms": round(_quantile(lats, 0.99) * 1e3, 1),
        # pipeline-occupancy rollup (r22): fleet-wide busy/wall ratio
        # + per-family split from the merged scrape, plus the flush
        # trigger mix — which knob (size/timeout/handoff) actually
        # released each engine dispatch during this point
        "occupancy": agg.get("occupancy"),
        "flush_reasons": {
            k[len("batcher.flush."):]: v
            for k, v in sorted((agg.get("counters") or {}).items())
            if k.startswith("batcher.flush.")},
        "per_worker_tokens": served,
        "placement": {w: list(d) for w, d in
                      pool.placement_map().items()},
        # EXACT fleet-side stage attribution: the workers' mergeable
        # histogram snapshots, bucket-added across the fleet (not an
        # average of per-worker quantiles), plus respawn accounting.
        "telemetry": {
            "stage_latency": {
                name: {"count": int(s["count"]),
                       "p50": round(s["p50"], 6),
                       "p95": round(s["p95"], 6),
                       "p99": round(s["p99"], 6)}
                for name, s in sorted(agg["series"].items())},
            "counters": agg["counters"],
            "respawns": agg["restarts"],
        },
    }
    pt.update(_mix_fields(zipf, sent_total, used_union))
    return pt


def _frontdoor_client_proc(groups, routing, spill, tokens, req_tokens,
                           start_at, seconds, seed, outq, zipf=None,
                           pool_idx=None):
    """One closed-loop FrontDoor driver PROCESS (own interpreter):
    routes over the pool endpoint groups by digest affinity (or rr,
    the control arm) and ships its routing counters back with the
    throughput numbers."""
    from cap_tpu.fleet.frontdoor import FrontDoor

    fd = FrontDoor(groups, routing=routing, spill_factor=spill,
                   client_kw={"attempt_timeout": 30.0,
                              "total_deadline": 120.0})
    lats = []
    done = 0
    sent = 0
    used = set()
    picker = _zipf_picker(tokens, req_tokens, seed, zipf,
                          pool_idx=pool_idx) if zipf else None
    rng = seed * 7919 + 17
    while time.time() < start_at:
        time.sleep(0.005)
    deadline = time.time() + seconds
    err = None
    try:
        while time.time() < deadline:
            if picker is not None:
                toks, idx = picker()
                used.update(idx.tolist())
            else:
                rng = (rng * 1103515245 + 12345) & 0x7FFFFFFF
                lo = rng % max(1, len(tokens) - req_tokens)
                toks = tokens[lo: lo + req_tokens]
                used.update(range(lo, lo + req_tokens))
            sent += len(toks)
            t0 = time.perf_counter()
            out = fd.verify_batch(toks)
            lats.append(time.perf_counter() - t0)
            bad = sum(1 for r in out if isinstance(r, Exception))
            assert bad == 0, f"unexpected failures: {bad}"
            done += len(out)
    except BaseException as e:  # noqa: BLE001 - reported to the parent
        err = f"{type(e).__name__}: {e}"
    finally:
        outq.put((done, lats, err, sent, used, fd.counters()))
        fd.close()


def run_frontdoor_point(n_pools: int, pool_workers: int, routing: str,
                        keyset_spec: str, tokens, n_clients: int,
                        req_tokens: int, seconds: float,
                        max_wait_ms: float, target_batch: int,
                        env_extra=None) -> dict:
    """Throughput of an n_pools × pool_workers fleet behind the
    digest-affinity front door (or the rr control arm). Fresh pools
    per point: cache state must NOT leak between routing arms."""
    import multiprocessing as mp

    from cap_tpu import telemetry
    from cap_tpu.fleet import WorkerPool

    pools = [WorkerPool(pool_workers, keyset_spec=keyset_spec,
                        platform=_fleet_platform(keyset_spec),
                        target_batch=target_batch,
                        max_wait_ms=max_wait_ms, ping_interval=1.0,
                        env_extra=dict(env_extra or {}))
             for _ in range(n_pools)]
    try:
        for i, p in enumerate(pools):
            if not p.wait_all_ready(120.0):
                raise RuntimeError(f"pool {i} did not come up")
        groups = [sorted(p.endpoints().values()) for p in pools]
        zipf = _zipf_cfg()
        pool_idx = _zipf_pool_indices(len(tokens), zipf)
        spill = float(os.environ.get("CAP_SERVE_SPILL", "2.0"))
        ctx = mp.get_context("spawn")
        outq = ctx.Queue()
        start_at = time.time() + max(4.0, n_clients * 0.15)
        procs = [ctx.Process(
            target=_frontdoor_client_proc,
            args=(groups, routing, spill, tokens, req_tokens, start_at,
                  seconds, i, outq, zipf, pool_idx), daemon=True)
            for i in range(n_clients)]
        for p in procs:
            p.start()
        total, lats, errors = 0, [], []
        sent_total = 0
        used_union: set = set()
        fd_counters: dict = {}
        for _ in procs:
            d, ls, err, sent, used, ctr = outq.get(
                timeout=seconds + 300)
            total += d
            lats.extend(ls)
            sent_total += sent
            used_union |= used
            for k, v in ctr.items():
                fd_counters[k] = fd_counters.get(k, 0) + v
            if err:
                errors.append(err)
        for p in procs:
            p.join(timeout=30)
        if errors:
            raise RuntimeError(f"frontdoor clients failed: "
                               f"{errors[:3]}")
        merged = telemetry.merge_snapshots(
            [(s or {}).get("snapshot")
             for pool in pools for s in pool.stats().values()])
        agg_counters = merged.get("counters") or {}
    finally:
        for p in pools:
            p.close()
    lats.sort()
    lookups = fd_counters.get("frontdoor.lookups", 0)
    hits = fd_counters.get("frontdoor.affinity_hits", 0)
    pt = {
        "n_pools": n_pools,
        "pool_workers": pool_workers,
        "routing": routing,
        "keyset_spec": keyset_spec,
        "clients": n_clients,
        "req_tokens": req_tokens,
        "throughput": round(total / seconds, 1),
        "requests": len(lats),
        "p50_ms": round(_quantile(lats, 0.50) * 1e3, 1),
        "p99_ms": round(_quantile(lats, 0.99) * 1e3, 1),
        "frontdoor": {
            "lookups": lookups,
            "affinity_hits": hits,
            "affinity_hit_rate": (round(hits / lookups, 4)
                                  if lookups else None),
            "spills": fd_counters.get("frontdoor.spills", 0),
            "reroutes": fd_counters.get("frontdoor.reroutes", 0),
            "fallback_tokens": fd_counters.get(
                "frontdoor.fallback_tokens", 0),
        },
        "cache": {
            "lookups": agg_counters.get("vcache.lookups", 0),
            "hits": agg_counters.get("vcache.hits", 0),
            "misses": agg_counters.get("vcache.misses", 0),
            "evictions": agg_counters.get("vcache.evictions", 0),
            "stale_accepts": agg_counters.get("vcache.stale_accepts",
                                              0),
            "peer_fills": agg_counters.get("vcache.peer_fills", 0),
        },
    }
    pt.update(_mix_fields(_zipf_cfg(), sent_total, used_union))
    return pt


def _materialize_drive_tokens(tokens, zipf, pool_idx, n_out=16384):
    """The gateway arms' SHARED drive corpus. ``cap_bench_drive``
    samples request windows uniformly from its blob with a
    per-connection seed, so pinning the workload across chain arms
    means pinning the BLOB: when the Zipf mix is on, the full token
    sequence is pre-sampled ONCE here in the parent (pinned seed over
    the shared rank→token permutation) and every chain arm's C driver
    replays the identical byte stream — same blob + same conn count →
    frame-for-frame identical traffic on both router chains."""
    if zipf is None:
        return list(tokens)
    import numpy as np

    zs, _pool = zipf
    perm = np.asarray(pool_idx)
    n = len(perm)
    w = np.arange(1, n + 1, dtype=np.float64) ** -zs
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    seed = int(os.environ.get("CAP_SERVE_ZIPF_SEED", "1234"))
    rng = np.random.RandomState(seed * 7919 + 29)
    idx = perm[np.searchsorted(cdf, rng.random_sample(n_out))]
    return [tokens[i] for i in idx]


def _align_drive_tokens(drive_tokens, n_pools):
    """Owner-align the gateway drive corpus (``CAP_FRONTDOOR_ALIGN``,
    default 1): group the materialized sequence by owning pool — the
    parent-side ring is bit-identical to the router's (pinned by
    test_frontdoor_native's parity tests), so contiguous request
    windows become single-owner. That is the ingress shape ANY
    affinity-aware upstream tier produces (the Python FrontDoor driver
    itself ships per-pool sub-batches), and the shape that exercises
    the native relay's zero-copy splice path; ``=0`` leaves the Zipf
    stream unaligned, so nearly every frame mixes owners and rides the
    re-frame relay path instead. Both chain arms get the SAME corpus
    either way — the A/B stays frame-identical."""
    if os.environ.get("CAP_FRONTDOOR_ALIGN", "1") == "0":
        return drive_tokens
    from cap_tpu.fleet.frontdoor import ConsistentHashRing
    from cap_tpu.serve.vcache import token_digest

    ring = ConsistentHashRing(list(range(n_pools)))
    buckets = [[] for _ in range(n_pools)]
    for t in drive_tokens:
        buckets[ring.primary(token_digest(t))].append(t)
    return [t for b in buckets for t in b]


def _gateway_stats(host, port):
    """One CVB1 STATS round-trip against a gateway process — the
    router-side counter scrape the gateway A/B records (frontdoor.*
    routing counters + frontdoor.native.* relay counters)."""
    import socket

    from cap_tpu.serve import protocol as P

    s = socket.create_connection((host, port), timeout=30)
    try:
        s.settimeout(30)
        P.send_stats_request(s)
        ftype, entries = P.FrameReader(s).recv_frame()
        if ftype != P.T_STATS_RESP or entries[0][0] != 0:
            raise RuntimeError(f"gateway stats failed: {ftype}")
        return json.loads(entries[0][1])
    finally:
        s.close()


def _spawn_gateway(keyset_spec, chain):
    """A deployed router-tier gateway PROCESS: worker_main with a
    ``frontdoor:`` keyset, pinned to the requested router chain
    (``--frontdoor-chain python|native`` — no silent fallback arm
    contamination: a chain mismatch on the ready line is an error)."""
    import subprocess

    p = subprocess.Popen(
        [sys.executable, "-m", "cap_tpu.fleet.worker_main",
         "--keyset", keyset_spec, "--frontdoor-chain", chain,
         "--obs-port", "-1"],
        stdout=subprocess.PIPE, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    line = (p.stdout.readline() or "").strip()
    kv = dict(f.split("=", 1) for f in line.split()[1:] if "=" in f)
    if (not line.startswith("CAP_FLEET_READY")
            or kv.get("frontdoor_chain") != chain):
        p.kill()
        p.wait(timeout=30)
        raise RuntimeError(
            f"gateway chain={chain} did not come up: {line!r}")
    return p, ("127.0.0.1", int(kv["port"]))


def run_gateway_point(n_pools: int, pool_workers: int, chain: str,
                      keyset_spec: str, drive_tokens, n_clients: int,
                      req_tokens: int, seconds: float,
                      max_wait_ms: float, target_batch: int,
                      env_extra=None) -> dict:
    """Wire-speed router-tier arm: the same pools-behind-front-door
    topology as :func:`run_frontdoor_point`, but the router is ONE
    deployed gateway process (worker_main ``--keyset frontdoor:``)
    and the load is the native closed-loop C driver aimed at the
    gateway's front socket — client cost leaves the measurement, so
    the number is the ROUTER TIER's serve capacity, python chain vs
    native relay chain on the frame-identical pinned workload."""
    from cap_tpu import telemetry
    from cap_tpu.fleet import WorkerPool

    pools = [WorkerPool(pool_workers, keyset_spec=keyset_spec,
                        platform=_fleet_platform(keyset_spec),
                        target_batch=target_batch,
                        max_wait_ms=max_wait_ms, ping_interval=1.0,
                        env_extra=dict(env_extra or {}))
             for _ in range(n_pools)]
    gw = None
    try:
        for i, p in enumerate(pools):
            if not p.wait_all_ready(120.0):
                raise RuntimeError(f"pool {i} did not come up")
        spill = os.environ.get("CAP_SERVE_SPILL", "2.0")
        spec = ("frontdoor:" + ";".join(
            "pool=" + "+".join(
                f"{h}:{pt}" for h, pt in sorted(p.endpoints().values()))
            for p in pools) + f";spill={spill}")
        gw, gw_addr = _spawn_gateway(spec, chain)
        total, n_reqs = _native_drive([gw_addr], drive_tokens,
                                      req_tokens, seconds, n_clients)
        st = _gateway_stats(*gw_addr)
        ctr = st.get("counters") or {}
        lookups = ctr.get("frontdoor.lookups", 0)
        hits = ctr.get("frontdoor.affinity_hits", 0)
        misses = ctr.get("frontdoor.affinity_misses", 0)
        # the r21 counting contract, enforced per point: every routed
        # token is a lookup and lands in exactly one bucket — native
        # fast path included (its deltas fold into the same counters)
        if lookups != hits + misses:
            raise RuntimeError(
                f"front-door accounting broke: lookups={lookups} != "
                f"hits={hits} + misses={misses}")
        merged = telemetry.merge_snapshots(
            [(s or {}).get("snapshot")
             for pool in pools for s in pool.stats().values()])
        agg_counters = merged.get("counters") or {}
    finally:
        if gw is not None:
            gw.terminate()
            try:
                gw.wait(timeout=30)
            except Exception:  # noqa: BLE001 - last resort
                gw.kill()
        for p in pools:
            p.close()
    native = {k[len("frontdoor.native."):]: v for k, v in ctr.items()
              if k.startswith("frontdoor.native.")}
    vps = total / seconds
    return {
        "n_pools": n_pools,
        "pool_workers": pool_workers,
        "gateway_chain": chain,
        "keyset_spec": keyset_spec,
        "clients": n_clients,
        "req_tokens": req_tokens,
        "driver": "native",
        "throughput": round(vps, 1),
        "requests": n_reqs,
        "relay_us_per_token": (round(1e6 / vps, 3) if vps else None),
        "frontdoor": {
            "lookups": lookups,
            "affinity_hits": hits,
            "affinity_misses": misses,
            "affinity_hit_rate": (round(hits / lookups, 4)
                                  if lookups else None),
            "spills": ctr.get("frontdoor.spills", 0),
            "reroutes": ctr.get("frontdoor.reroutes", 0),
            "fallback_tokens": ctr.get("frontdoor.fallback_tokens", 0),
            "native_fallbacks": ctr.get("frontdoor.native_fallbacks",
                                        0),
        },
        "native": native,
        "cache": {
            "lookups": agg_counters.get("vcache.lookups", 0),
            "hits": agg_counters.get("vcache.hits", 0),
            "stale_accepts": agg_counters.get("vcache.stale_accepts",
                                              0),
        },
        "tokens_sent": total,
        "drive_corpus": len(drive_tokens),
    }


def _mk_tenant_tokens(iss: str, kid: str, n: int = 128):
    """Stub-verifiable tokens for ONE tenant: a shared header (kid) +
    payload (iss) with n distinct trailing segments, so the batcher's
    dedup can't collapse the load while tenant attribution stays
    per-issuer."""
    import base64 as _b64
    import json as _json

    def b64(obj):
        return _b64.urlsafe_b64encode(
            _json.dumps(obj).encode()).rstrip(b"=").decode()

    hdr = b64({"alg": "ES256", "kid": kid})
    pay = b64({"iss": iss})
    return [f"{hdr}.{pay}.s{i}.ok" for i in range(n)]


def _tenant_driver_proc(endpoints, tokens, req_tokens, start_at,
                        seconds, target_vps, outq):
    """One closed-loop per-tenant driver PROCESS: hammers its tenant's
    token pool, optionally rate-limited to target_vps (the flooding
    driver runs unbounded / at the configured flood rate), and splits
    its outcomes accepted / throttled / rejected so the fairness A/B
    can report the per-tenant vps + p99 view."""
    import time as _t

    from cap_tpu.fleet import FleetClient

    cl = FleetClient(endpoints, attempt_timeout=30.0,
                     total_deadline=120.0)
    lats = []
    ok = thr = rej = 0
    i = 0
    # warmup exclusion (CAP_SERVE_WARMUP_S): latencies sampled only
    # after the cold-start transient (first flushes, bucket prefill)
    # — the steady-state p99 is what the fairness bar describes; the
    # same window applies to every arm, flood and baseline alike
    warmup = float(os.environ.get("CAP_SERVE_WARMUP_S", "0"))
    while _t.time() < start_at:
        _t.sleep(0.005)
    t_start = _t.time()
    measure_from = t_start + warmup
    deadline = t_start + seconds
    sent = 0
    err = None
    try:
        while _t.time() < deadline:
            if target_vps and sent > (_t.time() - t_start) * target_vps:
                _t.sleep(0.002)
                continue
            batch = [tokens[(i + j) % len(tokens)]
                     for j in range(req_tokens)]
            i += req_tokens
            in_window = _t.time() >= measure_from
            t0 = _t.perf_counter()
            out = cl.verify_batch(batch)
            if in_window:
                lats.append(_t.perf_counter() - t0)
            sent += len(batch)
            for r in out:
                if isinstance(r, Exception):
                    if str(r).startswith("ThrottledError"):
                        thr += 1
                    else:
                        rej += 1
                else:
                    ok += 1
    except BaseException as e:  # noqa: BLE001 - reported to the parent
        err = f"{type(e).__name__}: {e}"
    finally:
        outq.put((ok, thr, rej, lats, err))


def run_fairness_point(arm: str, flood_vps: float, keyset_spec: str,
                       n_workers: int, n_victims: int,
                       req_tokens: int, seconds: float,
                       max_wait_ms: float, target_batch: int,
                       with_flood: bool = True) -> dict:
    """One fairness arm: a fleet with (fair) or without (fifo) the
    enforcement plane, a flooding tenant driver next to well-behaved
    drivers, per-tenant vps/p99 split from the drivers AND the exact
    merged worker counters. with_flood=False is the no-flood baseline
    the inflation ratios are computed against."""
    import multiprocessing as mp

    from cap_tpu.fleet import WorkerPool
    from cap_tpu.obs import decision as obs_decision

    env_extra = {"CAP_SERVE_VCACHE": "0"}   # honest scheduling A/B
    if arm == "fair":
        env_extra["CAP_SERVE_FAIR"] = "1"
        env_extra["CAP_SERVE_ADMIT_RATE"] = os.environ.get(
            "CAP_SERVE_FAIR_RATE", "2000")
        burst = os.environ.get("CAP_SERVE_FAIR_BURST")
        if burst:
            env_extra["CAP_SERVE_ADMIT_BURST"] = burst
    autoscale = {"min_workers": n_workers,
                 "max_workers": n_workers + 1,
                 "high_queue_per_worker": float(os.environ.get(
                     "CAP_SERVE_SCALE_WATERMARK", "2048")),
                 "sustain_ticks": 2, "quiet_ticks": 1000,
                 "interval_s": 1.0}
    pool = WorkerPool(n_workers, keyset_spec=keyset_spec,
                      platform=_fleet_platform(keyset_spec),
                      target_batch=target_batch,
                      max_wait_ms=max_wait_ms, ping_interval=0.5,
                      env_extra=env_extra,
                      autoscale=autoscale if arm == "fair" else None)
    try:
        if not pool.wait_all_ready(120.0):
            raise RuntimeError("fairness fleet did not come up")
        endpoints = sorted(pool.endpoints().values())
        quiet_toks = _mk_tenant_tokens(
            "https://tenant-wellbehaved.example", "kw")
        flood_toks = _mk_tenant_tokens(
            "https://tenant-flooding.example", "kf")
        # victim offered load is PINNED (CAP_SERVE_VICTIM_VPS per
        # driver, 0 = closed loop) so both arms and the no-flood
        # baseline see the identical well-behaved demand — that is
        # what makes the p99 inflation ratios comparable.
        victim_vps = float(os.environ.get("CAP_SERVE_VICTIM_VPS",
                                          "0"))
        n_flooders = int(os.environ.get("CAP_SERVE_FLOOD_CLIENTS",
                                        "1"))
        # the flood's batch size may differ from the victims' (a
        # flood of big frames behind small victim requests is the
        # head-of-line shape the FIFO control arm must exhibit)
        flood_req = int(os.environ.get("CAP_SERVE_FLOOD_REQ_TOKENS",
                                       str(req_tokens)))
        ctx = mp.get_context("spawn")
        outq = ctx.Queue()
        floodq = ctx.Queue()
        start_at = time.time() + max(3.0,
                                     (n_victims + n_flooders) * 0.2)
        procs = [ctx.Process(
            target=_tenant_driver_proc,
            args=(endpoints, quiet_toks, req_tokens, start_at,
                  seconds, victim_vps, outq), daemon=True)
            for _ in range(n_victims)]
        if with_flood:
            for _ in range(n_flooders):
                procs.append(ctx.Process(
                    target=_tenant_driver_proc,
                    args=(endpoints, flood_toks, flood_req, start_at,
                          seconds, flood_vps / n_flooders, floodq),
                    daemon=True))
        for p in procs:
            p.start()
        v_ok = v_thr = v_rej = 0
        v_lats = []
        errors = []
        for _ in range(n_victims):
            ok, thr, rej, lats, err = outq.get(timeout=seconds + 300)
            v_ok += ok
            v_thr += thr
            v_rej += rej
            v_lats.extend(lats)
            if err:
                errors.append(err)
        f_ok = f_thr = f_rej = 0
        f_lats = []
        if with_flood:
            for _ in range(n_flooders):
                ok, thr, rej, lats, err = floodq.get(
                    timeout=seconds + 300)
                f_ok += ok
                f_thr += thr
                f_rej += rej
                f_lats.extend(lats)
                if err:
                    errors.append(err)
        for p in procs:
            p.join(timeout=30)
        if errors:
            raise RuntimeError(f"fairness drivers failed: {errors[:3]}")
        merged = pool.stats_merged()
        agg_counters = merged["aggregate"]["counters"]
        tenants = obs_decision.tenant_totals(agg_counters,
                                             surface="serve")
        resize_events = pool.resize_events()
    finally:
        pool.close()
    v_lats.sort()
    f_lats.sort()
    return {
        "arm": arm,
        "with_flood": with_flood,
        "n_workers": n_workers,
        "victims": n_victims,
        "flood_target_vps": flood_vps if with_flood else 0,
        "victim_vps": round(v_ok / seconds, 1),
        "victim_p50_ms": round(_quantile(v_lats, 0.50) * 1e3, 2),
        "victim_p99_ms": round(_quantile(v_lats, 0.99) * 1e3, 2),
        "victim_throttled": v_thr,
        "victim_rejected": v_rej,
        "flood_vps": round(f_ok / seconds, 1),
        "flood_throttled": f_thr,
        "flood_p99_ms": round(_quantile(f_lats, 0.99) * 1e3, 2),
        "admission": {
            "checked": agg_counters.get("admission.checked", 0),
            "admitted": agg_counters.get("admission.admitted", 0),
            "throttled": agg_counters.get("admission.throttled", 0),
            "sheds": agg_counters.get("admission.sheds", 0),
        },
        "resize_events": resize_events,
        "tenants": tenants,
    }


def fairness_main() -> None:
    """Fairness A/B mode (``CAP_SERVE_FLOOD=<tenant_vps>``): a
    flooding tenant driver next to well-behaved drivers, run through
    a FAIR fleet (DRR + admission + autoscaler) and a FIFO control
    fleet, arms interleaved over ``CAP_SERVE_REPS``, plus one
    no-flood baseline per arm. Headlines: ``fairness_vps`` (the
    well-behaved tenant's verified/s under flood on the fair arm —
    bench-trend-tracked) and ``fair_p99_ms`` next to the inflation
    ratios the acceptance bar reads (fair ≤ 2× no-flood while fifo
    inflates)."""
    from cap_tpu import telemetry

    telemetry.enable()
    flood_vps = float(os.environ["CAP_SERVE_FLOOD"])
    n_workers = int(os.environ.get("CAP_SERVE_POOL_WORKERS", 1))
    keyset_spec = os.environ.get("CAP_SERVE_FLEET_KEYSET",
                                 "stub:batch_ms=1,token_us=300")
    n_victims = int(os.environ.get("CAP_SERVE_CLIENTS", 2))
    req_tokens = int(os.environ.get("CAP_SERVE_REQ_TOKENS", 64))
    seconds = float(os.environ.get("CAP_SERVE_SECONDS", 12))
    max_wait_ms = float(os.environ.get("CAP_SERVE_WAITS",
                                       "2").split(",")[0])
    target_batch = int(os.environ.get("CAP_SERVE_TARGET_BATCH", 8192))
    reps = int(os.environ.get("CAP_SERVE_REPS", 2))

    points = []
    baselines = {}
    for arm in ("fair", "fifo"):
        pt = run_fairness_point(arm, flood_vps, keyset_spec,
                                n_workers, n_victims, req_tokens,
                                max(4.0, seconds / 2), max_wait_ms,
                                target_batch, with_flood=False)
        baselines[arm] = pt
        print(f"fairness arm={arm:<5} NO-FLOOD  "
              f"victim_vps={pt['victim_vps']:>9.0f} "
              f"p99={pt['victim_p99_ms']:7.1f}ms", file=sys.stderr)
    for rep in range(reps):
        for arm in ("fair", "fifo"):      # interleaved, same-day arms
            pt = run_fairness_point(arm, flood_vps, keyset_spec,
                                    n_workers, n_victims, req_tokens,
                                    seconds, max_wait_ms,
                                    target_batch)
            pt["rep"] = rep
            points.append(pt)
            print(f"fairness arm={arm:<5} rep={rep} "
                  f"victim_vps={pt['victim_vps']:>9.0f} "
                  f"p99={pt['victim_p99_ms']:7.1f}ms  "
                  f"flood_vps={pt['flood_vps']:>9.0f} "
                  f"flood_throttled={pt['flood_throttled']}  "
                  f"resizes={len(pt['resize_events'])}",
                  file=sys.stderr)

    def _best(arm, key="victim_vps"):
        vals = [p[key] for p in points if p["arm"] == arm]
        return max(vals) if vals else None

    def _p99(arm):
        vals = [p["victim_p99_ms"] for p in points if p["arm"] == arm]
        return min(vals) if vals else None

    fairness_vps = _best("fair")
    fair_p99 = _p99("fair")
    fifo_p99 = _p99("fifo")
    base_fair = baselines["fair"]["victim_p99_ms"] or None
    base_fifo = baselines["fifo"]["victim_p99_ms"] or None
    print(json.dumps({
        "metric": "fairness_victim_verifies_per_sec",
        "value": fairness_vps,
        "unit": "verifies/sec",
        "fairness_vps": fairness_vps,
        "fair_p99_ms": fair_p99,
        "fifo_p99_ms": fifo_p99,
        "noflood_fair_p99_ms": base_fair,
        "noflood_fifo_p99_ms": base_fifo,
        "p99_inflation_fair": (round(fair_p99 / base_fair, 3)
                               if fair_p99 and base_fair else None),
        "p99_inflation_fifo": (round(fifo_p99 / base_fifo, 3)
                               if fifo_p99 and base_fifo else None),
        "fifo_victim_vps": _best("fifo"),
        "flood_target_vps": flood_vps,
        "throttled_total": sum(p["admission"]["throttled"]
                               for p in points),
        "sheds_total": sum(p["admission"]["sheds"] for p in points),
        "resize_events_total": sum(len(p["resize_events"])
                                   for p in points),
        "baselines": baselines,
        "points": points,
    }))


def frontdoor_main() -> None:
    """Multi-pool front-door mode (``CAP_SERVE_POOLS=N``): N fresh
    WorkerPools ("hosts") behind FrontDoor drivers, one run per
    routing arm in ``CAP_SERVE_ROUTING`` (default "affinity,rr"),
    arms INTERLEAVED over ``CAP_SERVE_REPS`` repetitions so same-day
    weather hits both arms equally. Headline:
    ``fleet_affinity_vps`` / ``fleet_rr_vps`` and their ratio — the
    §Round 16 affinity-vs-round-robin A/B (the per-worker verdict
    cache is ON in both arms; only the routing policy differs).

    GATEWAY-CHAIN A/B (``CAP_FRONTDOOR_CHAINS="python,native"``, the
    r21 arms): the same pool topology behind ONE deployed worker_main
    gateway per listed router chain, driven at the front socket by
    the native closed-loop C driver on a frame-identical pinned
    workload (Zipf mix pre-materialized once in the parent — see
    :func:`_materialize_drive_tokens`). ALL arms — routing × chain —
    interleave inside every rep. Headlines: ``fleet_native_vps`` /
    ``fleet_gateway_python_vps``, their ratio, the native arm's
    speedup over the in-driver ``fleet_affinity_vps`` baseline, and
    ``frontdoor_relay_us_per_token``. Set the env to "" to skip the
    gateway arms (routing-only legacy shape)."""
    n_pools = int(os.environ["CAP_SERVE_POOLS"])
    pool_workers = int(os.environ.get("CAP_SERVE_POOL_WORKERS", 1))
    keyset_spec = os.environ.get("CAP_SERVE_FLEET_KEYSET",
                                 "stub:batch_ms=1,token_us=300")
    n_clients = int(os.environ.get("CAP_SERVE_CLIENTS", 4))
    req_tokens = int(os.environ.get("CAP_SERVE_REQ_TOKENS", 64))
    seconds = float(os.environ.get("CAP_SERVE_SECONDS", 12))
    max_wait_ms = float(os.environ.get("CAP_SERVE_WAITS",
                                       "2").split(",")[0])
    target_batch = int(os.environ.get("CAP_SERVE_TARGET_BATCH", 8192))
    routings = [r for r in os.environ.get(
        "CAP_SERVE_ROUTING", "affinity,rr").split(",") if r]
    reps = int(os.environ.get("CAP_SERVE_REPS", 2))
    # Per-worker cache capacity: the fleet-scale regime is token
    # corpus >> one worker's cache (millions of users), which is
    # exactly when routing policy decides whether the fleet caches
    # the corpus ONCE (affinity: each host holds its ring share) or
    # N× with thrash (rr: every host needs everything).
    env_extra = {}
    if os.environ.get("CAP_SERVE_VCACHE_CAP"):
        env_extra["CAP_SERVE_VCACHE_CAP"] = \
            os.environ["CAP_SERVE_VCACHE_CAP"]
    if keyset_spec.startswith("stub"):
        tokens = [f"bench.{i:06d}.ok" for i in range(16384)]
    else:
        from cap_tpu import testing as T

        _, tokens = T.headline_fixtures(16384)

    # r21 gateway-chain arms: one deployed router process per chain,
    # native C drivers at the front. The drive corpus is materialized
    # ONCE here (pinned Zipf seed) so every chain arm replays the
    # identical byte stream.
    chains = [c for c in os.environ.get(
        "CAP_FRONTDOOR_CHAINS", "python,native").split(",") if c]
    zipf = _zipf_cfg()
    drive_tokens = _align_drive_tokens(
        _materialize_drive_tokens(
            tokens, zipf, _zipf_pool_indices(len(tokens), zipf)),
        n_pools)

    points = []
    gw_points = []
    for rep in range(reps):
        for routing in routings:      # interleaved: a,rr,a,rr,…
            pt = run_frontdoor_point(
                n_pools, pool_workers, routing, keyset_spec, tokens,
                n_clients, req_tokens, seconds, max_wait_ms,
                target_batch, env_extra=env_extra)
            pt["rep"] = rep
            points.append(pt)
            fdc = pt["frontdoor"]
            print(f"frontdoor pools={n_pools} routing={routing:<8} "
                  f"rep={rep}  thr={pt['throughput']:>9.0f}/s  "
                  f"p50={pt['p50_ms']:6.1f}ms "
                  f"p99={pt['p99_ms']:7.1f}ms  "
                  f"aff_hit={fdc['affinity_hit_rate']}  "
                  f"vc_hit="
                  f"{pt['cache']['hits']}/{pt['cache']['lookups']}",
                  file=sys.stderr)
        for chain in chains:          # …then gw-py,gw-native, same rep
            pt = run_gateway_point(
                n_pools, pool_workers, chain, keyset_spec,
                drive_tokens, n_clients, req_tokens, seconds,
                max_wait_ms, target_batch, env_extra=env_extra)
            pt["rep"] = rep
            pt["aligned"] = os.environ.get("CAP_FRONTDOOR_ALIGN",
                                           "1") != "0"
            gw_points.append(pt)
            fdc = pt["frontdoor"]
            print(f"frontdoor pools={n_pools} gateway={chain:<7} "
                  f"rep={rep}  thr={pt['throughput']:>9.0f}/s  "
                  f"relay={pt['relay_us_per_token']}us/tok  "
                  f"aff_hit={fdc['affinity_hit_rate']}  "
                  f"relays={pt['native'].get('relays', 0)} "
                  f"splices={pt['native'].get('splices', 0)}",
                  file=sys.stderr)

    def _best(routing):
        vals = [p["throughput"] for p in points
                if p["routing"] == routing]
        return max(vals) if vals else None

    def _gw_best(chain):
        vals = [p["throughput"] for p in gw_points
                if p["gateway_chain"] == chain]
        return max(vals) if vals else None

    affinity_vps = _best("affinity")
    rr_vps = _best("rr")
    native_vps = _gw_best("native")
    gw_python_vps = _gw_best("python")
    stale = (sum(p["cache"]["stale_accepts"] for p in points)
             + sum(p["cache"]["stale_accepts"] for p in gw_points))
    print(json.dumps({
        "metric": "fleet_affinity_verifies_per_sec",
        "value": affinity_vps,
        "unit": "verifies/sec",
        "fleet_affinity_vps": affinity_vps,
        "fleet_rr_vps": rr_vps,
        "affinity_speedup_vs_rr": (round(affinity_vps / rr_vps, 3)
                                   if affinity_vps and rr_vps
                                   else None),
        # r21 router-tier headlines: the native relay gateway vs the
        # python gateway on the identical pinned workload, plus the
        # native arm against the in-driver routing baseline above
        "fleet_native_vps": native_vps,
        "fleet_gateway_python_vps": gw_python_vps,
        "native_speedup_vs_python_gw": (
            round(native_vps / gw_python_vps, 3)
            if native_vps and gw_python_vps else None),
        "native_speedup_vs_affinity": (
            round(native_vps / affinity_vps, 3)
            if native_vps and affinity_vps else None),
        "frontdoor_relay_us_per_token": (
            round(1e6 / native_vps, 3) if native_vps else None),
        "n_pools": n_pools,
        "pool_workers": pool_workers,
        "vcache_cap": env_extra.get("CAP_SERVE_VCACHE_CAP"),
        "stale_accepts_total": stale,
        "points": points,
        "gateway_points": gw_points,
    }))


def fleet_main() -> None:
    from cap_tpu import telemetry

    # Parent-process recorder: pool supervision counters (respawns,
    # crashes, ping latency) land here and ride into the BENCH JSON.
    telemetry.enable()
    sizes = [int(s) for s in
             os.environ["CAP_SERVE_FLEET"].split(",") if s]
    keyset_spec = os.environ.get("CAP_SERVE_FLEET_KEYSET",
                                 "stub:batch_ms=1,token_us=300")
    n_clients = int(os.environ.get("CAP_SERVE_CLIENTS", 8))
    req_tokens = int(os.environ.get("CAP_SERVE_REQ_TOKENS", 64))
    seconds = float(os.environ.get("CAP_SERVE_SECONDS", 12))
    max_wait_ms = float(os.environ.get("CAP_SERVE_WAITS", "2").split(",")[0])
    target_batch = int(os.environ.get("CAP_SERVE_TARGET_BATCH", 8192))
    if keyset_spec.startswith("stub"):
        # constant first segment: stub tokens model real traffic's
        # few-distinct-JOSE-headers shape (decision family attribution
        # caches by header segment; one unique segment per token would
        # be a pathological workload no IdP produces)
        tokens = [f"bench.{i:06d}.ok" for i in range(16384)]
    else:
        from cap_tpu import testing as T

        _, tokens = T.headline_fixtures(16384)

    # serve-chain A/B: run every size once per listed chain (empty →
    # one run inheriting the environment's CAP_SERVE_NATIVE)
    chains = [c for c in os.environ.get(
        "CAP_SERVE_CHAINS", "").split(",") if c] or [None]
    # verdict-cache A/B: CAP_SERVE_VCACHES="on,off" runs every
    # (size, chain) arm once per listed cache state — the §Round 14
    # cached-vs-uncached Zipf headline pair
    vcaches = [v for v in os.environ.get(
        "CAP_SERVE_VCACHES", "").split(",") if v] or [None]
    points = []
    for n in sizes:
        for chain in chains:
            for vc in vcaches:
                pt = run_fleet_point(n, keyset_spec, tokens, n_clients,
                                     req_tokens, seconds, max_wait_ms,
                                     target_batch, serve_chain=chain,
                                     vcache=vc)
                points.append(pt)
                hit_line = ""
                if pt["cache"]["lookups"]:
                    rate = (100.0 * pt["cache"]["hits"]
                            / pt["cache"]["lookups"])
                    hit_line = f"  vc_hit={rate:.1f}%"
                print(f"fleet n={n} chain={chain or 'env'} "
                      f"vc={vc or 'env'}  "
                      f"thr={pt['throughput']:>9.0f}/s  "
                      f"p50={pt['p50_ms']:6.1f}ms "
                      f"p99={pt['p99_ms']:7.1f}ms{hit_line}  "
                      f"per-worker={pt['per_worker_tokens']}",
                      file=sys.stderr)

    best = max(points, key=lambda p: p["throughput"])
    smallest = min(points, key=lambda p: p["n_workers"])
    scaling = (round(best["throughput"] / smallest["throughput"], 3)
               if smallest["throughput"] else None)
    rec = telemetry.active()
    supervision = {
        k: v for k, v in sorted(rec.counters().items())
        if k.startswith("fleet.")
    } if rec is not None else {}
    ping = (rec.summary().get("fleet.ping_s") if rec is not None
            else None)
    # Reason-keyed decision counters across the whole sweep (worker-
    # side, summed from every point's exact merged snapshot) + the SLO
    # objective status over those counters: the fleet BENCH record is
    # self-describing from this round on (tools/bench_trend.py).
    from cap_tpu.obs import decision as obs_decision
    from cap_tpu.obs import slo as obs_slo

    sweep_counters: dict = {}
    for pt in points:
        for k, v in (pt.get("telemetry", {}).get("counters")
                     or {}).items():
            sweep_counters[k] = sweep_counters.get(k, 0) + int(v)
    if rec is not None:
        for k, v in rec.counters().items():
            sweep_counters[k] = sweep_counters.get(k, 0) + int(v)
    try:
        slo_results = [
            {"name": r["name"], "ok": r["ok"], "windows": r["windows"]}
            for r in obs_slo.evaluate_once({"counters": sweep_counters})
        ]
    except Exception as e:  # noqa: BLE001 - advisory field
        slo_results = [{"error": repr(e)}]
    def _chain_best(name):
        vals = [p["throughput"] for p in points
                if set((p.get("serve_chains") or {}).values()) == {name}]
        return max(vals) if vals else None

    native_vps = _chain_best("native")
    python_vps = _chain_best("python")

    # verdict-cache Zipf headline pair: best cache-on vs best
    # cache-off throughput among the Zipf-mix points (None unless the
    # Zipf mode and both arms ran)
    def _vc_best(state):
        vals = [p["throughput"] for p in points
                if p.get("vcache") == state and p.get("zipf_s")]
        return max(vals) if vals else None

    zipf_cached_vps = _vc_best("on")
    zipf_uncached_vps = _vc_best("off")
    # pipeline-occupancy headline (r22): the best point's busy/wall
    # ratio (the workload the throughput headline describes) + its
    # idle-gap p99 — where the microseconds waited while the headline
    # was being set; bench_trend tracks device_occupancy
    best_occ = best.get("occupancy") or {}
    idle_gap = (best.get("telemetry", {}).get("stage_latency")
                or {}).get("device.idle_gap_s") or {}
    print(json.dumps({
        "metric": "serve_fleet_verifies_per_sec",
        "value": best["throughput"],
        "unit": "verifies/sec",
        "p99_request_latency_ms": best["p99_ms"],
        "fleet_scaling_vs_smallest": scaling,
        # chain A/B headline (None unless both chains were run):
        # native-chain best vs python-chain best across the sweep
        "serve_native_vps": native_vps,
        "serve_python_vps": python_vps,
        "chain_speedup_native_vs_python": (
            round(native_vps / python_vps, 3)
            if native_vps and python_vps else None),
        # verdict-cache Zipf headline (None unless CAP_SERVE_ZIPF and
        # CAP_SERVE_VCACHES=on,off both ran): end-to-end vps with the
        # cache tier on vs off on the identical pinned token pool.
        "zipf_cached_vps": zipf_cached_vps,
        "zipf_uncached_vps": zipf_uncached_vps,
        "cache_speedup_on_vs_off": (
            round(zipf_cached_vps / zipf_uncached_vps, 3)
            if zipf_cached_vps and zipf_uncached_vps else None),
        "device_occupancy": (round(best_occ["occupancy"], 4)
                             if best_occ else None),
        "occupancy": best_occ or None,
        "idle_gap_p99_s": idle_gap.get("p99"),
        "flush_reasons": best.get("flush_reasons") or None,
        "placement_model": "single-owner-per-device",
        # Pool-side supervision attribution for the whole sweep:
        # respawn/crash/hung counters + health-ping latency quantiles.
        "supervision_counters": supervision,
        "ping_p99_s": round(ping["p99"], 6) if ping else None,
        "decisions": obs_decision.decision_counters(sweep_counters),
        # per-tenant rollup of the same sweep counters (issuer-hash
        # keyed: tokens / accept / reject mix / vcache hit splits) —
        # the BENCH record shows WHOSE traffic the headline served
        "tenants": obs_decision.tenant_totals(sweep_counters),
        "slo": slo_results,
        "points": points,
    }))


def transport_main() -> None:
    """CAP_SERVE_TRANSPORTS=1: the shm-vs-socket serve A/B and the
    Go-driver loadgen point.

    Emits ``shm_vps`` (closed-loop C drive over the mapped ring
    against a device-stubbed worker — the zero-copy ingest rate) next
    to the interleaved socket arm, and ``go_client_vps`` when a Go
    toolchain exists (``clients/go/captpu/loadgen`` against the same
    worker; null with a note otherwise — this image has no Go).
    """
    import ctypes
    import shutil
    import subprocess

    import numpy as np

    from cap_tpu import telemetry
    from cap_tpu.fleet.worker_main import StubKeySet
    from cap_tpu.serve import native_serve
    from cap_tpu.serve.worker import VerifyWorker

    telemetry.disable()
    seconds = float(os.environ.get("CAP_SERVE_SECONDS", 5))
    req_tokens = int(os.environ.get("CAP_SERVE_REQ_TOKENS", 64))
    depth = int(os.environ.get("CAP_SERVE_DEPTH", 48))
    n_conns = int(os.environ.get("CAP_SERVE_CLIENTS", 4))
    lib = native_serve.load()
    if not getattr(lib, "cap_shm_ok", False):
        raise RuntimeError("library lacks the shm TU "
                           "(run: make native-build)")
    chain = "native"
    try:
        worker = VerifyWorker(StubKeySet(raw=1), serve_native=True,
                              max_wait_ms=2.0, transport="shm",
                              vcache=False)
        if worker.serve_chain != "native":
            worker.close(deadline_s=5)
            raise RuntimeError("native chain unavailable")
    except Exception:  # noqa: BLE001 - python-chain fallback
        chain = "python"
        worker = VerifyWorker(StubKeySet(raw=1), serve_native=False,
                              max_wait_ms=2.0, transport="shm",
                              vcache=False)
    assert worker.transport == "shm"
    host, port = worker.address
    tokens = [f"bench.{i:06d}.ok" for i in range(8192)]
    encoded = [t.encode() for t in tokens]
    blob = np.frombuffer(b"".join(encoded), np.uint8)
    offs = np.zeros(len(encoded) + 1, np.int64)
    np.cumsum([len(e) for e in encoded], out=offs[1:])
    out_tokens = np.zeros(1, np.int64)
    out_reqs = np.zeros(1, np.int64)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    shm_dir = os.environ.get("CAP_SHM_DIR") or (
        "/dev/shm" if os.path.isdir("/dev/shm") else "/tmp")

    def drive(arm: str, window_s: float) -> float:
        t0 = time.perf_counter()
        if arm == "shm":
            rc = lib.cap_shm_drive(
                host.encode(), port, shm_dir.encode(),
                blob.ctypes.data_as(u8p), offs.ctypes.data_as(i64p),
                len(encoded), req_tokens, depth, window_s, n_conns,
                1 << 20,
                out_tokens.ctypes.data_as(i64p),
                out_reqs.ctypes.data_as(i64p))
        else:
            rc = lib.cap_bench_drive(
                host.encode(), port, blob.ctypes.data_as(u8p),
                offs.ctypes.data_as(i64p), len(encoded), req_tokens,
                depth, window_s, n_conns,
                out_tokens.ctypes.data_as(i64p),
                out_reqs.ctypes.data_as(i64p))
        elapsed = time.perf_counter() - t0
        if rc != 0 or int(out_tokens[0]) == 0:
            raise RuntimeError(f"{arm} drive failed (rc={rc})")
        return int(out_tokens[0]) / elapsed

    go_point = None
    go_note = None
    try:
        drive("socket", 0.5)        # warmup
        drive("shm", 0.5)
        best = {"socket": 0.0, "shm": 0.0}
        for _ in range(2):          # interleaved arms, best-of-2
            for arm in ("socket", "shm"):
                vps = drive(arm, seconds / 2)
                best[arm] = max(best[arm], vps)
                print(f"transport {arm:6s} chain={chain} "
                      f"vps={vps:>10.0f}", file=sys.stderr)
        go = shutil.which("go")
        if go:
            repo = os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))
            out = subprocess.run(
                [go, "run", "./loadgen", "-addr", f"{host}:{port}",
                 "-seconds", str(seconds / 2), "-batch",
                 str(req_tokens), "-conns", str(n_conns),
                 "-transport", "auto"],
                cwd=os.path.join(repo, "clients", "go", "captpu"),
                capture_output=True, text=True, timeout=300)
            if out.returncode == 0:
                go_point = json.loads(out.stdout.strip().splitlines()[-1])
            else:
                go_note = f"loadgen failed: {out.stderr[-500:]}"
        else:
            go_note = ("no Go toolchain on this host — run "
                       "'make go-conformance' + this mode where go "
                       "exists")
    finally:
        worker.close(deadline_s=10)
    print(json.dumps({
        "metric": "shm_verifies_per_sec",
        "value": best["shm"],
        "unit": "verifies/sec",
        "serve_chain": chain,
        "shm_vps": round(best["shm"], 1),
        "socket_vps": round(best["socket"], 1),
        "shm_vs_socket_speedup": (round(best["shm"] / best["socket"],
                                        3) if best["socket"] else None),
        "go_client_vps": (round(go_point["go_client_vps"], 1)
                          if go_point else None),
        "go_client_transport": (go_point or {}).get("transport"),
        "go_note": go_note,
    }))


def main() -> None:
    if os.environ.get("CAP_SERVE_TRANSPORTS"):
        # Transport mode: shm-vs-socket serve A/B + Go-driver loadgen.
        transport_main()
        return
    if os.environ.get("CAP_SERVE_FLOOD"):
        # Fairness mode: flooding-tenant A/B (fair DRR+admission fleet
        # vs FIFO control), per-tenant vps/p99 split + fairness_vps.
        fairness_main()
        return
    if os.environ.get("CAP_SERVE_POOLS"):
        # Multi-pool front-door mode: the affinity-vs-rr routing A/B.
        frontdoor_main()
        return
    if os.environ.get("CAP_SERVE_FLEET"):
        # Fleet mode builds no in-process engine: workers own their
        # devices exclusively (single-owner placement).
        fleet_main()
        return

    from cap_tpu import compile_cache, telemetry
    from cap_tpu._build import build_native

    build_native()
    compile_cache.enable()
    telemetry.enable()               # stage attribution in the JSON

    n_clients = int(os.environ.get("CAP_SERVE_CLIENTS", 32))
    req_tokens = int(os.environ.get("CAP_SERVE_REQ_TOKENS", 64))
    seconds = float(os.environ.get("CAP_SERVE_SECONDS", 12))
    waits = [float(w) for w in
             os.environ.get("CAP_SERVE_WAITS", "1,5,20").split(",")]
    target_batch = int(os.environ.get("CAP_SERVE_TARGET_BATCH", 8192))
    depths = [int(d) for d in
              os.environ.get("CAP_SERVE_DEPTHS", "1,2").split(",")]

    from cap_tpu.jwt.tpu_keyset import TPUBatchKeySet

    jwks, tokens = _fixtures()
    ks = TPUBatchKeySet(jwks)
    # Warm every (family, pad) bucket shape the batcher can flush:
    # coalesced batches pad to powers of two below target_batch.
    sz = 128
    while sz <= 16384:
        ks.verify_batch(tokens[:sz])
        sz *= 2

    points = []
    for w in waits:
        for depth in depths:
            pt = run_point(ks, tokens, w, n_clients, req_tokens,
                           seconds, target_batch, depth=depth)
            points.append(pt)
            print(f"max_wait={w:5.1f}ms depth={depth}  "
                  f"thr={pt['throughput']:>9.0f}/s  "
                  f"p50={pt['p50_ms']:6.1f}ms "
                  f"p95={pt['p95_ms']:7.1f}ms "
                  f"p99={pt['p99_ms']:7.1f}ms  reqs={pt['requests']}",
                  file=sys.stderr)

    best = max(points, key=lambda p: p["throughput"])
    rec = telemetry.active()
    # flush the occupancy plane (r22): the workers ran in-process, so
    # the interval accumulator is ours — publish before reading
    from cap_tpu.obs import occupancy as _occupancy

    _occupancy.publish(rec)
    stage_latency = {
        name: {"count": int(s["count"]), "p50": round(s["p50"], 6),
               "p95": round(s["p95"], 6), "p99": round(s["p99"], 6)}
        for name, s in sorted(rec.summary().items())
    } if rec is not None else {}
    from cap_tpu.obs import decision as obs_decision
    from cap_tpu.obs import slo as obs_slo

    counters = rec.counters() if rec is not None else {}
    try:
        slo_results = [
            {"name": r["name"], "ok": r["ok"], "windows": r["windows"]}
            for r in obs_slo.evaluate_once(
                rec.snapshot() if rec is not None else {})
        ]
    except Exception as e:  # noqa: BLE001 - advisory field
        slo_results = [{"error": repr(e)}]
    print(json.dumps({
        "metric": "serve_verifies_per_sec",
        "value": best["throughput"],
        "unit": "verifies/sec",
        "p99_request_latency_ms": best["p99_ms"],
        # Worker-side stage attribution accumulated over the sweep
        # (batcher fill/dispatch/collect, per-family dispatch.*).
        "telemetry": {"stage_latency": stage_latency},
        # pipeline-occupancy rollup over the whole sweep (r22):
        # busy/wall ratio, per-family split, dispatch count
        "occupancy": _occupancy.occupancy_from_counters(counters),
        # Decision/SLO self-description (cap_tpu.obs), serve surface.
        "decisions": obs_decision.decision_counters(counters),
        # per-tenant rollup (issuer-hash keyed), same counters
        "tenants": obs_decision.tenant_totals(counters),
        "slo": slo_results,
        "points": points,
    }))


if __name__ == "__main__":
    main()
