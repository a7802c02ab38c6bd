#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json`` through the served
verify path on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The system under test is ``VerifyWorker`` on its native serve chain,
in this process (which holds the chip), over a ``TPUBatchKeySet`` with
no CPU fallback. A configuration with a ``layout`` runs one such worker
per chip, each in a process of its own, behind the program's front door
(``lib/fleet.py``); this process then imports no JAX. Client processes
that never import JAX drive it over CVB1. Set-up makes keys and tokens
from the seed, starts the worker, verifies the traffic's warm-up
batches straight through the keyset, and runs the cell's own traffic
until enough of it was answered with no program built; the window
follows without a pause. Every answer is compared with the plain
reference (``lib/oracle.py``) once the window has closed.

Earlier lines go to standard error; the last line of standard output
is the JSON result. Without a TPU, or with fewer chips than the cell
asks for, it exits 1 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

# the benchmark's own files, and the checkout whose program it runs
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import (fixtures, fleet, load, oracle, spec, stats,  # noqa: E402
                 sut, trace)
from lib.sut import (BenchError, CompileCounter, TracedKeySet,  # noqa: E402
                     build_native, prewarm_batches, require_chips, say,
                     start_worker, tpu_keyset, use_compile_cache,
                     window_stats)

TRACE_MAX_S = 5.0       # a traced window is at most this long: the
                        # trace of a busy 10 s window took ~200 s to
                        # stop and read back (my chip run, PR 22)
GRACE_S = 60.0          # wait for answers past the window's close
WARM_MAX_S = 1100.0     # give up warming after this long


class Clients:
    """The traffic's client processes (spawned; they import no JAX)."""

    def __init__(self, ctx, port, tokens, t: dict, seed: int):
        self.pipes, self.procs = [], []
        for p in range(t["procs"]):
            a, b = ctx.Pipe()
            proc = ctx.Process(target=load.run_client, daemon=True,
                               args=(b, port, tokens, t, seed, p,
                                     t["procs"], GRACE_S))
            proc.start()
            b.close()
            self.pipes.append(a)
            self.procs.append(proc)
        for a in self.pipes:
            if not a.poll(300):
                raise BenchError("a client did not come up")
            a.recv()

    def tokens_answered_since(self, t: float) -> int:
        for a in self.pipes:
            a.send(("since", t))
        return sum(a.recv()[1] for a in self.pipes)

    def window(self, t0: float, t1: float) -> None:
        for a in self.pipes:
            a.send(("window", t0, t1))

    def results(self):
        out = []
        for a in self.pipes:
            if not a.poll(GRACE_S + 120):
                raise BenchError("a client sent no result")
            out.append(a.recv()[1])
        return out

    def close(self) -> None:
        for a in self.pipes:
            try:
                a.send(("stop",))
            except OSError:
                pass
        for proc in self.procs:
            proc.join(10)
            if proc.is_alive():
                proc.terminate()
                proc.join(5)


def warm_up(compiles, clients, t: dict):
    """Run the cell's own traffic until ``warm_tokens`` tokens were
    answered in requests sent after the last program was built. (A new
    shape shows as a trace event only once its tracing is done, so only
    answered requests show that the traffic runs on programs that
    exist.)"""
    t0 = time.perf_counter()
    while True:
        time.sleep(0.25)
        if time.perf_counter() - t0 > WARM_MAX_S:
            raise BenchError(f"still warming after {WARM_MAX_S:.0f} s")
        last = compiles.last()
        if clients.tokens_answered_since(last) >= t["warm_tokens"] \
                and compiles.last() == last:
            return


def layer_ctx(before: dict, after: dict, tsum: dict, window_s: float):
    keys = set(before["counters"]) | set(after["counters"])
    counters = {k: after["counters"].get(k, 0) - before["counters"].get(k, 0)
                for k in keys}
    series = {k: stats.series_delta(v, before["series"].get(k))
              for k, v in after["series"].items()}
    return {"counters": counters, "series": series, "trace": tsum,
            "window_s": window_s}


def compare(results, jwks_doc, tokens, pool):
    """Every answer against the reference. Returns the counts that
    decide ``correct`` and a few examples of disagreement."""
    seen = {}
    for r in results:
        for i, a in r["first"].items():
            seen.setdefault(i, []).append(a)
    idx = sorted(seen)
    want = oracle.reference(pool, jwks_doc, [tokens[i] for i in idx])
    bad, examples = 0, []
    for i, w in zip(idx, want):
        for a in seen[i]:
            if not oracle.agrees(a, w):
                bad += 1
                if len(examples) < 3:
                    examples.append(f"#{i} got {a[0]}:{a[1][:80]!r} "
                                    f"want {w[0]}:{str(w[1])[:80]}")
    return {"checked_tokens": len(idx), "mismatched": bad,
            "examples": examples,
            "accepted_ref": sum(1 for w in want if w[0])}


def parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(argv=None, keyset_factory=None, need_chip=True,
        root=spec.ROOT) -> int:
    """The command. ``keyset_factory`` and ``need_chip`` let a test
    drive a whole run on the CPU with a keyset of its own."""
    args = parse(argv)
    try:
        result = measure(args, keyset_factory, need_chip, root)
    except (BenchError, spec.SpecError) as e:
        print(f"error: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


def measure(args, keyset_factory=None, need_chip=True,
            root=spec.ROOT) -> dict:
    c = spec.cell(args.workload, root)
    s = Session(args, c, keyset_factory, need_chip)
    try:
        seconds = (min(args.seconds, TRACE_MAX_S) if args.trace
                   else args.seconds)
        d = s.drive(c["traffic"], seconds, args.trace)
        setup_s = d["w0"] - T_START
        peak = s.sut.memory_peak()
        device = s.sut.device()
        s.sut.close()
        say("setup", **{k: round(v, 3) if isinstance(v, float) else v
                        for k, v in s.setup.items()},
            setup_s=round(setup_s, 3))
        t0 = time.perf_counter()
        cmp = compare(d["results"], s.jwks_doc, s.tokens, s.pool)
        ref_s = time.perf_counter() - t0
        return report(args, c, device, setup_s, d, cmp, ref_s, peak,
                      len(s.tampered))
    finally:
        s.close()


class InProcess:
    """The worker in this process, which holds the chip: the path of a
    configuration without ``layout``."""

    def __init__(self, c: dict, traced: bool, keyset_factory=None,
                 need_chip: bool = True):
        import jax

        self.cfg, self.traced, self.factory = c["config"], traced, \
            keyset_factory
        self.worker = None
        self.devs = (require_chips(c["workload"]["chips"]) if need_chip
                     else jax.devices()[:1])
        say("device", platform=self.devs[0].platform,
            kind=json.dumps(self.devs[0].device_kind), count=len(self.devs),
            jax=jax.__version__,
            cache=use_compile_cache() if need_chip else "off")
        self.compiles = CompileCounter()

    def start(self, jwks_doc: dict) -> None:
        self.keyset = (self.factory or tpu_keyset)(jwks_doc, self.cfg)
        self.worker = start_worker(
            TracedKeySet(self.keyset) if self.traced else self.keyset,
            self.cfg)
        self.port = self.worker.address[1]

    def prewarm(self, batches) -> None:
        sut.run_prewarm(self.keyset, batches)

    def trace_start(self) -> None:
        self.tdir = sut.start_profile()

    def window(self, w0: float, w1: float):
        """The worker's stats at both ends of the window, which is a
        host span of the trace."""
        import jax

        time.sleep(max(0.0, w0 - time.perf_counter()))
        before = window_stats(self.worker)
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            time.sleep(max(0.0, w1 - time.perf_counter()))
        return before, window_stats(self.worker)

    def trace_stop(self) -> dict:
        return sut.stop_profile(self.tdir)

    def memory_peak(self) -> int:
        return sut.memory_peak(self.devs)

    def device(self) -> dict:
        return {"platform": self.devs[0].platform,
                "kind": self.devs[0].device_kind, "count": len(self.devs)}

    def close(self) -> None:
        if self.worker is not None:
            self.worker.close()
            self.worker = None


class Session:
    """One run's set-up: keys and tokens from the seed (signed in a
    process pool), the system under test (``InProcess``, or a
    ``fleet.Fleet`` where the configuration has a ``layout``); then
    ``drive`` runs traffic against it."""

    def __init__(self, args, c, keyset_factory=None, need_chip=True):
        self.args, self.c, self.sut = args, c, None
        self.ctx = multiprocessing.get_context("spawn")
        self.pool = self.ctx.Pool(max(1, min(12, (os.cpu_count() or 2) - 1)))
        try:
            self._start(keyset_factory, need_chip)
        except BaseException:
            self.close()
            raise

    def _start(self, keyset_factory, need_chip):
        args, cfg, t = self.args, self.c["config"], self.c["traffic"]
        jobs = fixtures.rsa_jobs(cfg["keys"], args.seed)
        keys_async = self.pool.starmap_async(fixtures.rsa_numbers, jobs)
        kind = fleet.Fleet if "layout" in cfg else InProcess
        self.sut = kind(self.c, args.trace, keyset_factory, need_chip)
        self.setup = {"build_s": build_native()}
        t0 = time.perf_counter()
        specs = fixtures.key_specs(
            cfg["keys"], args.seed,
            dict(zip([j[1] for j in jobs], keys_async.get())))
        self.jwks_doc = fixtures.jwks_document(specs)
        self.setup["keys_s"] = time.perf_counter() - t0
        t_sign = time.perf_counter()
        n = t["pool_tokens"]
        sign_async = self.pool.starmap_async(fixtures.sign_range, [
            (specs, cfg["kid_order"], cfg["claims"], args.seed, lo,
             min(n, lo + 2048)) for lo in range(0, n, 2048)])
        t0 = time.perf_counter()
        self.sut.start(self.jwks_doc)
        self.setup["worker_s"] = time.perf_counter() - t0
        tokens = [tok for part in sign_async.get() for tok in part]
        self.tokens, self.tampered = fixtures.with_tampered(
            tokens, args.seed, t["tampered_share"])
        self.setup["sign_s"] = time.perf_counter() - t_sign

    def drive(self, t: dict, seconds: float, traced: bool) -> dict:
        """Run traffic ``t``: warm up until nothing compiles, then the
        window. Returns the clients' records and the worker's counters
        and trace around the window."""
        compiles = self.sut.compiles
        t0 = time.perf_counter()
        self.sut.prewarm(prewarm_batches(self.tokens, self.c["config"],
                                         t.get("warm_sizes", {})))
        self.setup["prewarm_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        clients = Clients(self.ctx, self.sut.port, self.tokens, t,
                          self.args.seed)
        self.setup["clients_s"] = time.perf_counter() - t0
        try:
            t0 = time.perf_counter()
            warm_up(compiles, clients, t)
            self.setup["warm_s"] = time.perf_counter() - t0
            for kind in ("trace", "lower", "compile"):
                built = compiles.between(0, time.perf_counter(), kind)
                self.setup[f"{kind}s"] = len(built)
                self.setup[f"{kind}_s"] = sum(built)
            say("built", last_s=round(compiles.last() - T_START, 1),
                seconds=json.dumps(compiles.per_second(T_START)))
            if traced:
                self.sut.trace_start()
            w0 = time.perf_counter() + 0.05
            w1 = w0 + seconds
            clients.window(w0, w1)
            before, after = self.sut.window(w0, w1)
            tsum = self.sut.trace_stop() if traced else {}
            results = clients.results()
        finally:
            clients.close()
        return {"w0": w0, "w1": w1, "before": before, "after": after,
                "in_window": compiles.between(w0, w1),
                "traced_in_window": compiles.between(w0, w1, "trace"),
                "tsum": tsum,
                "results": results}

    def close(self):
        if self.sut is not None:
            self.sut.close()
        self.pool.terminate()
        self.pool.join()


def report(args, c, device, setup_s, d, cmp, ref_s, peak,
           n_tampered) -> dict:
    t = c["traffic"]
    w0, w1, results, tsum = d["w0"], d["w1"], d["results"], d["tsum"]
    in_window = d["in_window"]
    traced_in_window = d["traced_in_window"]
    done = [d for r in results for d in r["done"] if w0 <= d[1] <= w1]
    sent = [s for r in results for s in r["sent"] if w0 <= s[0] < w1]
    answered = sum(1 for r in results for d in r["done"] if w0 <= d[0] < w1)
    unanswered = len(sent) - answered
    errors = [e for r in results for e in r["errors"]]
    hung = sum(r["hung_conns"] for r in results)
    inconsistent = sum(r["inconsistent"] for r in results)
    rewrapped = sum(r["rewrapped"] for r in results)
    window_s = w1 - w0
    lat = [(d[1] - d[0]) * 1e3 for d in done]
    late = [x * 1e3 for r in results for t0, x in r["lateness"]
            if w0 <= t0 < w1]
    say("window", seconds=round(window_s, 3), requests=len(done),
        tokens=sum(d[2] for d in done), compiles_in_window=len(in_window),
        traces_in_window=len(traced_in_window),
        compile_s_in_window=round(sum(in_window), 3),
        generator_late_ms_p50=stats.percentile(late, 50),
        generator_late_ms_p99=stats.percentile(late, 99),
        generator_late_ms_max=max(late) if late else None)
    built = {k[len("compile.fn."):]: v - d["before"]["counters"].get(k, 0)
             for k, v in d["after"]["counters"].items()
             if k.startswith("compile.fn.")}
    say("built_in_window",
        programs=json.dumps({k: v for k, v in built.items() if v}))
    if t.get("latency") and len(lat) < 1000:
        say("warning", latency_samples=len(lat),
            note="fewer_than_1000_so_p99_has_under_10_beyond_it")
    vals = {
        "setup_s": (setup_s, "s"),
        "tokens_per_s": (sum(d[2] for d in done) / window_s, "tokens/s"),
        "req_p99_ms": (stats.percentile(lat, 99), "ms"),
        "req_p50_ms": (stats.percentile(lat, 50), "ms"),
    }
    metrics = {}
    breakdown = None
    if args.trace:
        lctx = layer_ctx(d["before"], d["after"], tsum, window_s)
        if tsum:
            say("trace", busy_s=tsum["busy_s"], window_s=tsum["window_s"],
                modules=json.dumps(sorted(tsum["module_s"])[:12]))
        for m in c["per_layer"]:
            v = spec.reader(m["name"], c["root"])(lctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if tsum:
            breakdown = {"device_ops": tsum["device_ops"],
                         "idle_gaps": tsum["idle_gaps"]}
    else:
        for m in c["end_to_end"]:
            v, unit = vals[m["name"]]
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": unit}
    checks = {
        "mismatched_answers": [cmp["mismatched"], 0],
        "inconsistent_repeats": [inconsistent, 0],
        "unanswered_requests": [unanswered, 0],
        "connection_errors": [len(errors) + hung, 0],
    }
    say("compare", checked_tokens=cmp["checked_tokens"],
        accepted_by_reference=cmp["accepted_ref"],
        tampered_in_pool=n_tampered, reference_s=round(ref_s, 3),
        rewrapped_rejects=rewrapped,
        examples=json.dumps(cmp["examples"] + errors[:3]))
    for k, (v, lim) in checks.items():
        print(f"check {k}={v} limit={lim}", file=sys.stderr, flush=True)
    correct = all(v <= lim for v, lim in checks.values())
    device = dict(device, memory_peak_bytes=int(peak))
    if args.trace and tsum:
        # per chip: a fleet's trace sums its workers' seconds
        n = tsum.get("workers", 1)
        device["busy_s"] = tsum["busy_s"] / n
        device["window_s"] = tsum["window_s"] / n
    out = {"correct": correct, "attempted": len(sent), "failed": unanswered,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    # repeats that differ only by a router's re-raised reject (counted
    # among inconsistent_repeats; see lib/load.py)
    out["diagnostics"] = {"rewrapped_rejects": rewrapped}
    out["checks"] = checks
    return out


if __name__ == "__main__":
    sys.exit(run())
