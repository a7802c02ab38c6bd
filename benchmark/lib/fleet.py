"""A configuration's worker ``layout``: one-chip verify workers behind
the program's front door, as a gateway tier deploys them.

    "layout": {"workers": W, "router": "frontdoor",
               "routing": "affinity" | "rr"}

The harness process imports no JAX. Each worker is a process pinned to
one chip by the program's own placement environment
(``cap_tpu.parallel.place.WorkerPlacement.env``, what ``WorkerPool``
gives its workers). It builds the keyset and ``VerifyWorker`` that
``sut.tpu_keyset`` / ``sut.start_worker`` build from the config (no CPU
fallback, the config's ``max_chunk``), pre-warms its own keyset, counts
its own builds, and answers control messages on a pipe. The router is
the program's front-door process (``python -m
cap_tpu.fleet.worker_main --keyset frontdoor:pool=...;routing=...``),
one pool per worker, on the CPU; the JAX-free clients connect to its
port. So the timed path is the program's router and its one-chip
workers, as deployed.

Why not ``WorkerPool`` itself: its workers are built from a spec
string, and the ``jwks:`` spec can neither turn the CPU fallback off,
set ``max_chunk`` nor pre-warm; and no one outside such a worker can
start or read its profile.

Times that cross the pipe are ``time.perf_counter()`` readings, which
on Linux is CLOCK_MONOTONIC: one clock for every process of the
machine, so a worker's times compare with the harness's.
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time

from . import spec, sut, trace

READY_S = 1100.0        # a cold worker compiles its tables' programs
ASK_S = 600.0           # the longest one control message may take


class Fleet:
    """The workers and the router of one run. ``start`` returns once
    every worker serves and the router answers."""

    def __init__(self, c: dict, traced: bool, keyset_factory=None,
                 need_chip: bool = True):
        cfg = c["config"]
        self.layout = lay = cfg["layout"]
        self.n = int(lay["workers"])
        if lay.get("router") != "frontdoor":
            raise spec.SpecError(f"layout router {lay.get('router')!r}: "
                                 "only 'frontdoor' is known")
        if lay.get("routing") not in ("affinity", "rr"):
            raise spec.SpecError(f"layout routing {lay.get('routing')!r}")
        if self.n != c["workload"]["chips"]:
            raise spec.SpecError(
                f"layout has {self.n} workers, the cell asks for "
                f"{c['workload']['chips']} chips: one worker a chip")
        self.cfg, self.traced = cfg, traced
        self.factory, self.need_chip = keyset_factory, need_chip
        self.procs, self.pipes, self.info = [], [], []
        self.router = None
        self.port = None
        self.compiles = _Compiles(self)

    # -- life cycle ---------------------------------------------------------

    def start(self, jwks_doc: dict) -> None:
        import multiprocessing

        from cap_tpu.parallel.place import WorkerPlacement

        ctx = multiprocessing.get_context("spawn")
        platform = "tpu" if self.need_chip else "cpu"
        held = os.environ.get("JAX_PLATFORMS", "")
        if self.need_chip and held and "tpu" not in held.split(","):
            # the placement would hand each worker "tpu": refuse as the
            # in-process path does when JAX finds another platform
            raise sut.BenchError(f"no TPU: JAX_PLATFORMS={held}")
        for i in range(self.n):
            env = WorkerPlacement(i, (i,), platform).env()
            a, b = ctx.Pipe()
            p = ctx.Process(target=worker_main, daemon=True,
                            args=(b, env, jwks_doc, self.cfg, self.traced,
                                  self.factory, self.need_chip))
            p.start()
            b.close()
            self.procs.append(p)
            self.pipes.append(a)
        for i, a in enumerate(self.pipes):
            self.info.append(self._reply(i, READY_S))
            sut.say("device", worker=i, **self.info[-1])
        self._start_router([d["port"] for d in self.info])

    def _start_router(self, ports) -> None:
        w = self.cfg["worker"]
        lay = self.layout
        # the native relay routes by affinity only; round-robin is the
        # Python router's
        chain = "native" if lay["routing"] == "affinity" else "python"
        pools = ";".join(f"pool=127.0.0.1:{p}" for p in ports)
        cmd = [sys.executable, "-m", "cap_tpu.fleet.worker_main",
               "--keyset", f"frontdoor:{pools};routing={lay['routing']}",
               "--port", "0", "--obs-port", "-1",
               "--serve-chain", "native" if w["serve_native"] else "python",
               "--frontdoor-chain", chain,
               "--transport", w["transport"],
               "--target-batch", str(w["target_batch"]),
               "--max-wait-ms", str(w["max_wait_ms"]),
               "--max-batch", str(w["max_batch"]),
               "--drain-deadline-s", "10"]
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(
                       [spec.ROOT] + [p for p in os.environ.get(
                           "PYTHONPATH", "").split(os.pathsep) if p]))
        self.router = subprocess.Popen(cmd, cwd=spec.ROOT, env=env,
                                       stdout=subprocess.PIPE, text=True)
        line = _read_ready(self.router, 120.0)
        fields = dict(kv.split("=", 1) for kv in line.split()[1:]
                      if "=" in kv)
        self.port = int(fields["port"])
        if fields.get("frontdoor_chain") != chain:
            raise sut.BenchError(f"front door chain is "
                                 f"{fields.get('frontdoor_chain')}, "
                                 f"not {chain}")
        threading.Thread(target=_drain, args=(self.router.stdout,),
                         daemon=True).start()
        sut.say("router", port=self.port, routing=lay["routing"],
                workers=self.n, chain=fields.get("frontdoor_chain"),
                serve_chain=fields.get("serve_chain"))

    def close(self) -> None:
        """Stop the router, then the workers, and wait for each."""
        if self.router is not None:
            self.router.terminate()
            try:
                self.router.wait(30)
            except subprocess.TimeoutExpired:
                self.router.kill()
                self.router.wait(10)
            self.router = None
        for a in self.pipes:
            try:
                a.send(("close",))
                if a.poll(60):
                    a.recv()
            except (OSError, EOFError):
                pass
        for p in self.procs:
            p.join(30)
            if p.is_alive():
                p.kill()
                p.join(10)
        self.pipes, self.procs = [], []

    # -- control messages ---------------------------------------------------

    def _reply(self, i: int, timeout: float = ASK_S):
        a = self.pipes[i]
        if not a.poll(timeout):
            raise sut.BenchError(f"worker {i} sent no answer in "
                                 f"{timeout:.0f} s")
        try:
            kind, value = a.recv()
        except EOFError:
            raise sut.BenchError(f"worker {i} exited") from None
        if kind == "error":
            raise sut.BenchError(f"worker {i}: {value}")
        return value

    def ask_all(self, *msg, timeout: float = ASK_S):
        """Send ``msg`` to every worker, then collect every answer."""
        for a in self.pipes:
            a.send(msg)
        return [self._reply(i, timeout) for i in range(self.n)]

    def prewarm(self, batches) -> None:
        self.ask_all("prewarm", batches, timeout=READY_S)

    def stats(self) -> dict:
        """Every worker's counters and series summed, and the router's
        ``frontdoor.*`` counters beside them."""
        from cap_tpu.serve.client import VerifyClient

        merged = merge_stats(self.ask_all("stats"))
        c = VerifyClient(port=self.port, timeout=30.0)
        try:
            fd = c.stats().get("counters") or {}
        finally:
            c.close()
        merged["counters"].update(
            {k: v for k, v in fd.items() if k.startswith("frontdoor.")})
        return merged

    def trace_start(self) -> None:
        self.ask_all("trace_start")

    def window(self, w0: float, w1: float):
        """Each worker marks the window in its own trace; the harness
        reads the stats at both ends."""
        self.ask_all("window", w0, w1)
        time.sleep(max(0.0, w0 - time.perf_counter()))
        before = self.stats()
        time.sleep(max(0.0, w1 - time.perf_counter()))
        return before, self.stats()

    def trace_stop(self) -> dict:
        sums = self.ask_all("trace_stop")
        for i, s in enumerate(sums):
            if s:
                sut.say("worker_trace", worker=i, busy_s=s["busy_s"],
                        window_s=s["window_s"],
                        idle_share=round(1 - s["busy_s"] / s["window_s"],
                                         6))
            else:
                sut.say("worker_trace", worker=i,
                        note="no_device_op_in_the_window")
        return merge_traces(sums)

    def memory_peak(self) -> int:
        """The fullest worker's peak."""
        return max(self.ask_all("memory"))

    def device(self) -> dict:
        d = self.info[0]
        return {"platform": d["platform"], "kind": d["kind"],
                "count": sum(x["count"] for x in self.info)}


class _Compiles:
    """The builds of every worker, as ``sut.CompileCounter`` shows one
    process's."""

    def __init__(self, fleet: Fleet):
        self.fleet = fleet

    def last(self) -> float:
        return max(self.fleet.ask_all("last_build"))

    def between(self, t0, t1, kind="compile"):
        return [d for part in self.fleet.ask_all("builds", t0, t1, kind)
                for d in part]

    def per_second(self, t0: float):
        events = [e for part in self.fleet.ask_all("events") for e in part]
        return sut.per_second(events, t0)


def _read_ready(proc, timeout: float) -> str:
    out = {}

    def read():
        for line in proc.stdout:
            if line.startswith("CAP_FLEET_READY"):
                out["line"] = line
                return

    th = threading.Thread(target=read, daemon=True)
    th.start()
    th.join(timeout)
    if "line" not in out:
        raise sut.BenchError(f"the front door did not come up (exit "
                             f"{proc.poll()})")
    return out["line"]


def _drain(stream) -> None:
    for line in stream:
        print(line, end="", file=sys.stderr, flush=True)


# -- merging the workers' readings -----------------------------------------

def merge_stats(parts) -> dict:
    """Counters and histogram series (count, sum, buckets) summed."""
    counters, series = {}, {}
    for s in parts:
        for k, v in s["counters"].items():
            if isinstance(v, (int, float)):
                counters[k] = counters.get(k, 0) + v
        for k, v in s["series"].items():
            m = series.setdefault(k, {"count": 0, "sum": 0.0,
                                      "buckets": {}})
            m["count"] += int(v.get("count", 0))
            m["sum"] += float(v.get("sum", 0.0))
            for b, n in (v.get("buckets") or {}).items():
                m["buckets"][str(b)] = m["buckets"].get(str(b), 0) + int(n)
    return {"counters": counters, "series": series}


def merge_traces(parts, top: int = 10) -> dict:
    """Busy, window and program seconds summed over the workers (so an
    idle share read from them is the window-weighted mean); device ops
    summed by name; the ``top`` longest idle gaps of any worker."""
    parts = [p for p in parts if p]
    if not parts:
        return {}
    ops, mods = {}, {}
    for p in parts:
        for k, v in p["device_ops"]:
            ops[k] = ops.get(k, 0.0) + v
        for k, v in p["module_s"].items():
            mods[k] = mods.get(k, 0.0) + v
    gaps = sorted((g for p in parts for g in p["idle_gaps"]),
                  key=lambda g: -g[1])
    return {"busy_s": sum(p["busy_s"] for p in parts),
            "window_s": sum(p["window_s"] for p in parts),
            "devices": sum(p["devices"] for p in parts),
            "workers": len(parts), "module_s": mods,
            "device_ops": [[k, v] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [list(g) for g in gaps[:top]]}


# -- the worker process ----------------------------------------------------

def worker_main(conn, env: dict, jwks_doc: dict, cfg: dict, traced: bool,
                keyset_factory, need_chip: bool) -> None:
    """Process entry: one worker on the chip its placement names."""
    os.environ.update(env)        # before JAX is imported
    os.dup2(2, 1)                 # standard output is the harness's
    try:
        w = _Worker(jwks_doc, cfg, traced, keyset_factory, need_chip)
    except Exception as e:  # noqa: BLE001 - reported to the harness
        conn.send(("error", f"{type(e).__name__}: {e}"))
        return
    conn.send(("ok", w.info))
    try:
        while True:
            try:
                msg = conn.recv()
            except EOFError:
                return
            try:
                conn.send(("ok", w.handle(*msg)))
            except Exception as e:  # noqa: BLE001 - reported to the harness
                conn.send(("error", f"{type(e).__name__}: {e}"))
            if msg[0] == "close":
                return
    finally:
        w.close()


class _Worker:
    def __init__(self, jwks_doc, cfg, traced, keyset_factory, need_chip):
        import jax

        self.devs = sut.require_chips(1) if need_chip else jax.devices()[:1]
        cache = sut.use_compile_cache() if need_chip else "off"
        self.compiles = sut.CompileCounter()
        self.keyset = (keyset_factory or sut.tpu_keyset)(jwks_doc, cfg)
        self.worker = sut.start_worker(
            sut.TracedKeySet(self.keyset) if traced else self.keyset, cfg)
        d = self.devs[0]
        self.info = {"port": self.worker.address[1],
                     "platform": d.platform, "kind": d.device_kind,
                     "count": len(self.devs), "jax": jax.__version__,
                     "cache": cache,
                     "chips": os.environ.get("TPU_VISIBLE_CHIPS", "-")}
        self.tdir = self.marker = None

    def handle(self, op, *args):
        if op == "prewarm":
            sut.run_prewarm(self.keyset, args[0])
            return None
        if op == "last_build":
            return self.compiles.last()
        if op == "builds":
            return self.compiles.between(*args)
        if op == "events":
            return list(self.compiles.events)
        if op == "stats":
            return sut.window_stats(self.worker)
        if op == "trace_start":
            self.tdir = sut.start_profile()
            return None
        if op == "window":
            self.marker = threading.Thread(target=_mark_window, args=args,
                                           daemon=True)
            self.marker.start()
            return None
        if op == "trace_stop":
            self.marker.join()
            tsum = sut.stop_profile(self.tdir)
            self.tdir = None
            return tsum
        if op == "memory":
            return int(sut.memory_peak(self.devs))
        if op == "close":
            self.close()
            return None
        raise ValueError(f"unknown message {op!r}")

    def close(self) -> None:
        if self.worker is not None:
            self.worker.close()
            self.worker = None


def _mark_window(w0: float, w1: float) -> None:
    """The traced window as a host span of this worker's trace."""
    from jax.profiler import TraceAnnotation

    time.sleep(max(0.0, w0 - time.perf_counter()))
    with TraceAnnotation(trace.WINDOW):
        time.sleep(max(0.0, w1 - time.perf_counter()))
