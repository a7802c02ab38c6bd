"""Load client: one process of the traffic, speaking CVB1 over TCP.

It never imports JAX (the chip belongs to the worker's process). It
runs the cell's traffic from the moment it connects: the parent's
warm-up is this same traffic. The parent then sends the window
``(t0, t1)`` on the control pipe; after ``t1`` no request is sent, and
every request in flight is waited for up to ``grace_s``.

Each completed request is recorded as ``(t_start, t_done, n_tokens)``:
``t_start`` is the actual send in a closed loop and the scheduled send
in an open loop. The first answer to each pool index is kept for the
comparison with the reference; any later answer to the same index must
equal it byte for byte, and each that does not is counted.
"""

from __future__ import annotations

import collections
import socket
import threading
import time

from . import cvb1, traffic
from .oracle import RELAY_HEAD

clock = time.perf_counter


class _Recorder:
    def __init__(self):
        self.lock = threading.Lock()
        self.done = []            # (t_start, t_done, n_tokens)
        self.first = {}           # pool index -> (status, payload)
        self.inconsistent = 0
        self.rewrapped = 0        # of those, one reject under another's
                                  # RELAY_HEAD
        self.errors = []
        self.sent = []            # (t_start, n_tokens) of every request
        self.lateness = []        # open loop: (scheduled, actual - scheduled)

    def answers(self, t_start, idx, out):
        t_done = clock()
        first = self.first
        bad = rewrapped = 0
        for i, a in zip(idx, out):
            prev = first.get(i)
            if prev is None:
                first[i] = a
            elif prev != a:
                bad += 1
                rewrapped += _rewrapped(prev, a)
        with self.lock:
            self.done.append((t_start, t_done, len(idx)))
            self.inconsistent += bad
            self.rewrapped += rewrapped


def _rewrapped(a, b) -> bool:
    """Two answers that differ only by one reject carrying the other
    under ``RELAY_HEAD``."""
    return a[0] == b[0] == 1 and (a[1] == RELAY_HEAD + b[1]
                                  or b[1] == RELAY_HEAD + a[1])


def _connect(port: int):
    s = socket.create_connection(("127.0.0.1", port), timeout=300.0)
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return s, s.makefile("rb", buffering=1 << 20)


def _closed_conn(port, entries, pick, depth, rec, stop):
    """One connection keeping ``depth`` requests in flight."""
    sock, rf = _connect(port)
    inflight = collections.deque()
    try:
        def send():
            idx = pick()
            frame = cvb1.request(entries, idx)
            t = clock()
            sock.sendall(frame)
            inflight.append((t, idx))
            with rec.lock:
                rec.sent.append((t, len(idx)))

        for _ in range(depth):
            send()
        while inflight:
            out = cvb1.read_response(rf, len(inflight[0][1]))
            t, idx = inflight.popleft()
            rec.answers(t, idx, out)
            if not stop.is_set():
                send()
    except (OSError, cvb1.FrameError) as e:
        with rec.lock:
            rec.errors.append(f"{type(e).__name__}: {e}")
    finally:
        rf.close()
        sock.close()


def _open_reader(rf, pending, rec, finished):
    try:
        while True:
            with pending["cv"]:
                while not pending["q"] and not finished.is_set():
                    pending["cv"].wait(0.05)
                if not pending["q"]:
                    return
                n = len(pending["q"][0][1])
            out = cvb1.read_response(rf, n)
            with pending["cv"]:
                t, idx = pending["q"].popleft()
            rec.answers(t, idx, out)
    except (OSError, cvb1.FrameError) as e:
        with rec.lock:
            rec.errors.append(f"{type(e).__name__}: {e}")


def run_client(ctl, port, tokens, t: dict, seed: int, proc: int,
               n_procs: int, grace_s: float = 60.0):
    """Process entry: run the traffic until the window closes, then
    send the record back on ``ctl``."""
    entries = [cvb1.entry(tok) for tok in tokens]
    del tokens
    rec = _Recorder()
    stop = threading.Event()
    window = {}

    def control():
        while True:
            msg = ctl.recv()
            if msg[0] == "since":
                # tokens of requests sent after msg[1] and answered
                with rec.lock:
                    n = sum(d[2] for d in rec.done if d[0] > msg[1])
                ctl.send(("since", n))
                continue
            if msg[0] == "window":
                window["t0"], window["t1"] = msg[1], msg[2]
                delay = window["t1"] - clock()
                if delay > 0:
                    time.sleep(delay)
            break
        stop.set()

    threads = []
    if t["loop"] == "closed":
        pick = traffic.picker(t, seed, proc, n_procs)
        lock = threading.Lock()

        def locked_pick():
            with lock:
                return pick()

        for _ in range(t["conns"]):
            threads.append(threading.Thread(
                target=_closed_conn, daemon=True,
                args=(port, entries, locked_pick, t["depth"], rec, stop)))
        for th in threads:
            th.start()
        ctl.send(("ready",))
        control()
    else:
        threads = _run_open(ctl, port, entries, t, seed, proc, n_procs,
                            rec, stop, control)
    deadline = clock() + grace_s
    for th in threads:
        th.join(max(0.0, deadline - clock()))
    hung = sum(th.is_alive() for th in threads)
    with rec.lock:
        ctl.send(("result", {
            "done": rec.done, "sent": rec.sent, "first": rec.first,
            "inconsistent": rec.inconsistent,
            "rewrapped": rec.rewrapped, "errors": rec.errors,
            "lateness": rec.lateness, "hung_conns": hung,
            "window": (window.get("t0"), window.get("t1"))}))


def _run_open(ctl, port, entries, t, seed, proc, n_procs, rec, stop,
              control):
    """Poisson arrivals over ``conns`` connections in turn; one reader
    thread per connection. A request is timed from its scheduled send."""
    pick = traffic.picker(t, seed, proc, n_procs)
    gaps = traffic.arrival_gaps(t["rate_rps"] / n_procs, seed, proc)
    conns = [_connect(port) for _ in range(t["conns"])]
    finished = threading.Event()
    pend = [{"q": collections.deque(), "cv": threading.Condition()}
            for _ in conns]
    readers = [threading.Thread(target=_open_reader, daemon=True,
                                args=(rf, p, rec, finished))
               for (_, rf), p in zip(conns, pend)]
    for th in readers:
        th.start()

    def sender():
        k = 0
        t_next = clock()
        try:
            while not stop.is_set():
                t_next += next(gaps)
                now = clock()
                if t_next > now:
                    time.sleep(t_next - now)
                idx = pick()
                frame = cvb1.request(entries, idx)
                c = k % len(conns)
                k += 1
                with pend[c]["cv"]:
                    pend[c]["q"].append((t_next, idx))
                    pend[c]["cv"].notify()
                sent_at = clock()
                conns[c][0].sendall(frame)
                with rec.lock:
                    rec.sent.append((t_next, len(idx)))
                    rec.lateness.append((t_next, sent_at - t_next))
        except OSError as e:
            with rec.lock:
                rec.errors.append(f"{type(e).__name__}: {e}")
        finally:
            finished.set()

    snd = threading.Thread(target=sender, daemon=True)
    snd.start()
    ctl.send(("ready",))
    control()
    snd.join()
    return readers + [_Closer(conns, readers)]


class _Closer(threading.Thread):
    """Closes the open loop's sockets once its readers are done."""

    def __init__(self, conns, readers):
        super().__init__(daemon=True)
        self._conns, self._readers = conns, readers
        self.start()

    def run(self):
        for th in self._readers:
            th.join()
        for sock, rf in self._conns:
            rf.close()
            sock.close()
