"""Fleet worker subprocess entry: ``python -m cap_tpu.fleet.worker_main``.

One process = one :class:`~cap_tpu.serve.worker.VerifyWorker` = one
exclusive device group (the pool passes the placement as environment —
see ``parallel.place.WorkerPlacement.env``). The process:

1. builds its keyset from ``--keyset`` (below), honoring the placement
   env BEFORE any jax backend init;
2. binds the serve socket and prints ONE machine-readable ready line on
   stdout (``CAP_FLEET_READY port=<p> pid=<p>``) — the pool parses it
   to learn the ephemeral port;
3. serves until SIGTERM, then drains gracefully: stops accepting,
   flushes every queued batch, answers the in-flight connections, and
   exits 0 (kill -9 is the CRASH path, exercised by the chaos suite).

Keyset specs (``--keyset``):

- ``stub`` / ``stub:batch_ms=F,token_us=F`` — the deterministic test
  engine (tokens ending ``.ok`` verify). The optional knobs sleep per
  flushed batch / per token to model DEVICE occupancy: ``time.sleep``
  releases the GIL and the "device time" of two worker processes then
  genuinely overlaps, which is exactly the fleet's scaling claim. No
  jax import — stub workers start in ~0.2 s.
- ``jwks:<path>`` — a real ``TPUBatchKeySet`` over the JWKS JSON file
  at ``<path>`` (imports jax + the crypto stack; the placement env
  decides which devices the backend sees).
- ``jwks-url:<url>`` — boot straight from a REMOTE JWKS via the
  keyplane: a ``KeyPlaneKeySet`` fetches the document, builds the
  device tables, and keeps them fresh (jittered periodic refresh +
  singleflight unknown-kid refresh; env knobs
  ``CAP_KEYPLANE_REFRESH_S`` / ``CAP_KEYPLANE_GRACE_S``). Hot key
  rotation without a worker restart — see docs/KEYPLANE.md.
- ``oidc:<issuer>`` — same, with the JWKS URL resolved through OIDC
  discovery (issuer-equality enforced).
- ``oidc-rp:issuer=I;client=C;nonce=N[;algs=ES256+RS256][;aud=a+b]
  [;keyset=<inner spec>]`` — the serve tier's FULL OIDC surface:
  wraps the inner engine (default ``stub:raw=1,echo=1``) in
  ``oidc.OIDCRawKeySet`` so every served token passes signature
  verification AND registered-claims validation (native rules engine
  behind ``CAP_OIDC_NATIVE``; see docs/SERVE.md).
- ``frontdoor:pool=h:p+h:p;pool=h:p[;routing=rr][;spill=2.0]`` — the
  router-tier process: this worker serves CVB1 on the front and
  routes every token to the named worker pools by consistent hash
  over its digest (the native serve chain hands the reader-computed
  sha256[:16] straight through the batcher — no re-hash). KEYS pushes
  to a front-door worker fan out to every pool behind it. See
  docs/SERVE.md §Front door.

Every keyset kind accepts the fleet's KEYS pushes (CVB1 type 11):
``swap_keys`` swaps the live tables and the ready line / STATS /
``/snapshot`` all report ``key_epoch`` so the pool can verify epoch
convergence. The stub records the epoch without changing verdicts —
rotation must never alter a stub fleet's ground truth, which is
exactly what the rotation chaos tests assert.
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
import time


class StubKeySet:
    """Deterministic verdict engine: tokens ending ``.ok`` verify.

    The fleet tests' ground truth — the router's CPU-oracle fallback
    uses the SAME class, so a verdict produced by any path (worker,
    failover peer, fallback) is comparable bit-for-bit.
    """

    def __init__(self, batch_ms: float = 0.0, token_us: float = 0.0,
                 pipeline: float = 0.0, raw: float = 0.0,
                 echo: float = 0.0):
        self._batch_s = batch_ms / 1e3
        self._token_s = token_us / 1e6
        # echo=1 (raw mode only): a verified token's payload is its
        # OWN base64url-decoded middle segment instead of the fixed
        # stub bytes — the crypto-free seam the OIDC serve surface and
        # the claims differential suite drive real claim JSON through
        # (verdict still suffix-determined; undecodable middles keep
        # the fixed payload so the stub can never raise).
        self._echo = bool(echo)
        # raw=1: serve the raw-claims interface real engines expose
        # (verify_batch_raw → payload BYTES per verified token), so a
        # bench against the stub exercises the same zero-reserialize
        # response path as a TPUBatchKeySet. Verdicts stay
        # suffix-determined either way.
        self._raw = bool(raw)
        # pipeline=1: expose verify_batch_async so the batcher runs
        # its 2-deep pipeline against the stub — the simulated device
        # occupancy of batch k+1 then overlaps batch k's drain, the
        # way a real device's H2D/compute overlap does. Opt-in: the
        # chaos suite's timing assumptions stay on the sync path.
        self._pipeline = bool(pipeline)
        self.key_epoch = 0

    def swap_keys(self, jwks, epoch=None, grace_s: float = 0.0) -> int:
        """Keyplane hook: record the pushed epoch. Verdicts stay
        suffix-determined — a rotation must not change the fleet
        tests' ground truth (that WOULD be a wrong verdict)."""
        self.key_epoch = (self.key_epoch + 1 if epoch is None
                          else int(epoch))
        return self.key_epoch

    def _results(self, tokens):
        from ..errors import InvalidSignatureError

        if self._raw:
            reject = InvalidSignatureError(
                "no known key successfully validated the token signature")
            ok = b'{"sub":"stub"}'
            if self._echo:
                return [self._echo_payload(t, ok)
                        if t.endswith(".ok") else reject for t in tokens]
            return [ok if t.endswith(".ok") else reject for t in tokens]
        return [
            {"sub": t} if t.endswith(".ok")
            else InvalidSignatureError(
                "no known key successfully validated the token signature")
            for t in tokens
        ]

    @staticmethod
    def _echo_payload(token: str, default: bytes) -> bytes:
        import base64
        import binascii

        parts = token.split(".")
        if len(parts) != 3:
            return default
        try:
            pad = "=" * (-len(parts[1]) % 4)
            # validate=True: stdlib b64decode silently DROPS foreign
            # characters otherwise, and a corrupt middle segment must
            # keep the fixed payload, not decode to garbage
            return base64.b64decode(
                parts[1].replace("-", "+").replace("_", "/") + pad,
                validate=True)
        except (ValueError, binascii.Error):
            return default

    def verify_batch(self, tokens):
        from ..obs import occupancy as _occupancy

        sleep_s = self._batch_s + self._token_s * len(tokens)
        # The simulated device time is a real dispatch-level busy
        # interval on the occupancy plane — the stubbed-device
        # occupancy baseline (PERF.md §Round 22) comes from here.
        with _occupancy.interval("stub"):
            if sleep_s > 0.0:
                time.sleep(sleep_s)  # models device occupancy (no GIL)
        return self._results(tokens)

    def __getattr__(self, name):
        # Mode-dependent interface: verify_batch_async exists only in
        # pipeline mode (the batcher's hasattr probe picks the right
        # dispatch path) and verify_batch_raw only in raw mode (the
        # worker's raw-claims wrapper probes it the same way).
        # (__dict__ lookup: __getattr__ must not recurse during
        # unpickling, before __init__ has run.)
        if name == "verify_batch_async" and self.__dict__.get("_pipeline"):
            return self._verify_batch_async
        if name == "verify_batch_raw" and self.__dict__.get("_raw"):
            return self.verify_batch
        raise AttributeError(name)

    def _verify_batch_async(self, tokens):
        from ..obs import occupancy as _occupancy

        done_at = time.monotonic() + self._batch_s \
            + self._token_s * len(tokens)
        results = self._results(tokens)
        # pipeline=1 arm: the busy interval spans dispatch → collect
        # return, so two in-flight stub batches overlap on the plane
        # exactly like a real device's H2D/compute overlap (the union
        # accounting never double-counts the overlap window).
        occ_t0 = _occupancy.begin()

        def collect():
            remaining = done_at - time.monotonic()
            if remaining > 0.0:
                time.sleep(remaining)   # occupancy overlaps next prep
            _occupancy.end("stub", occ_t0)
            return results

        return collect


def make_keyset(spec: str):
    """Build the worker's engine from a ``--keyset`` spec string."""
    if spec == "stub" or spec.startswith("stub:"):
        kwargs = {}
        if spec.startswith("stub:"):
            for kv in spec[len("stub:"):].split(","):
                if not kv:
                    continue
                k, _, v = kv.partition("=")
                if k not in ("batch_ms", "token_us", "pipeline", "raw",
                             "echo"):
                    raise ValueError(f"unknown stub option {k!r}")
                kwargs[k] = float(v)
        return StubKeySet(**kwargs)
    if spec.startswith("frontdoor:"):
        # Router-tier process: no device engine of its own — the
        # "keyset" is the digest-affinity router over remote pools.
        from .frontdoor import frontdoor_from_spec

        return frontdoor_from_spec(spec[len("frontdoor:"):])
    if spec.startswith("oidc-rp:"):
        # Full OIDC verify-AND-validate serving: wrap an inner engine
        # spec in the Provider-backed serve surface. Options are
        # ';'-separated k=v; `keyset=` holds the inner spec verbatim
        # (its own ','/':' intact). Discovery is injected, not
        # fetched — an `oidc-rp:` worker boots without IdP traffic.
        from ..oidc.serve_keyset import oidc_rp_keyset_from_spec

        opts = {}
        for part in spec[len("oidc-rp:"):].split(";"):
            if not part:
                continue
            k, _, v = part.partition("=")
            if k not in ("issuer", "client", "nonce", "algs", "aud",
                         "redirect", "keyset"):
                raise ValueError(f"unknown oidc-rp option {k!r}")
            opts[k] = v
        inner = make_keyset(opts.pop("keyset", "stub:raw=1,echo=1"))
        return oidc_rp_keyset_from_spec(opts, inner)
    if spec.startswith("jwks:"):
        _configure_devices()
        import json

        from ..jwt.jwk import parse_jwks
        from ..jwt.tpu_keyset import TPUBatchKeySet

        with open(spec[len("jwks:"):], "r") as f:
            doc = json.load(f)
        return TPUBatchKeySet(parse_jwks(doc))
    if spec.startswith("jwks-url:") or spec.startswith("oidc:"):
        _configure_devices()
        from ..keyplane import source_for_spec
        from ..keyplane.plane import KeyPlaneKeySet

        return KeyPlaneKeySet(
            source_for_spec(spec),
            interval_s=float(os.environ.get(
                "CAP_KEYPLANE_REFRESH_S", "300")),
            grace_s=float(os.environ.get("CAP_KEYPLANE_GRACE_S", "30")))
    raise ValueError(f"unknown keyset spec {spec!r}")


def _configure_devices() -> None:
    """Apply the placement env to jax BEFORE first backend use, and
    turn the persistent compile cache on so a respawned worker does
    not compile cold."""
    import jax

    from .. import compile_cache

    n_cpu = int(os.environ.get("CAP_FLEET_CPU_DEVICES", "0") or 0)
    if n_cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n_cpu)
    # platform="tpu": libtpu reads the chip bounds and visible chips
    # from the placement env at backend init — nothing to do here.
    compile_cache.enable()


def _device_fields() -> str:
    """Ready-line fields naming the devices JAX actually gave this
    process (``platform=none`` when it never touched JAX: the stub
    and router keysets use no device)."""
    if "jax" not in sys.modules:
        return " platform=none"
    import jax

    devs = jax.local_devices()
    out = (f" platform={devs[0].platform}"
           f" devices={','.join(str(d.id) for d in devs)}")
    if devs[0].platform == "tpu":
        # JAX numbers a one-chip slice's device 0 whichever chip it
        # is; the host chip comes from the placement libtpu honored.
        out += f" chips={os.environ.get('TPU_VISIBLE_CHIPS', 'all')}"
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="cap_tpu.fleet.worker_main")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--keyset", default="stub")
    ap.add_argument("--target-batch", type=int, default=4096)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--max-batch", type=int, default=32768)
    ap.add_argument("--drain-deadline-s", type=float, default=30.0)
    # Observability server (serve.obs): 0 = ephemeral port (default),
    # -1 = disabled. The bound port is announced on the ready line.
    ap.add_argument("--obs-port", type=int, default=0)
    # Serve chain: "native" (C++ frame I/O + lock-free ring), "python"
    # (reader/responder threads), or "auto" — CAP_SERVE_NATIVE=1 in
    # the environment selects native, anything else python. A native
    # request falls back to python when the library is unbuildable;
    # the ready line's serve_chain= field reports what actually runs.
    ap.add_argument("--serve-chain", default="auto",
                    choices=["auto", "native", "python"])
    # Front-door router chain (frontdoor: keysets only): "native" runs
    # the zero-copy relay gate (C++ readers route by digest against
    # the pushed-down ring and splice payload bytes to the owning
    # pool; Python keeps the slow path), "python" the classic
    # VerifyWorker(FrontDoor) gate, "auto" native unless
    # CAP_FRONTDOOR_NATIVE=0 — an unbuildable native gate falls back
    # to python with frontdoor.native_fallbacks counted. The ready
    # line's frontdoor_chain= field reports what actually runs.
    ap.add_argument("--frontdoor-chain", default="auto",
                    choices=["auto", "native", "python"])
    # Native telemetry plane: "auto" (on whenever the native chain and
    # telemetry are both on — CAP_SERVE_NATIVE_OBS in the environment
    # wins) or "off" (force the Python decision fold; the A/B knob
    # tools/bench_stages.py measures the obs-overhead table with).
    ap.add_argument("--native-obs", default="auto",
                    choices=["auto", "off"])
    # Transport capability: "shm" honors per-connection shared-memory
    # attach negotiations (CVB1 type 15, docs/SERVE.md §Transports) on
    # whichever serve chain runs; "socket" refuses them (counted
    # serve.shm_fallbacks); "auto" defers to CAP_SERVE_TRANSPORT in
    # the environment. The ready line's transport= field reports what
    # actually runs (a stale native library degrades shm → socket).
    ap.add_argument("--transport", default="auto",
                    choices=["auto", "socket", "shm"])
    # Verdict cache: "auto" (on unless CAP_SERVE_VCACHE=0 in the
    # environment) or "off" (force the cache tier — worker cache,
    # native digest handoff, batcher in-flight dedup — off; the
    # graceful-off switch docs/SERVE.md documents).
    ap.add_argument("--vcache", default="auto",
                    choices=["auto", "off"])
    # Crash postmortems: checkpoint telemetry to this path on a timer
    # and on SIGTERM drain, so the pool can collect a ≤interval-stale
    # document even after kill -9. Empty = disabled. The pool passes
    # the path via CAP_FLEET_PM_PATH (env wins over the default).
    ap.add_argument("--postmortem-path",
                    default=os.environ.get("CAP_FLEET_PM_PATH", ""))
    ap.add_argument("--pm-interval", type=float,
                    default=float(os.environ.get(
                        "CAP_FLEET_PM_INTERVAL", "2.0")))
    args = ap.parse_args(argv)

    from .. import telemetry
    from ..serve.worker import VerifyWorker

    # CAP_FLEET_TELEMETRY=0: run with the observability layer OFF
    # (decision accounting is the serve path's main per-token Python
    # cost once the native chain is on — PERF.md §Round 12 quantifies
    # the tradeoff; the STATS op then serves structural fields only).
    if os.environ.get("CAP_FLEET_TELEMETRY", "1") != "0":
        telemetry.enable()           # STATS op serves real numbers
    if args.native_obs == "off":
        os.environ["CAP_SERVE_NATIVE_OBS"] = "0"
    if args.vcache == "off":
        os.environ["CAP_SERVE_VCACHE"] = "0"
    keyset = make_keyset(args.keyset)
    serve_native = (None if args.serve_chain == "auto"
                    else args.serve_chain == "native")
    worker = None
    fd_chain = None
    from .frontdoor import (FrontDoor, NativeFrontDoorServer,
                            native_frontdoor_enabled)

    if isinstance(keyset, FrontDoor):
        want_native = (args.frontdoor_chain == "native"
                       or (args.frontdoor_chain == "auto"
                           and native_frontdoor_enabled()))
        fd_chain = "python"
        if want_native:
            try:
                worker = NativeFrontDoorServer(
                    keyset, host=args.host, port=args.port,
                    obs_port=(None if args.obs_port < 0
                              else args.obs_port))
                fd_chain = "native"
            except Exception as e:  # noqa: BLE001 - fall back loudly
                if args.frontdoor_chain == "native":
                    raise
                keyset._count({"frontdoor.native_fallbacks": 1})
                print(f"CAP_FRONTDOOR_FALLBACK "
                      f"{type(e).__name__}: {e}",
                      file=sys.stderr, flush=True)
    if worker is None:
        worker = VerifyWorker(keyset, host=args.host, port=args.port,
                              target_batch=args.target_batch,
                              max_wait_ms=args.max_wait_ms,
                              max_batch=args.max_batch,
                              obs_port=(None if args.obs_port < 0
                                        else args.obs_port),
                              serve_native=serve_native,
                              transport=(None if args.transport == "auto"
                                         else args.transport))
    pm = None
    if args.postmortem_path:
        from ..obs.postmortem import PostmortemWriter

        pm = PostmortemWriter(args.postmortem_path,
                              interval_s=args.pm_interval,
                              stats_fn=worker.stats)
    host, port = worker.address
    obs = worker.obs_address
    epoch = worker.key_epoch
    # The ONE ready line the pool parses; flushed so it cannot sit in a
    # stdio buffer while the pool's spawn timeout burns. Additive
    # fields (obs=, epoch=) ride the same k=v format the pool already
    # skips when unknown.
    print(f"CAP_FLEET_READY port={port} pid={os.getpid()}"
          + (f" obs={obs[1]}" if obs is not None else "")
          + (f" epoch={epoch}" if epoch is not None else "")
          + f" serve_chain={worker.serve_chain}"
          + f" transport={worker.transport}"
          + f" tel={int(telemetry.active() is not None)}"
          + _device_fields()
          + (f" frontdoor_chain={fd_chain}" if fd_chain else ""),
          flush=True)

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    stop.wait()
    # Graceful drain: stop accepting, flush queued batches (bounded),
    # give the responder threads a beat to write the last frames out.
    worker.close(deadline_s=args.drain_deadline_s)
    if pm is not None:
        # Fresh final checkpoint AFTER the drain: the postmortem then
        # reflects everything this process ever served.
        pm.close("sigterm-drain")
    time.sleep(0.2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
