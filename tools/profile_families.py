#!/usr/bin/env python3
"""Resident packed-path verifies/sec for EVERY algorithm family.

The headline bench measures the RS256/ES256 mix; this walks all ten
JOSE algorithms through the same resident methodology (records already
on device, min-of-3 slope, accept-sum checked) so the per-family
engine rates are on record. Usage:

    python tools/profile_families.py [n_tokens]
    python tools/profile_families.py [n_tokens] --mesh N
    python tools/profile_families.py [n_tokens] --trace
    python tools/profile_families.py [n_tokens] --ladder affine

``--mesh N`` runs every family's packed program under ``shard_map``
on an N-device mesh (VERDICT r4 #7). Without real multi-chip
hardware it forces the N-virtual-device CPU backend, where absolute
rates are meaningless but the SHARDED step itself compiles, executes,
and splits the batch n/N per device — so a sharding-overhead
regression (replication of the batch, a stray all-gather) shows up
as a per-device dispatch-size change long before real hardware does,
and on a real N-chip slice the same command captures the scaling
number.

``--trace`` (VERDICT r5 #6) additionally times each family from the
DEVICE TIMELINE: the dispatchers run under ``jax.profiler.trace``,
the trace-viewer JSON is parsed, and the per-dispatch ms is the union
span of on-device execution events (everything not on a host python
thread) divided by the dispatch count. Slope samples that exceed the
trace-implied rate by >15% are flagged ``SLOPE-OUTLIER`` — the
round-5 scoreboard's unannotated 1046k/s ES256 sample is exactly the
artifact this retires: a favorable host-link window inside the min-of-3
shifts the slope, but cannot shift the device timeline.

``--ladder {jacobian,affine}`` pins the ES* window-add law for the
affine-ladder A/B (docs/PERF.md round 6); default is the engine's own
default (CAP_TPU_EC_LADDER or jacobian).
"""
import glob
import gzip
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ALGS = ["RS256", "RS384", "RS512", "PS256", "PS384", "PS512",
        "ES256", "ES384", "ES512", "EdDSA"]


def _parse_args(argv):
    n, mesh_n, trace, ladder = 16384, None, False, None
    pos = []
    i = 0
    while i < len(argv):
        if argv[i] == "--mesh":
            if i + 1 >= len(argv):
                sys.exit("usage: profile_families.py [n_tokens] --mesh N")
            mesh_n = int(argv[i + 1])
            if mesh_n < 1 or mesh_n & (mesh_n - 1):
                sys.exit("--mesh N must be a power of two (packed "
                         "records pad to power-of-two batch sizes)")
            i += 2
        elif argv[i] == "--trace":
            trace = True
            i += 1
        elif argv[i] == "--ladder":
            if i + 1 >= len(argv) or argv[i + 1] not in ("jacobian",
                                                         "affine"):
                sys.exit("usage: --ladder {jacobian|affine}")
            ladder = argv[i + 1]
            i += 2
        else:
            pos.append(argv[i])
            i += 1
    if pos:
        n = int(pos[0])
    return n, mesh_n, trace, ladder


# --mesh spans the real devices; only a caller that set
# JAX_PLATFORMS=cpu gets N virtual CPU devices, configured here before
# first backend use.
_N_TOKENS, _MESH_N, _TRACE, _LADDER = _parse_args(sys.argv[1:])
if _MESH_N is not None and os.environ.get("JAX_PLATFORMS") == "cpu":
    import jax

    jax.config.update("jax_num_cpu_devices", _MESH_N)
    os.environ.setdefault("CAP_TPU_RNS", "1")
if _LADDER is not None:
    os.environ["CAP_TPU_EC_LADDER"] = _LADDER


def trace_device_ms(fns, reps: int = 3):
    """Device-timeline ms per dispatch set, via jax.profiler.

    Runs the family's dispatchers ``reps`` times back-to-back under a
    profiler trace, parses the trace-viewer JSON, and returns the
    union span (max end − min start, ms) of all EXECUTION events that
    are not on a host python thread — XLA device/runtime op events —
    divided by ``reps``. Ground truth against slope-method artifacts:
    host dispatch stalls and host-link weather stretch a wall-clock
    slope, but cannot add device-op span. Returns None when the trace
    carries no device events (unknown runtime).
    """
    import jax

    with tempfile.TemporaryDirectory() as td:
        with jax.profiler.trace(td):
            for _ in range(reps):
                for _, fn in fns:
                    fn().block_until_ready()
        paths = glob.glob(td + "/**/*.trace.json.gz", recursive=True)
        if not paths:
            return None
        events = []
        for path in paths:
            with gzip.open(path) as f:
                events.extend(json.load(f).get("traceEvents", []))
    host_tids = set()
    for e in events:
        if (e.get("ph") == "M" and e.get("name") == "thread_name"
                and "python" in str(e["args"].get("name", "")).lower()):
            host_tids.add((e["pid"], e["tid"]))
    spans = [(e["ts"], e["ts"] + e["dur"]) for e in events
             if e.get("ph") == "X" and e.get("dur", 0) > 0
             and (e["pid"], e["tid"]) not in host_tids
             and not str(e.get("name", "")).startswith("$")]
    if not spans:
        return None
    lo = min(s for s, _ in spans)
    hi = max(t for _, t in spans)
    return (hi - lo) / 1e3 / reps


def measure(alg: str, n: int, mesh=None, trace=False):
    from cap_tpu import testing as T
    from cap_tpu.jwt.jwk import JWK
    from cap_tpu.jwt.tpu_keyset import (
        TPUBatchKeySet,
        resident_dispatchers,
        resident_slope_vps,
    )

    priv, pub = T.generate_keys(alg)
    ks = TPUBatchKeySet([JWK(pub, kid="k0")], mesh=mesh)
    base = [T.sign_jwt(priv, alg, T.default_claims(sub=f"s{i}"), kid="k0")
            for i in range(512)]
    toks = (base * ((n // len(base)) + 1))[:n]
    n_tok, fns = resident_dispatchers(ks, toks)
    vps = resident_slope_vps(n_tok, fns)
    t_ms = trace_device_ms(fns) if trace else None
    return n_tok, vps, t_ms


def main():
    n = _N_TOKENS
    mesh = None
    if _MESH_N is not None:
        from cap_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(_MESH_N)
        print(f"mesh: {len(mesh.devices.flat)} devices "
              f"({mesh.devices.flat[0].platform})")
    mode = f", ladder={_LADDER}" if _LADDER else ""
    print(f"resident packed path, {n} tokens/family, min-of-3 slope"
          f"{mode}")
    for alg in ALGS:
        try:
            n_tok, vps, t_ms = measure(alg, n, mesh=mesh, trace=_TRACE)
            if vps is None:
                print(f"{alg:6s} no clean slope (timer noise)",
                      flush=True)
                continue
            line = (f"{alg:6s} {n_tok / vps * 1e3:7.1f} ms  "
                    f"{vps / 1e3:7.0f}k verifies/s")
            if t_ms is not None:
                trace_vps = n_tok / t_ms * 1e3
                line += (f"  | trace {t_ms:7.1f} ms "
                         f"{trace_vps / 1e3:7.0f}k/s")
                if vps > 1.15 * trace_vps:
                    # >15% over the device timeline: the slope sample
                    # is measurement weather, not engine speed.
                    line += "  SLOPE-OUTLIER"
            elif _TRACE:
                line += "  | trace n/a"
            print(line, flush=True)
        except Exception as e:  # noqa: BLE001 - report and continue
            print(f"{alg:6s} FAILED: {e}", flush=True)


if __name__ == "__main__":
    main()
