"""Verdict cache: serve repeated tokens at memory speed.

Real ingress traffic for an auth verifier is massively repetitive —
the same bearer token arrives hundreds of times within its lifetime
(ROADMAP item #3; the Zipf harness measured repeat_rate ≈ 0.996 on
realistic mixes). This module is the correctness-preserving caching
tier in front of the verify engines: a sharded, bounded map from
**token digest** (sha256 of the token bytes, truncated to 16 bytes —
collision-resistant, so digest equality IS token equality) to the
token's verdict, clamped so a cached entry can never outlive:

- the token's own ``exp`` (and never activate before ``nbf``) — both
  parsed once, at insert time, from the claims the accept carries;
- the key-table **epoch**: every entry is tagged with the epoch it was
  verified under; a keyplane rotation bumps the cache epoch atomically
  (:meth:`VerdictCache.bump_epoch`) and entries from the previous
  epoch survive only inside the rotation's grace window (default 0 —
  cached verdicts die IMMEDIATELY on rotation; the engines' own grace
  handling serves the re-verify);
- a hard TTL (``max_ttl_s``) as belt-and-braces bound for entries
  whose claims carry no ``exp``.

What is cached: **accepts** (the claims payload — for raw-claims
engines these are exactly the token's own payload bytes, so a cache
hit is byte-identical to a fresh verify by construction) and **only
terminal rejects** — reason classes where the verdict is a pure
function of the token bytes and the key material
(:data:`CACHEABLE_REJECTS`: bad_signature / malformed / not_signed).
Transient or environment-dependent classes (unknown_kid before a
refresh, jwks_error, transport, expired, internal) are NEVER cached:
the next arrival must reach an engine.

Any clamp uncertainty resolves to a MISS: the token goes to the
engine and the verdict is whatever the engine says — the cache can
change how fast a verdict is produced, never which verdict. A final
re-validation at serve time backs this with a tripwire counter
(``vcache.stale_accepts``, SLO-pinned to 0).

Counters (one ``count_many`` lock round per batched lookup or fill):
``vcache.lookups == vcache.hits + vcache.misses`` exactly
(obs-smoke gates this), plus inserts / evictions / fill_fallbacks /
epoch_bumps / clamp_drops, and a ``vcache.size`` gauge on the worker
scrape.
"""

from __future__ import annotations

import base64
import binascii
import hashlib
import json
import threading
import time
from collections import deque
from itertools import islice, repeat
from operator import itemgetter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import telemetry
from ..obs import decision as _decision

DIGEST_LEN = 16

# Reject reason classes whose verdict depends only on the token bytes
# and the installed key tables — safe to replay until the epoch moves.
CACHEABLE_REJECTS = frozenset({
    _decision.REASON_BAD_SIGNATURE,
    _decision.REASON_MALFORMED,
    _decision.REASON_NOT_SIGNED,
})

_MISS = object()


def token_digest(token: Any) -> bytes:
    """The cache key: sha256 of the token's UTF-8 bytes, truncated to
    :data:`DIGEST_LEN`. The native serve chain computes the identical
    digest in its reader threads (serve_native.cpp) so the Python
    drain does zero hashing on that chain."""
    if isinstance(token, str):
        token = token.encode("utf-8", "surrogatepass")
    return hashlib.sha256(token).digest()[:DIGEST_LEN]


def _claims_exp_nbf(verdict: Any, token: Any) -> Tuple[Optional[float],
                                                       Optional[float]]:
    """(exp, nbf) for the clamp, best-effort: from the accept's claims
    (dict or raw payload bytes), else from the token's payload
    segment. Unparseable → (None, None): the TTL bound still applies."""
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
            except (ValueError, binascii.Error, UnicodeDecodeError):
                claims = None
    if not isinstance(claims, dict):
        return (None, None)
    return (_num(claims.get("exp")), _num(claims.get("nbf")))


def _num(v: Any) -> Optional[float]:
    # float() of an int too large for a double raises OverflowError
    return float(v) if isinstance(v, (int, float)) \
        and not isinstance(v, bool) else None


def _native_exp_nbf():
    """The native exp/nbf scan over raw payloads
    (``native_binding.exp_nbf_from_payloads``), or None where the
    native runtime is unbuilt."""
    from ..runtime.prep import _load_native

    native = _load_native()
    return None if native is None else native.exp_nbf_from_payloads


_first_byte = itemgetter(0)


def _take(seq: Sequence[Any], idx: List[int]) -> tuple:
    """``seq[i] for i in idx`` as a tuple, at C speed."""
    if len(idx) < 2:
        return tuple(seq[i] for i in idx)
    return itemgetter(*idx)(seq)


# Cache entries are plain tuples — the lookup hot loop indexes them
# without attribute-load overhead:
#   (verdict, valid_from, valid_until, epoch, exp)
# valid_from = nbf (0.0 when absent); valid_until = min(insert-time +
# max_ttl, exp) — the exp and TTL clamps collapse into ONE compare.
_E_VERDICT, _E_FROM, _E_UNTIL, _E_EPOCH, _E_EXP = range(5)


class VerdictCache:
    """Sharded bounded token-digest → verdict map with epoch/exp/nbf
    clamps. Thread-safe; every public entry point may be called from
    any serve/drain/client thread."""

    def __init__(self, capacity: int = 65536, shards: int = 16,
                 max_ttl_s: float = 300.0):
        # power-of-two shard count so digest[0] masks cleanly
        n = 1
        while n < max(1, shards):
            n <<= 1
        self._n_shards = n
        self._cap_per_shard = max(1, capacity // n)
        self._shards: List[Dict[bytes, _Entry]] = [{} for _ in range(n)]
        self._locks = [threading.Lock() for _ in range(n)]
        self._max_ttl = float(max_ttl_s)
        # epoch state: entries tagged `epoch` serve while it is the
        # current epoch, or while it is the PREVIOUS epoch inside the
        # grace window of the last bump. Anything older is invalid.
        self._epoch_lock = threading.Lock()
        self._epoch: Optional[int] = None
        self._prev_epoch: Optional[int] = None
        self._grace_until = 0.0
        # counter staging: folded into the active telemetry recorder
        # in one count_many round per batched operation
        self._ctr_lock = threading.Lock()
        self._ctr = {"vcache.lookups": 0, "vcache.hits": 0,
                     "vcache.misses": 0, "vcache.inserts": 0,
                     "vcache.insert_skips": 0, "vcache.evictions": 0,
                     "vcache.fill_fallbacks": 0,
                     "vcache.epoch_bumps": 0, "vcache.clamp_drops": 0,
                     "vcache.stale_accepts": 0,
                     "vcache.peer_fills": 0,
                     "vcache.peer_fill_skips": 0,
                     "vcache.peer_exports": 0}

    # -- epoch / invalidation ---------------------------------------------

    @property
    def epoch(self) -> Optional[int]:
        return self._epoch

    def set_epoch(self, epoch: Optional[int]) -> None:
        """Initial epoch install (construction time): no bump
        accounting, no grace — the cache is empty anyway."""
        with self._epoch_lock:
            self._epoch = epoch
            self._prev_epoch = None
            self._grace_until = 0.0

    def bump_epoch(self, epoch: Optional[int],
                   grace_s: float = 0.0) -> None:
        """Atomic invalidation on key rotation: entries verified under
        the (now previous) epoch stay valid for ``grace_s`` seconds,
        then die; entries from any older epoch are invalid at once.
        A no-op when the epoch is unchanged (re-pushes must not churn
        the cache)."""
        with self._epoch_lock:
            if epoch == self._epoch:
                return
            self._prev_epoch = self._epoch
            self._epoch = epoch
            self._grace_until = time.time() + max(0.0, grace_s)
        self._count({"vcache.epoch_bumps": 1})

    def _epoch_valid(self, entry_epoch: Optional[int],
                     now: float) -> bool:
        # unlocked read of the trio: a racing bump makes the check
        # CONSERVATIVE at worst (a just-valid entry misses)
        if entry_epoch == self._epoch:
            return True
        return (entry_epoch == self._prev_epoch
                and entry_epoch is not None
                and now < self._grace_until)

    # -- lookup -----------------------------------------------------------

    def _valid(self, e: tuple, now: float) -> bool:
        return (e[_E_FROM] <= now < e[_E_UNTIL]
                and self._epoch_valid(e[_E_EPOCH], now))

    def get(self, digest: bytes, now: Optional[float] = None) -> Any:
        """The verdict for one digest, or the module's miss sentinel
        (compare with ``vcache.MISS``). Single-key form of
        :meth:`lookup_batch` — counts exactly the same way."""
        hit = self._get_nocount(digest, now)
        self._count({"vcache.lookups": 1,
                     "vcache.hits": 0 if hit is _MISS else 1,
                     "vcache.misses": 1 if hit is _MISS else 0})
        return hit

    def _get_nocount(self, digest: bytes,
                     now: Optional[float] = None) -> Any:
        if now is None:
            now = time.time()
        s = digest[0] & (self._n_shards - 1)
        e = self._shards[s].get(digest)
        if e is None:
            return _MISS
        if not self._valid(e, now):
            with self._locks[s]:
                self._shards[s].pop(digest, None)
            self._stage("vcache.clamp_drops", 1)
            return _MISS
        verdict = e[_E_VERDICT]
        # serve-time tripwire: re-validate against a FRESH clock read
        # before the verdict leaves the cache — an accept that expired
        # between check and serve is dropped and counted, never served
        # (vcache.stale_accepts is SLO-pinned to 0).
        if not isinstance(verdict, BaseException) \
                and not self._valid(e, time.time()):
            self._stage("vcache.stale_accepts", 1)
            return _MISS
        return verdict

    def lookup_batch(self, tokens: Sequence[Any],
                     digests: Optional[Sequence[Optional[bytes]]] = None
                     ) -> Tuple[List[Any], List[int], List[bytes]]:
        """Consult the cache for a whole batch in one pass.

        Returns ``(results, miss_idx, digests)``: ``results`` has the
        cached verdict at hit positions and ``None`` at misses,
        ``miss_idx`` lists the miss positions (submit exactly these to
        the engine), ``digests`` the per-token digest (computed here
        unless the caller supplies them, e.g. from the native reader
        threads). One counter fold for the whole batch."""
        now = time.time()
        n = len(tokens)
        out: List[Any] = [None] * n
        miss_idx: List[int] = []
        digs: List[bytes] = [b""] * n
        # inlined hot loop (no per-token function calls): validity =
        # TTL deadline ∧ epoch/grace ∧ exp ∧ nbf, all against one
        # clock read; epoch trio snapshotted unlocked (a racing bump
        # makes the check conservative at worst)
        shards = self._shards
        locks = self._locks
        mask = self._n_shards - 1
        cur, prev, guntil = self._epoch, self._prev_epoch, \
            self._grace_until
        hits = 0
        drops = 0
        hit_entries: List[tuple] = []
        for i in range(n):
            d = digests[i] if digests is not None else None
            if not d:
                d = token_digest(tokens[i])
            digs[i] = d
            # unlocked read (GIL-atomic, same stance as the decision
            # header cache); the lock is taken only to delete
            e = shards[d[0] & mask].get(d)
            if e is not None:
                ep = e[3]
                if e[1] <= now < e[2] and (
                        ep == cur or (ep == prev and ep is not None
                                      and now < guntil)):
                    out[i] = e[0]
                    hits += 1
                    hit_entries.append((i, e))
                    continue
                s = d[0] & mask
                with locks[s]:
                    shards[s].pop(d, None)
                drops += 1
            miss_idx.append(i)
        # serve-time tripwire: ONE fresh clock read for the batch; an
        # accept whose exp crossed between check and serve is demoted
        # to a miss and counted (vcache.stale_accepts, SLO-pinned 0).
        stale = 0
        if hit_entries:
            now2 = time.time()
            for i, e in hit_entries:
                exp = e[4]
                if exp is not None and now2 >= exp:
                    out[i] = None
                    miss_idx.append(i)
                    hits -= 1
                    stale += 1
            if stale:
                miss_idx.sort()
        self._count({"vcache.lookups": n, "vcache.hits": hits,
                     "vcache.misses": n - hits,
                     "vcache.clamp_drops": drops,
                     "vcache.stale_accepts": stale})
        return out, miss_idx, digs

    # -- insert -----------------------------------------------------------

    def cacheable(self, verdict: Any) -> bool:
        """Whether a verdict may be cached at all: accepts always,
        rejects only for :data:`CACHEABLE_REJECTS` reason classes."""
        if isinstance(verdict, BaseException):
            return _decision.classify(verdict) in CACHEABLE_REJECTS
        return True

    def _clamp_inputs(self, verdicts: Sequence[Any],
                      tokens: Optional[Sequence[Any]]) -> tuple:
        """Per verdict: ``exp``/``nbf`` (float64 arrays, with
        ``has_exp``/``has_nbf`` saying which are present) and ``keep``
        (cacheable, and no exp/nbf too large for a float); plus how
        many verdicts took the per-entry reader.

        Raw-payload (``bytes``) verdicts are read by one native scan;
        what it declines, and any other verdict type, goes through
        :func:`_claims_exp_nbf` one by one — all of them where the
        native runtime is unbuilt. Dicts are read directly, and
        exceptions carry no exp/nbf."""
        n = len(verdicts)
        payloads = [v if type(v) is bytes else b"" for v in verdicts]
        scan = _native_exp_nbf() if any(payloads) else None
        found = None if scan is None else scan(payloads)
        if found is None:
            exp, nbf = np.full(n, np.nan), np.full(n, np.nan)
            redo = range(n)
        else:
            exp, nbf, status = found
            redo = np.flatnonzero(status).tolist()
        has_exp, has_nbf = ~np.isnan(exp), ~np.isnan(nbf)
        keep = np.ones(n, bool)
        fallbacks = 0
        for i in redo:
            v = verdicts[i]
            if isinstance(v, BaseException):
                keep[i] = self.cacheable(v)
                continue
            if not isinstance(v, dict):
                fallbacks += 1
            try:
                e, f = _claims_exp_nbf(
                    v, tokens[i] if tokens is not None else None)
            except OverflowError:
                keep[i] = False     # served, just not cached
                continue
            has_exp[i], has_nbf[i] = e is not None, f is not None
            exp[i] = np.nan if e is None else e
            nbf[i] = np.nan if f is None else f
        return exp, has_exp, nbf, has_nbf, keep, fallbacks

    def insert(self, digest: bytes, verdict: Any, token: Any = None,
               epoch: Optional[int] = None,
               now: Optional[float] = None) -> bool:
        """Insert one verdict; returns False (counted as a skip) when
        the verdict class is uncacheable, the entry is already expired,
        or ``epoch`` no longer matches the cache epoch (the verify
        raced a rotation — conservative drop). A one-entry
        :meth:`insert_batch`."""
        return self.insert_batch([digest], [verdict],
                                 None if token is None else [token],
                                 epoch=epoch, now=now) == 1

    def insert_batch(self, digests: Sequence[bytes],
                     verdicts: Sequence[Any],
                     tokens: Optional[Sequence[Any]] = None,
                     epoch: Optional[int] = None,
                     now: Optional[float] = None) -> int:
        """Insert a batch of fresh verdicts; returns how many were
        cached. Each entry is judged as :meth:`insert` judges one —
        uncacheable, already expired, or (for the whole batch) an
        ``epoch`` that no longer matches is a skip — and an ``exp`` or
        ``nbf`` too large for a float skips that entry too.

        One clock read, one epoch check, one native exp/nbf scan over
        the raw-payload verdicts, each shard lock taken once, and one
        counter fold per batch."""
        n = len(digests)
        if not n:
            return 0
        if now is None:
            now = time.time()
        if epoch != self._epoch:
            self._count({"vcache.insert_skips": n})
            return 0
        exp, has_exp, nbf, has_nbf, keep, fallbacks = self._clamp_inputs(
            verdicts, tokens)
        # skip where now >= exp: NaN (absent) compares False, as a
        # skipped `exp is not None` test does
        keep &= ~(exp <= now)
        sel = np.flatnonzero(keep)
        evicted = 0
        if len(sel):
            # valid_until = min(now + max_ttl, exp); fmin ignores NaN
            until = np.fmin(exp, now + self._max_ttl)
            valid_from = np.where(has_nbf, nbf, 0.0)
            if len(sel) < n:
                idx = sel.tolist()
                digests, verdicts = _take(digests, idx), _take(verdicts, idx)
                until, valid_from = until[sel], valid_from[sel]
                exp, has_exp = exp[sel], has_exp[sel]
            exps = exp.tolist()
            for i in np.flatnonzero(~has_exp).tolist():
                exps[i] = None
            evicted = self._fill(digests, list(zip(
                verdicts, valid_from.tolist(), until.tolist(),
                repeat(epoch), exps)))
        self._count({"vcache.inserts": len(sel),
                     "vcache.insert_skips": n - len(sel),
                     "vcache.evictions": evicted,
                     "vcache.fill_fallbacks": fallbacks})
        return len(sel)

    def _fill(self, digests: Sequence[bytes],
              entries: Sequence[tuple]) -> int:
        """Install ``entries`` under ``digests`` in order, grouped by
        shard (each lock taken once); returns the evictions."""
        mask = self._n_shards - 1
        shard_of = np.fromiter(map(_first_byte, digests), np.intp,
                               count=len(digests)) & mask
        order = np.argsort(shard_of, kind="stable")
        bounds = np.searchsorted(shard_of[order],
                                 np.arange(self._n_shards + 1)).tolist()
        idx = order.tolist()
        digests, entries = _take(digests, idx), _take(entries, idx)
        evicted = 0
        for s in range(self._n_shards):
            lo, hi = bounds[s], bounds[s + 1]
            if lo < hi:
                with self._locks[s]:
                    evicted += self._fill_shard(
                        self._shards[s], digests[lo:hi], entries[lo:hi])
        return evicted

    def _fill_shard(self, shard: Dict[bytes, tuple],
                    digests: Sequence[bytes],
                    entries: Sequence[tuple]) -> int:
        """Exactly what inserting one entry at a time does: a digest
        already cached keeps its place and takes the new entry; a new
        one into a full shard evicts the oldest inserted. Returns the
        evictions. Caller holds the shard's lock.

        ``update`` alone is that, up to capacity: each digest's last
        entry, cached ones in place, new ones appended in order."""
        cap = self._cap_per_shard
        before = len(shard)
        shard.update(zip(digests, entries))
        over = len(shard) - cap
        if over <= 0:
            return 0
        if len(shard) - before == len(digests):
            # all new and distinct: the oldest `over` go, in one pass
            # (it walks the dict's deleted slots once per batch)
            for d in list(islice(shard, over)):
                del shard[d]
            return over
        # repeats, or digests cached already, while evicting: one at a
        # time, a digest evicted before it comes again is new again.
        # Replay that order on the keys (the first `before` are the
        # shard's old order), then rebuild the shard in it.
        order = deque(islice(shard, before))
        live = set(order)
        evicted = 0
        for d in digests:
            if d not in live:
                if len(live) >= cap:
                    live.discard(order.popleft())
                    evicted += 1
                order.append(d)
                live.add(d)
        kept = [(d, shard[d]) for d in order]
        shard.clear()
        shard.update(kept)
        return evicted

    # -- peer fill (fleet cache warming, CVB1 frame pair 13/14) -----------

    def export_entries(self, max_entries: int = 2048,
                       max_bytes: int = 768 * 1024
                       ) -> Tuple[List[list], Optional[int]]:
        """Dump currently-valid ACCEPT entries for a peer-fill
        transfer → (entries, epoch).

        Each entry is ``[digest_hex, payload_b64, valid_from,
        valid_until, exp_or_null]``. Accepts only: rejects are cheap
        to re-verify and their exception classes don't round-trip
        bit-exactly. Entries from a previous epoch (grace residue)
        are skipped — an export carries exactly ONE epoch, the
        current one, so the importer's clamp is a single equality.
        ``max_bytes`` approximates the wire bound so the frame can
        never exceed ``protocol.MAX_ENTRY_BYTES``."""
        now = time.time()
        epoch = self._epoch
        out: List[list] = []
        size = 0
        for shard in self._shards:
            if len(out) >= max_entries or size >= max_bytes:
                break
            # snapshot the dict (GIL-atomic list()) — exports race
            # inserts harmlessly; we only need a consistent-ish slice
            for digest, e in list(shard.items()):
                if len(out) >= max_entries or size >= max_bytes:
                    break
                verdict = e[_E_VERDICT]
                if isinstance(verdict, BaseException):
                    continue
                if e[_E_EPOCH] != epoch or not (e[_E_FROM] <= now
                                                < e[_E_UNTIL]):
                    continue
                if isinstance(verdict, (bytes, bytearray, memoryview)):
                    payload = bytes(verdict)
                else:
                    # exactly protocol._response_parts' encoding, so
                    # an imported hit is byte-identical on the wire
                    payload = json.dumps(
                        verdict, separators=(",", ":")).encode()
                row = [digest.hex(),
                       base64.b64encode(payload).decode("ascii"),
                       e[_E_FROM], e[_E_UNTIL], e[_E_EXP]]
                size += len(payload) + len(row[0]) + 48
                out.append(row)
        self._count({"vcache.peer_exports": len(out)})
        return out, epoch

    def import_entries(self, entries: Sequence[Sequence[Any]],
                       epoch: Any) -> int:
        """Install a peer's export, under the SAME clamps a local
        insert gets — warming can never extend a verdict's validity:

        - ``epoch`` must equal the cache's CURRENT epoch (a transfer
          racing a rotation is dropped whole — conservative);
        - per entry, ``valid_until`` is re-bounded by this cache's own
          ``now + max_ttl`` (min, never max) and already-expired or
          not-yet-valid windows are skipped;
        - the serve-time stale-accept tripwire applies to imported
          entries exactly as to local ones (they are ordinary entries).

        Returns how many entries were installed
        (``vcache.peer_fills``); clamped drops count
        ``vcache.peer_fill_skips``."""
        now = time.time()
        if epoch != self._epoch:
            self._count({"vcache.peer_fill_skips": len(entries)})
            return 0
        digests: List[bytes] = []
        rows: List[tuple] = []
        for row in entries:
            try:
                digest = bytes.fromhex(row[0])
                payload = base64.b64decode(row[1])
                valid_from = float(row[2])
                valid_until = float(row[3])
                exp = float(row[4]) if row[4] is not None else None
            except (ValueError, TypeError, IndexError,
                    binascii.Error):
                continue
            until = min(valid_until, now + self._max_ttl)
            if exp is not None:
                until = min(until, exp)
            if len(digest) != DIGEST_LEN or now >= until:
                continue
            digests.append(digest)
            rows.append((payload, valid_from, until, epoch, exp))
        # re-check the epoch after the parse: a rotation landing
        # mid-import drops the whole transfer, not just the next lookup
        if epoch != self._epoch:
            digests, rows = [], []
        evicted = self._fill(digests, rows) if digests else 0
        self._count({"vcache.peer_fills": len(digests),
                     "vcache.peer_fill_skips": len(entries) - len(digests),
                     "vcache.evictions": evicted})
        return len(digests)

    # -- stats ------------------------------------------------------------

    def size(self) -> int:
        return sum(len(s) for s in self._shards)

    def stats(self) -> Dict[str, int]:
        """Lifetime counters (also folded into the telemetry recorder
        under the same names) plus the live size."""
        with self._ctr_lock:
            out = dict(self._ctr)
        out["vcache.size"] = self.size()
        return out

    def clear(self) -> None:
        for lock, shard in zip(self._locks, self._shards):
            with lock:
                shard.clear()

    # -- counter plumbing -------------------------------------------------

    def _stage(self, name: str, n: int) -> None:
        if not n:
            return
        with self._ctr_lock:
            self._ctr[name] += n
        telemetry.count(name, n)

    def _count(self, increments: Dict[str, int]) -> None:
        inc = {k: v for k, v in increments.items() if v}
        if not inc:
            return
        with self._ctr_lock:
            for k, v in inc.items():
                self._ctr[k] += v
        rec = telemetry.active()
        if rec is not None:
            rec.count_many(inc)


MISS = _MISS


def enabled_from_env(default: bool = True) -> bool:
    """The documented graceful-off switch: ``CAP_SERVE_VCACHE=0``
    disables the whole tier (worker caches, native digest handoff,
    batcher in-flight dedup stays separately controllable)."""
    import os

    v = os.environ.get("CAP_SERVE_VCACHE")
    if v is None:
        return default
    return v != "0"
