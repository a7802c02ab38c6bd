"""The serve worker's Python per token served in the window, in us:
the native chain's ``serve.native.drain``, ``vcache.lookup``,
``vcache.insert`` and ``serve.native.post`` spans' seconds over
``worker.tokens``. Read only where the batcher's ``batcher.handoff``
span ran (a program without it has no ``vcache.*`` spans either, and
its sum would leave the cache out) and the keyset saw tokens."""

from lib import spans

PARTS = ("serve.native.drain", "vcache.lookup", "vcache.insert",
         "serve.native.post")


def read(ctx):
    n = ctx["counters"].get("worker.tokens", 0)
    if (n <= 0 or spans.keyset_tokens(ctx) <= 0
            or spans.seconds(ctx, "batcher.handoff") is None):
        return None
    return sum(spans.seconds(ctx, p) or 0.0 for p in PARTS) * 1e6 / n
