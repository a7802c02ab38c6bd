"""What the per-layer readers take from the program's span series and
counters in the window (``run.layer_ctx``)."""

from __future__ import annotations

KEYSET_TOKENS = "verify_batch.tokens"


def seconds(ctx, name: str):
    """Seconds the ``name`` span took in the window, summed over its
    occurrences; None where it did not run (or the program has no such
    span)."""
    d = ctx["series"].get(name)
    if not d or d["count"] <= 0:
        return None
    return d["sum"]


def keyset_tokens(ctx) -> int:
    """Tokens handed to the keyset in the window."""
    return ctx["counters"].get(KEYSET_TOKENS, 0)


def us_per_keyset_token(ctx, name: str):
    """The ``name`` span's seconds per keyset token in the window, in
    us; None where either is missing."""
    s, n = seconds(ctx, name), keyset_tokens(ctx)
    if s is None or n <= 0:
        return None
    return s * 1e6 / n
