"""Host time per token in the window turning the device's verdict bits
into per-token answers, in us: the ``keyset.finish`` and
``keyset.decisions`` spans' seconds over the tokens handed to the
keyset (``verify_batch.tokens``)."""

from lib import spans


def read(ctx):
    finish = spans.seconds(ctx, "keyset.finish")
    n = spans.keyset_tokens(ctx)
    if finish is None or n <= 0:
        return None
    decisions = spans.seconds(ctx, "keyset.decisions") or 0.0
    return (finish + decisions) * 1e6 / n
