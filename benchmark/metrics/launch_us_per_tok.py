"""Launch time per token in the window, in us: the ``keyset.launch``
span's seconds (each chunk's H2D put and program enqueue, and the
verdict concatenation's) over the tokens handed to the keyset
(``verify_batch.tokens``)."""

from lib import spans


def read(ctx):
    return spans.us_per_keyset_token(ctx, "keyset.launch")
