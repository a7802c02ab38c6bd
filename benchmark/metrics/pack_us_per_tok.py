"""Record packing time per token in the window, in us: the
``keyset.pack`` span's seconds (each chunk's device records, built on
the host) over the tokens handed to the keyset
(``verify_batch.tokens``)."""

from lib import spans


def read(ctx):
    return spans.us_per_keyset_token(ctx, "keyset.pack")
