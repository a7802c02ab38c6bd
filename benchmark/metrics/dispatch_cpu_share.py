"""Share of the batcher dispatcher's wall time in which its thread ran,
in %: the thread CPU seconds inside ``batcher.dispatch``
(``batcher.dispatch.cpu``) over that span's seconds. The rest it
waited: for the GIL, or in a blocking call."""

from lib import spans


def read(ctx):
    wall = spans.seconds(ctx, "batcher.dispatch")
    cpu = spans.seconds(ctx, "batcher.dispatch.cpu")
    if spans.keyset_tokens(ctx) <= 0 or not wall or cpu is None:
        return None
    return 100.0 * cpu / wall
