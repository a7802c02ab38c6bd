"""Programs JAX built or loaded from its persistent cache in the
window, as the program counts them (``compile.programs``; its
``compile.fn.<name>`` counters name each one)."""

from lib import spans


def read(ctx):
    counters = ctx["counters"]
    if spans.keyset_tokens(ctx) <= 0 or "compile.programs" not in counters:
        return None
    return counters["compile.programs"]
