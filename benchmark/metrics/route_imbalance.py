"""How unevenly the front door spread the window's tokens over its
pools: the largest Δ``frontdoor.pool.<id>.tokens`` over their mean (1
is even; the slowest worker sets each request's pace). None where the
router has no such counters."""


def read(ctx):
    per = [v for k, v in ctx["counters"].items()
           if k.startswith("frontdoor.pool.") and k.endswith(".tokens")]
    if not per or sum(per) <= 0:
        return None
    return max(per) * len(per) / sum(per)
