"""The readers of the program's own spans and counters, on synthetic
window contexts (``run.layer_ctx``'s shape).

    python3 -m pytest benchmark/tests/test_program_span_readers.py -q
"""

from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from lib import spec  # noqa: E402

NEW = ("pack_us_per_tok", "launch_us_per_tok", "verdict_us_per_tok",
       "serve_us_per_tok", "dispatch_cpu_share", "programs_built")


def _s(total: float, count: int = 4) -> dict:
    return {"count": count, "sum": total, "buckets": {}}


def window() -> dict:
    """A window of 8,192 keyset tokens with every span of the served
    path, as the program with its host stages split reports it."""
    return {
        "counters": {"verify_batch.tokens": 8192, "worker.tokens": 8192,
                     "compile.programs": 2},
        "series": {
            "keyset.pack": _s(0.004096),
            "keyset.launch": _s(0.008192),
            "keyset.finish": _s(0.012288),
            "keyset.decisions": _s(0.004096),
            "serve.native.drain": _s(0.002048),
            "vcache.lookup": _s(0.001024),
            "vcache.insert": _s(0.002048),
            "serve.native.post": _s(0.003072),
            "batcher.handoff": _s(0.006),
            "batcher.dispatch": _s(0.050),
            "batcher.dispatch.cpu": _s(0.030),
        },
        "trace": {}, "window_s": 1.0}


def test_readers_on_a_full_window():
    ctx = window()
    want = {"pack_us_per_tok": 0.5, "launch_us_per_tok": 1.0,
            "verdict_us_per_tok": 2.0, "serve_us_per_tok": 1.0,
            "dispatch_cpu_share": 60.0, "programs_built": 2}
    for name, value in want.items():
        assert spec.reader(name)(ctx) == pytest.approx(value), name


@pytest.mark.parametrize("name", NEW)
def test_nothing_without_keyset_tokens(name):
    ctx = window()
    ctx["counters"]["verify_batch.tokens"] = 0
    assert spec.reader(name)(ctx) is None


@pytest.mark.parametrize("name", NEW)
def test_nothing_from_a_program_without_the_spans(name):
    """The parent of the split: ``serve.native.*``, ``batcher.*`` and
    the keyset's token counter, but none of the new spans, no CPU
    series and no compile counter."""
    ctx = window()
    for k in ("keyset.pack", "keyset.launch", "keyset.finish",
              "keyset.decisions", "vcache.lookup", "vcache.insert",
              "batcher.handoff", "batcher.dispatch.cpu"):
        del ctx["series"][k]
    del ctx["counters"]["compile.programs"]
    assert spec.reader(name)(ctx) is None


def test_no_build_in_the_window_reads_zero():
    ctx = window()
    ctx["counters"]["compile.programs"] = 0
    assert spec.reader("programs_built")(ctx) == 0


def test_serve_reads_without_the_cache():
    ctx = window()
    del ctx["series"]["vcache.lookup"], ctx["series"]["vcache.insert"]
    assert spec.reader("serve_us_per_tok")(ctx) == pytest.approx(0.625)


def test_a_span_that_did_not_run_in_the_window_is_missing():
    ctx = window()
    ctx["series"]["keyset.pack"] = _s(0.0, count=0)
    assert spec.reader("pack_us_per_tok")(ctx) is None
