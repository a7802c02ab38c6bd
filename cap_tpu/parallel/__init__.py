"""Multi-chip execution: device meshes + sharded batch verification.

The reference has no distributed execution of any kind (SURVEY.md §2.6);
this package is the TPU-native fill-in. The parallelism axes for a
batched-verify workload:

- ``dp`` — data parallelism over the token batch: each chip verifies a
  shard of the tokens. The analog of DP in an ML framework; tokens are
  independent, so this scales linearly over ICI with zero cross-chip
  traffic in the hot loop.
- key-gather — the EP-analog (SURVEY.md §2.6): per-token kid indices
  gather rows from the key table. Tables are small (a JWKS is ~16
  keys), so they are replicated per chip and the gather stays local;
  the collective cost is one broadcast at table-build time.

Verdict reduction (count of valid tokens) rides a ``psum`` over ``dp``.
"""

# Lazy: ``parallel.place`` (fleet placement) must import without jax —
# a fleet parent that imported it would be one step from holding the
# chip its workers need.
_MESH_EXPORTS = ("make_mesh", "sharded_rs256_verify", "sharded_verify_step")


def __getattr__(name):
    if name in _MESH_EXPORTS:
        from . import mesh

        return getattr(mesh, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
