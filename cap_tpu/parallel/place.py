"""Mesh placement helpers for the packed verify programs.

The packed dispatch functions (rsa/ec/ed25519 ``verify_*_packed_pending``)
take every device table as an explicit argument, so multi-chip execution
needs exactly two placements (SURVEY.md §2.6 "sharded bignum kernels"):

- the packed record matrix sharded along the batch axis
  (``PartitionSpec(axis, None)``) — token data parallelism over ICI;
- the key/window tables replicated (``PartitionSpec()``) — the key
  gather then runs locally on every shard.

XLA's GSPMD propagation partitions the whole verify program from those
input shardings; the jit-captured RNS context constants replicate
automatically. Validated on the virtual 8-device CPU mesh by
tests/test_parallel.py and the driver's dryrun_multichip.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from .. import telemetry


class PlacementError(ValueError):
    """A fleet placement violates the single-owner-per-device model."""


@dataclass(frozen=True)
class WorkerPlacement:
    """One worker process's exclusive device group.

    The serve-fleet placement model (VERDICT r5: the serve projection
    silently assumed two processes can share one TPU chip — they
    generally cannot): every device belongs to EXACTLY ONE worker
    process, expressed as subprocess environment rather than runtime
    cooperation, so ownership is enforced by process isolation:

    - ``platform="tpu"``: the child sees only its chips and is its
      own one-process slice — ``TPU_VISIBLE_CHIPS`` names the chips,
      ``TPU_CHIPS_PER_PROCESS_BOUNDS``/``TPU_PROCESS_BOUNDS`` make the
      group a whole slice (which is also what lets several libtpu
      loads share one host), and ``TPU_PROCESS_PORT``/
      ``TPU_PROCESS_ADDRESSES`` give each process its own runtime
      port (libtpu refuses a chip another process holds — the
      single-owner invariant is also enforced by the hardware
      runtime);
    - ``platform="cpu"`` (this container, tests, dry-runs): the child
      gets its OWN virtual-device world (``JAX_PLATFORMS=cpu`` plus a
      device count); CPU "devices" are process-local threads, so
      disjointness across children holds by construction.
    """

    worker_id: int
    device_ids: Tuple[int, ...]
    platform: str = "cpu"

    def env(self) -> Dict[str, str]:
        """Environment overrides for the worker subprocess."""
        ids = ",".join(str(d) for d in self.device_ids)
        out = {"CAP_FLEET_WORKER_ID": str(self.worker_id),
               "CAP_FLEET_DEVICE_GROUP": ids}
        if self.platform == "tpu":
            port = str(_TPU_PORT_BASE + self.worker_id)
            out.update({
                "JAX_PLATFORMS": "tpu",
                "TPU_VISIBLE_CHIPS": ids,
                "TPU_CHIPS_PER_PROCESS_BOUNDS": _chip_bounds(
                    len(self.device_ids)),
                "TPU_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_PORT": port,
                "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
            })
        else:
            out["JAX_PLATFORMS"] = "cpu"
            out["CAP_FLEET_CPU_DEVICES"] = str(len(self.device_ids))
        return out


# Per-worker libtpu runtime port: base + worker id keeps the ports of
# one host's one-chip processes distinct.
_TPU_PORT_BASE = 8476


def _chip_bounds(n_chips: int) -> str:
    """TPU_CHIPS_PER_PROCESS_BOUNDS for a group of ``n_chips`` chips of
    a v5e host (2x2 chips)."""
    bounds = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}
    if n_chips not in bounds:
        raise PlacementError(f"no TPU chip bounds for {n_chips} chips")
    return bounds[n_chips]


def single_owner_placement(n_workers: int, n_devices: int,
                           platform: str = "cpu",
                           devices_per_worker: Optional[int] = None,
                           ) -> List[WorkerPlacement]:
    """Partition ``n_devices`` into disjoint contiguous groups, one per
    worker — no device is ever assigned twice (chip sharing between
    processes is the failure mode this model exists to forbid).

    ``devices_per_worker`` defaults to an even split; the placement is
    rejected (:class:`PlacementError`) if it would overcommit.
    """
    if n_workers < 1:
        raise PlacementError(f"need at least one worker, got {n_workers}")
    if devices_per_worker is None:
        devices_per_worker = n_devices // n_workers
    if devices_per_worker < 1:
        raise PlacementError(
            f"{n_workers} workers over {n_devices} devices leaves some "
            "worker with no device (single-owner placement cannot share)")
    if n_workers * devices_per_worker > n_devices:
        raise PlacementError(
            f"{n_workers} workers x {devices_per_worker} devices = "
            f"{n_workers * devices_per_worker} > {n_devices} available: "
            "refusing to double-book a device")
    placements = [
        WorkerPlacement(
            worker_id=w,
            device_ids=tuple(range(w * devices_per_worker,
                                   (w + 1) * devices_per_worker)),
            platform=platform)
        for w in range(n_workers)
    ]
    assert_single_owner(placements)
    return placements


def assert_single_owner(placements: List[WorkerPlacement]) -> None:
    """Raise :class:`PlacementError` if any device has two owners."""
    owner: Dict[int, int] = {}
    for p in placements:
        for d in p.device_ids:
            if d in owner:
                raise PlacementError(
                    f"device {d} owned by both worker {owner[d]} and "
                    f"worker {p.worker_id}")
            owner[d] = p.worker_id

# (id(mesh), id(arr)) → (mesh, arr, replicated). The STRONG refs to the
# keying objects make id-aliasing impossible while an entry lives (a
# rebuilt key table can never be served another table's replicated
# copy), and the LRU bound keeps dropped keysets from pinning device
# buffers forever.
_replicated_cache: "OrderedDict[Tuple[int, int], Any]" = OrderedDict()
# Bounded by approximate BYTES, not entry count: individual tables
# range from a few KB to ~130 MB (12-bit EC windows), so a count bound
# either evicts a live working set or pins GBs of dropped keysets'
# buffers. The bound must comfortably exceed the combined working set
# of the live keysets or every batch silently re-broadcasts its tables
# across the mesh; 1 GiB covers dozens of keysets at default window
# sizes while capping the HBM a rotation churn can pin. Raise via
# CAP_TPU_REPLICATED_CACHE_MB for many live keysets with large (12-bit)
# windows; the `parallel.replicated_evictions` telemetry counter ticking
# steadily under load is the thrash signal to watch.
_REPLICATED_CACHE_MAX_BYTES = int(os.environ.get(
    "CAP_TPU_REPLICATED_CACHE_MB", str(1 << 10))) << 20
_replicated_cache_bytes = 0
# replicated() is called concurrently (serve dispatcher + user threads
# on the same mesh); the byte counter is read-modify-write state, so
# all cache mutations happen under this lock.
_cache_lock = threading.Lock()


def _entry_nbytes(arr) -> int:
    return int(getattr(arr, "nbytes", 0) or 0)


def batch_axis(mesh) -> str:
    """The mesh axis the batch shards over (its first axis)."""
    return mesh.axis_names[0]


def shard_batch(mesh, arr):
    """Place a host array sharded along axis 0 of the mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    spec = PartitionSpec(batch_axis(mesh), *([None] * (arr.ndim - 1)))
    return jax.device_put(arr, NamedSharding(mesh, spec))


_sharded_jit = None


def _sharded_body(rec, *tables, impl, mesh, static):
    from functools import partial

    import jax
    from jax.sharding import PartitionSpec

    ax = batch_axis(mesh)
    return jax.shard_map(
        partial(impl, **dict(static)), mesh=mesh,
        in_specs=(PartitionSpec(ax),) + (PartitionSpec(),) * len(tables),
        out_specs=PartitionSpec(ax), check_vma=False)(rec, *tables)


def run_batch_sharded(impl, mesh, rec, tables, static: dict):
    """``impl(rec, *tables, **static)`` over a mesh: the record split
    along the batch axis, the tables replicated, and the per-token
    program run by every device on its own rows (``shard_map``).

    GSPMD cannot partition the Pallas kernels inside the verify
    programs ("Mosaic kernels cannot be automatically partitioned"),
    and need not: tokens are independent, so each device's shard is a
    complete small batch. Outputs are per-token arrays (or tuples of
    them), sharded the same way.
    """
    global _sharded_jit
    import jax

    if _sharded_jit is None:
        _sharded_jit = jax.jit(_sharded_body,
                               static_argnames=("impl", "mesh", "static"))
    tables = jax.tree_util.tree_map(lambda a: replicated(mesh, a),
                                    tuple(tables))
    return _sharded_jit(shard_batch(mesh, rec), *tables, impl=impl,
                        mesh=mesh, static=tuple(sorted(static.items())))


def replicated(mesh, arr):
    """Mesh-replicated copy of a device array, cached per (mesh, array).

    The cache holds strong references to the mesh and source array, so
    entries can never be aliased by id reuse after garbage collection;
    an LRU bounded by approximate bytes evicts replicated buffers of
    dropped keysets without pinning GBs of HBM under keyset rotation.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    global _replicated_cache_bytes
    key = (id(mesh), id(arr))
    with _cache_lock:
        hit = _replicated_cache.get(key)
        if hit is not None:
            _replicated_cache.move_to_end(key)
            return hit[2]
    out = jax.device_put(arr, NamedSharding(mesh, PartitionSpec()))
    with _cache_lock:
        # A concurrent caller may have inserted the same key while we
        # were broadcasting — keep (and return) the first copy so every
        # shard keeps gathering from one buffer.
        hit = _replicated_cache.get(key)
        if hit is not None:
            _replicated_cache.move_to_end(key)
            return hit[2]
        _replicated_cache[key] = (mesh, arr, out)
        _replicated_cache_bytes += _entry_nbytes(arr)
        while (_replicated_cache_bytes > _REPLICATED_CACHE_MAX_BYTES
               and len(_replicated_cache) > 1):
            _, (_, old_arr, _) = _replicated_cache.popitem(last=False)
            _replicated_cache_bytes -= _entry_nbytes(old_arr)
            telemetry.count("parallel.replicated_evictions")
    return out
