"""Device-mesh parallelism: data-parallel batch sharding, policy sharding,
and the ICI collectives that aggregate verdicts/metrics.

The reference is a single-node thread-parallel server whose only scale-out
is an HTTP load balancer over replicas (SURVEY.md §2.3 last row). The
TPU-native design replaces that with sharding over a ``jax.sharding.Mesh``:

* ``data`` axis — requests (the batch dimension) shard across chips; XLA
  partitions the fused predicate program, elementwise work scales linearly
  and no collective is needed for the verdicts themselves.
* ``policy`` axis — large policy sets split into shards. Round 14: the
  serving form is ONE jit-compiled SPMD program over the full 2-D mesh —
  each policy shard's predicate block is a ``lax.switch`` branch selected
  by ``lax.axis_index("policy")`` inside a ``shard_map``, verdict blocks
  meet in an ``all_gather`` collective over the policy axis, and the
  group/expression combine runs on data-sharded rows with a
  ``with_sharding_constraint``. XLA overlaps the cross-shard collectives
  the old host-side thread pool serialized (one device program per batch
  instead of one per policy shard). The legacy thread-per-shard MPMD
  dispatcher (``policy_sharded.py``) remains as the
  ``--mesh-dispatch threaded`` fallback.
* metrics reduction — per-policy acceptance counts are a ``psum`` over the
  data axis (``shard_map`` + ``lax.psum``), the collective the driver's
  multi-chip dry-run exercises end to end.

Multi-host: ``jax.distributed.initialize`` + the same mesh spanning all
processes' devices (ICI within a slice, DCN across slices) — see
``initialize_distributed``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Mapping, Sequence

import jax
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from policy_server_tpu.config.config import MeshSpec

DATA_AXIS = "data"
POLICY_AXIS = "policy"


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Multi-host bring-up (jax.distributed over DCN). No-op when
    single-process args are absent. The CPU backend's cross-process
    collectives are gloo by default, so the 2-process localhost smoke
    needs no selection here."""
    if coordinator_address is None:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def resolve_axes(spec: MeshSpec, devices: Sequence[Any] | None = None) -> dict[str, int]:
    """Concretize a MeshSpec against the available devices (``data: 0`` =
    auto → all devices not consumed by the policy axis)."""
    devs = list(devices if devices is not None else jax.devices())
    policy = spec.policy_size()
    data = spec.data_size()
    if policy < 1 or len(devs) % policy != 0:
        raise ValueError(
            f"policy axis {policy} does not divide device count {len(devs)}"
        )
    if data == 0:  # auto
        data = len(devs) // policy
    if data * policy != len(devs):
        raise ValueError(
            f"mesh {data}x{policy} does not match device count {len(devs)}"
        )
    return {DATA_AXIS: data, POLICY_AXIS: policy}


def make_mesh(
    spec: MeshSpec | None = None, devices: Sequence[Any] | None = None
) -> Mesh:
    """Build the (data, policy) mesh.

    Single-process: axis order puts ``data`` innermost on the device
    list so batch shards ride the fastest ICI links. Multi-process
    (``jax.distributed``): ``data`` goes OUTERMOST instead — the global
    device list orders each process's devices contiguously, so an outer
    data axis splits the batch dimension ACROSS hosts (each host's
    frontend feeds host-local rows and fetches only its local verdicts)
    while the policy axis — and its all-gather collective — stays on
    each host's local links instead of crossing DCN per batch."""
    devs = np.array(list(devices if devices is not None else jax.devices()))
    axes = resolve_axes(spec or MeshSpec(), devs.tolist())
    if jax.process_count() > 1:
        # The host-local-rows contract requires every data row (one
        # batch shard = policy_axis consecutive global devices) to live
        # WITHIN one host: a row spanning hosts would make two processes
        # supply different local content for the same global batch
        # region (make_array_from_process_local_data then builds
        # silently divergent arrays). Fail fast instead.
        local = jax.local_device_count()
        policy = axes[POLICY_AXIS]
        if policy > local or local % policy != 0:
            raise ValueError(
                f"multi-process mesh: policy axis {policy} must divide "
                f"the per-host device count {local} (a data shard must "
                "be host-local; shrink the policy axis or use more "
                "devices per host)"
            )
        grid = devs.reshape(axes[DATA_AXIS], axes[POLICY_AXIS])
        return Mesh(grid, (DATA_AXIS, POLICY_AXIS))
    grid = devs.reshape(axes[POLICY_AXIS], axes[DATA_AXIS])
    return Mesh(grid, (POLICY_AXIS, DATA_AXIS))


@dataclass(frozen=True)
class SubmeshPlan:
    """One policy shard: the policy ids it evaluates and its data-parallel
    submesh."""

    shard_index: int
    policy_ids: tuple[str, ...]
    mesh: Mesh


def plan_policy_shards(
    policy_ids: Sequence[str], mesh: Mesh
) -> list[SubmeshPlan]:
    """Partition top-level policy ids round-robin over the policy axis; each
    shard owns one row of the mesh as its data-parallel submesh."""
    n_shards = mesh.shape[POLICY_AXIS]
    buckets: list[list[str]] = [[] for _ in range(n_shards)]
    for i, pid in enumerate(sorted(policy_ids)):
        buckets[i % n_shards].append(pid)
    plans = []
    for s in range(n_shards):
        row = mesh.devices[s]  # (data,) devices of this shard
        submesh = Mesh(row.reshape(1, -1), (POLICY_AXIS, DATA_AXIS))
        plans.append(SubmeshPlan(s, tuple(buckets[s]), submesh))
    return plans


# ---------------------------------------------------------------------------
# Fused SPMD planning (round 14): one program over the (data × policy) mesh
# ---------------------------------------------------------------------------


def plan_policy_buckets(
    policy_ids: Sequence[str], n_shards: int
) -> tuple[list[tuple[str, ...]], int, dict[str, int]]:
    """Partition policy ids round-robin (sorted, the same placement rule
    ``plan_policy_shards`` uses) into the ``lax.switch`` branch buckets of
    the fused SPMD program.

    Returns ``(buckets, width, column_of)``: every branch pads its
    verdict block to ``width`` columns so all switch branches agree on
    shape, and ``column_of[pid]`` is the policy's column in the
    all-gathered ``(batch, n_shards * width)`` verdict matrix
    (shard-major: shard ``s`` slot ``k`` lands at ``s * width + k``)."""
    ordered = sorted(policy_ids)
    buckets: list[list[str]] = [[] for _ in range(n_shards)]
    for i, pid in enumerate(ordered):
        buckets[i % n_shards].append(pid)
    width = max(1, max((len(b) for b in buckets), default=1))
    column_of = {
        pid: s * width + k
        for s, bucket in enumerate(buckets)
        for k, pid in enumerate(bucket)
    }
    return [tuple(b) for b in buckets], width, column_of


# ---------------------------------------------------------------------------
# Data-parallel dispatch of a fused forward
# ---------------------------------------------------------------------------


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Leading (batch) dim sharded over the data axis, everything else
    replicated."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully replicated placement (the columnar forms' resident
    column-index vectors: every shard scatters with the same columns)."""
    return NamedSharding(mesh, P())


def shard_features(
    features: Mapping[str, np.ndarray], mesh: Mesh
) -> dict[str, jax.Array]:
    """Host → device transfer with the batch axis pre-sharded (one
    device_put of the whole tree). Multi-host meshes assemble the global array from
    each process's LOCAL rows — every host ships only its own shard over
    its own PCIe/DCN link (the per-host frontends feed host-local
    batches)."""
    sharding = batch_sharding(mesh)
    if jax.process_count() > 1:
        return {
            k: jax.make_array_from_process_local_data(
                sharding, np.asarray(v)
            )
            for k, v in features.items()
        }
    return jax.device_put(dict(features), sharding)


def shard_delta_planes(
    shipped: Mapping[str, np.ndarray], mesh: Mesh
) -> dict[str, jax.Array]:
    """What a columnar launch ships → device, mesh-placed: ONE put with
    the batch axis sharded over ``data``, so the wire buffer costs one
    copy per device (every leaf leads with the batch dim: the wire
    buffer, and the wasm side channel where an environment has one). The
    column-index vectors are not here: they live on the device,
    replicated (replicated_sharding), across launches. Single-process
    meshes only (environment._columnar_mesh_ok)."""
    return jax.device_put(dict(shipped), batch_sharding(mesh))


def jit_data_parallel(
    forward: Callable[[Mapping[str, Any]], tuple],
    mesh: Mesh,
) -> Callable[[Mapping[str, Any]], tuple]:
    """jit the fused forward with batch-sharded inputs/outputs. XLA
    partitions the predicate program over the data axis — verdict tensors
    stay distributed until the host gathers them in one device_get."""
    sharding = batch_sharding(mesh)
    return jax.jit(forward, in_shardings=(sharding,), out_shardings=sharding)


def acceptance_psum(mesh: Mesh) -> Callable[[jax.Array], jax.Array]:
    """(B, P) verdict bits → (P,) global acceptance counts via an ICI psum
    over the data axis (the serving-metrics collective; SURVEY.md §5
    'distributed communication backend' row)."""

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=P(DATA_AXIS, None),
        out_specs=P(),
    )
    def count(allowed: jax.Array) -> jax.Array:
        local = allowed.sum(axis=0, dtype=np.int32)
        return lax.psum(local, axis_name=DATA_AXIS)

    return jax.jit(count)


def pad_batch_to(n: int, multiple: int) -> int:
    """Batches must divide the data axis; pad-rows are all-missing and cost
    one masked lane each."""
    if multiple <= 1:
        return n
    return ((n + multiple - 1) // multiple) * multiple
