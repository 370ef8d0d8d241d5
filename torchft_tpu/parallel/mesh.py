"""Fault-tolerant device mesh: intra-slice sharding x FT replica axis.

Role-equivalent of the reference's ``ManagedDeviceMesh`` / ``ft_init_device_mesh``
(/root/reference/torchft/device_mesh.py:307-340): the reference builds a real
DeviceMesh *without* the replicate dim and re-inserts it virtually, lying
about its size so FSDP/TP code composes with a dynamically-resizing replica
axis.

The TPU translation: intra-slice parallelism (fsdp/tp/sp) is a real
``jax.sharding.Mesh`` over the slice's devices — XLA inserts those
collectives inside the jitted step over ICI. The replica axis is *not* a
jax mesh dim: it is the manager's resizable process group over DCN, so
membership changes never force an XLA recompile. :class:`FTMesh` exposes the
composite view (replica axis size = live participant count) and
:func:`ft_allreduce_sharded` performs the HSDP gradient sync: each host
reduces its *local shards* with the corresponding hosts of other replica
groups, keeping sharded arrays sharded end to end.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from torchft_tpu import tracing
from torchft_tpu.manager import Manager

__all__ = ["FTMesh", "ft_init_device_mesh", "ft_allreduce_sharded"]


class FTMesh:
    """Composite mesh view: a real intra-slice Mesh plus the virtual,
    dynamically-sized replica axis managed by the fault-tolerance layer."""

    def __init__(
        self,
        manager: Manager,
        mesh: Mesh,
        replica_axis_name: str = "replica",
    ) -> None:
        self.manager = manager
        self.mesh = mesh
        self.replica_axis_name = replica_axis_name
        if replica_axis_name in mesh.axis_names:
            raise ValueError(
                f"replica axis {replica_axis_name!r} must not be a jax mesh dim: "
                "it is virtual (resized per quorum without recompiling)"
            )

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return (self.replica_axis_name, *self.mesh.axis_names)

    def size(self, axis: Optional[str] = None) -> int:
        """Axis size; the replica axis reports the live participant count
        (0 participants reads as 1, the ManagedDeviceMesh lie —
        reference device_mesh.py:169-184)."""
        if axis is None:
            return self.size(self.replica_axis_name) * int(
                np.prod([self.mesh.shape[a] for a in self.mesh.axis_names])
            )
        if axis == self.replica_axis_name:
            return max(self.manager.num_participants(), 1)
        return self.mesh.shape[axis]

    def replica_rank(self) -> Optional[int]:
        return self.manager.participating_rank()

    def sharding(self, *spec: Any) -> NamedSharding:
        """NamedSharding over the intra-slice mesh. The replica axis never
        appears in specs (replicated-by-construction across groups)."""
        for entry in spec:
            names = entry if isinstance(entry, (tuple, list)) else (entry,)
            for name in names:
                if name == self.replica_axis_name:
                    raise ValueError(
                        "shard over the replica axis via the manager "
                        "(ft_allreduce_sharded), not NamedSharding"
                    )
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def __repr__(self) -> str:
        return (
            f"FTMesh(replica={self.replica_axis_name}(dynamic), "
            f"mesh={dict(self.mesh.shape)})"
        )


def ft_init_device_mesh(
    manager: Manager,
    mesh_shape: Sequence[int],
    axis_names: Sequence[str],
    replica_axis_name: str = "replica",
    devices: Optional[Sequence[Any]] = None,
) -> FTMesh:
    """Builds the intra-slice Mesh (without the replica dim) and wraps it in
    an :class:`FTMesh` (reference ft_init_device_mesh, device_mesh.py:307-340).

    ``mesh_shape``/``axis_names`` describe only the intra-slice axes; pass
    ``devices`` to restrict to a subset (e.g. a slice's local devices).
    """
    if len(mesh_shape) != len(axis_names):
        raise ValueError("mesh_shape and axis_names must align")
    devices = list(devices if devices is not None else jax.devices())
    needed = int(np.prod(mesh_shape))
    if len(devices) < needed:
        raise ValueError(f"need {needed} devices, have {len(devices)}")
    device_grid = np.array(devices[:needed]).reshape(tuple(mesh_shape))
    return FTMesh(manager, Mesh(device_grid, tuple(axis_names)), replica_axis_name)


def ft_allreduce_sharded(
    manager: Manager, grads: Any, should_quantize: bool = False
) -> Any:
    """HSDP gradient sync: averages each leaf across replica groups while
    preserving its intra-slice sharding.

    For every jax.Array leaf, the host's addressable shards are staged to
    host memory, reduced shard-by-shard with the corresponding shards on the
    other replica groups (one flat payload on the manager's process group),
    and scattered back onto the same devices/sharding. Shard layouts must
    match across groups — guaranteed when every group runs the same model
    under the same intra-slice mesh, the invariant HSDP already requires.
    """
    from torchft_tpu.ddp import _single_participant_identity

    if _single_participant_identity(manager):
        return grads

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    # The stages of one sync are phases of their own (tpuft::wire::*,
    # tpuft_wire_stage_seconds{stage}); bucketing, the ring and the average
    # are recorded inside Manager.allreduce_pytree.
    trace = getattr(manager, "_trace", None)
    labels = getattr(manager, "_metric_labels", None)
    step = manager.current_step()
    ids = {"step": step if isinstance(step, int) else None}

    # Stage: per-leaf list of (device, host_shard) in index order.
    staged: List[Dict[str, Any]] = []
    flat_arrays: List[np.ndarray] = []
    with tracing.phase("wire_stage", trace, labels, **ids):
        for leaf in leaves:
            if isinstance(leaf, jax.Array) and hasattr(leaf, "addressable_shards"):
                # Deterministic, group-independent order: by the shard's index
                # window (device ids differ across replica groups).
                shards = sorted(
                    leaf.addressable_shards,
                    key=lambda s: tuple(
                        (sl.start or 0, sl.stop if sl.stop is not None else -1)
                        for sl in s.index
                    ),
                )
                entry = {
                    "type": "sharded",
                    "sharding": leaf.sharding,
                    "shape": leaf.shape,
                    "dtype": leaf.dtype,
                    "devices": [s.device for s in shards],
                    "indices": [s.index for s in shards],
                    "count": len(shards),
                }
                staged.append(entry)
                for s in shards:
                    flat_arrays.append(np.asarray(s.data))
            else:
                staged.append({"type": "plain", "count": 1})
                flat_arrays.append(np.asarray(leaf))

    work = manager.allreduce_pytree(flat_arrays, should_quantize=should_quantize)
    # The ring's journal event and histogram sample are recorded where its
    # future resolves (another thread); this thread's wait is annotated.
    with tracing.annotation("wire_ring", **ids):
        averaged: List[np.ndarray] = work.wait()

    # Scatter back preserving shardings.
    with tracing.phase("wire_scatter", trace, labels, **ids):
        out_leaves: List[Any] = []
        cursor = 0
        for entry, orig in zip(staged, leaves):
            if entry["type"] == "plain":
                host = averaged[cursor]
                cursor += 1
                if isinstance(orig, jax.Array):
                    out_leaves.append(jax.device_put(host, orig.sharding))
                else:
                    out_leaves.append(host)
                continue
            shard_arrays = averaged[cursor : cursor + entry["count"]]
            cursor += entry["count"]
            buffers = [
                jax.device_put(host, device)
                for host, device in zip(shard_arrays, entry["devices"])
            ]
            out_leaves.append(
                jax.make_array_from_single_device_arrays(
                    entry["shape"], entry["sharding"], buffers
                )
            )
    return jax.tree_util.tree_unflatten(treedef, out_leaves)
