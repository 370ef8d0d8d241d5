"""Multi-host support units: sharded checkpoint capture/restore, optimizer
state placement, group jax-cluster bootstrap, platform honoring.

The true multi-process paths are driven end-to-end by the launcher chaos
runs (verify drives); these tests pin the building blocks on the 8-device
single-process mesh, with a duck-typed stand-in for partially-addressable
arrays (single-process jax arrays are always fully addressable)."""

import io
from dataclasses import dataclass
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchft_tpu.checkpointing import _serialization
from torchft_tpu.checkpointing._serialization import ShardedLeaf, ShardedLeafMeta
from torchft_tpu.optim import Optimizer, _align_opt_state, _restore_leaf


class _FakeMultiHostArray:
    """Duck-typed partially-addressable array: only `local` shards visible."""

    def __init__(self, full: np.ndarray, mesh_size: int, local: List[int]) -> None:
        self._full = full
        self.shape = full.shape
        self.dtype = full.dtype
        self.is_fully_addressable = False
        rows = full.shape[0] // mesh_size

        @dataclass
        class Shard:
            index: Tuple[slice, ...]
            data: np.ndarray

        self.addressable_shards = [
            Shard(
                (slice(i * rows, (i + 1) * rows), slice(None)),
                full[i * rows : (i + 1) * rows],
            )
            for i in local
        ]


def test_sharded_leaf_capture_and_streaming_roundtrip() -> None:
    full = np.arange(32, dtype=np.float32).reshape(8, 4)
    fake = _FakeMultiHostArray(full, mesh_size=4, local=[0, 1])

    leaf = _serialization._to_host(fake)
    assert isinstance(leaf, ShardedLeaf)
    assert leaf.global_shape == (8, 4)
    assert len(leaf.shards) == 2  # only the local shards

    # Shard buffers ride the raw stream (meta carries sizes), not the header.
    state = {"w": fake, "plain": np.ones(3, np.float32)}
    treedef, metas, leaves = _serialization.state_dict_meta(state)
    sharded_metas = [m for m in metas if isinstance(m, ShardedLeafMeta)]
    assert len(sharded_metas) == 1
    assert sum(sharded_metas[0].shard_nbytes) == 2 * 2 * 4 * 4

    buf = io.BytesIO()
    _serialization.save_state_dict(state, buf)
    buf.seek(0)
    restored = _serialization.load_state_dict(buf)
    assert isinstance(restored["w"], ShardedLeaf)
    for (key, data), (rkey, rdata) in zip(leaf.shards, restored["w"].shards):
        assert key == rkey
        np.testing.assert_array_equal(data, rdata)
    np.testing.assert_array_equal(restored["plain"], np.ones(3, np.float32))


def test_restore_leaf_reassembles_against_current_sharding() -> None:
    mesh = Mesh(np.array(jax.devices()[:4]), ("fsdp",))
    sharding = NamedSharding(mesh, P("fsdp"))
    current = jax.device_put(jnp.zeros((8, 4), jnp.float32), sharding)

    donor_full = np.arange(32, dtype=np.float32).reshape(8, 4)
    donor = ShardedLeaf(
        (8, 4),
        "float32",
        [
            (((i * 2, (i + 1) * 2), (0, 4)), donor_full[i * 2 : (i + 1) * 2])
            for i in range(4)
        ],
    )
    restored = _restore_leaf(donor, current)
    assert restored.sharding == sharding
    np.testing.assert_array_equal(np.asarray(restored), donor_full)

    # Missing shard -> loud error, not silent corruption.
    partial = ShardedLeaf((8, 4), "float32", donor.shards[:2])
    with pytest.raises(ValueError, match="lacks shard"):
        _restore_leaf(partial, current)


def test_align_opt_state_replicates_scalars_over_params_mesh() -> None:
    mesh = Mesh(np.array(jax.devices()[:4]), ("fsdp",))
    params = {
        "w": jax.device_put(
            jnp.zeros((8, 4), jnp.float32), NamedSharding(mesh, P("fsdp"))
        )
    }
    tx = optax.adam(1e-3)
    aligned = _align_opt_state(tx.init(params), params)
    target = {d.id for d in params["w"].sharding.device_set}
    for leaf in jax.tree_util.tree_leaves(aligned):
        if isinstance(leaf, jax.Array):
            assert {d.id for d in leaf.sharding.device_set} == target

    # The jitted update accepts grads on the mesh without device conflicts.
    opt = object.__new__(Optimizer)
    from torchft_tpu.optim import make_jit_update

    update = make_jit_update(tx)
    grads = {"w": jax.device_put(jnp.ones((8, 4)), NamedSharding(mesh, P("fsdp")))}
    new_params, new_state = update(grads, aligned, params)
    assert jnp.isfinite(jax.tree_util.tree_leaves(new_params)[0]).all()


def test_init_group_jax_cluster_noop_without_coordinator(monkeypatch) -> None:
    from torchft_tpu.bootstrap import init_group_jax_cluster

    monkeypatch.delenv("TPUFT_JAX_COORDINATOR", raising=False)
    assert init_group_jax_cluster() is False


def test_launcher_rejects_coordinator_without_multirank() -> None:
    from torchft_tpu.launch import supervise

    with pytest.raises(ValueError, match="group-world-size"):
        supervise(
            ["true"], num_replica_groups=1, group_world_size=1,
            jax_coordinator_port_base=30000,
        )
