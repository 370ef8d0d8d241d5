"""FTMesh / HSDP tests: sharded training inside each replica group (real
jax Mesh over virtual CPU devices) x fault-tolerant replica axis (manager).

Parity target: the reference's device_mesh_test.py + fsdp_test.py (FSDP2
fully_shard over ft_init_device_mesh).
"""

from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec

from test_manager import make_manager, make_quorum

from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.manager import Manager
from torchft_tpu.optim import Optimizer
from torchft_tpu.parallel.mesh import FTMesh, ft_allreduce_sharded, ft_init_device_mesh
from torchft_tpu.parallel.process_group import ProcessGroupDummy, ProcessGroupTCP
from torchft_tpu.parallel.store import StoreClient, StoreServer


def scripted_manager(world: int = 2):
    manager, client, _, _ = make_manager(pg=ProcessGroupDummy(), min_replica_size=1)
    client._quorum.return_value = make_quorum(
        replica_world_size=world, max_world_size=world
    )
    client.should_commit.side_effect = lambda rank, step, vote, timeout: vote
    manager.start_quorum()
    return manager


def test_ft_mesh_reports_dynamic_replica_axis() -> None:
    manager = scripted_manager(world=3)
    ft_mesh = ft_init_device_mesh(
        manager, mesh_shape=(2, 2), axis_names=("fsdp", "tp"), devices=jax.devices()[:4]
    )
    assert ft_mesh.axis_names == ("replica", "fsdp", "tp")
    assert ft_mesh.size("replica") == 3
    assert ft_mesh.size("fsdp") == 2
    assert ft_mesh.size() == 12
    assert "dynamic" in repr(ft_mesh)


def test_ft_mesh_rejects_replica_axis_in_mesh_or_spec() -> None:
    manager = scripted_manager()
    with pytest.raises(ValueError, match="virtual"):
        FTMesh(
            manager,
            jax.sharding.Mesh(np.array(jax.devices()[:2]).reshape(2), ("replica",)),
        )
    ft_mesh = ft_init_device_mesh(
        manager, mesh_shape=(2,), axis_names=("fsdp",), devices=jax.devices()[:2]
    )
    with pytest.raises(ValueError, match="replica axis"):
        ft_mesh.sharding("replica")


def test_ft_allreduce_sharded_preserves_sharding() -> None:
    manager = scripted_manager(world=2)
    ft_mesh = ft_init_device_mesh(
        manager, mesh_shape=(4,), axis_names=("fsdp",), devices=jax.devices()[:4]
    )
    sharding = ft_mesh.sharding("fsdp")
    x = jax.device_put(jnp.arange(16, dtype=jnp.float32).reshape(8, 2), sharding)
    grads = {"w": x}
    out = ft_allreduce_sharded(manager, grads)
    # Dummy PG echoes: average over 2 participants = x / 2.
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(x) / 2.0)
    assert out["w"].sharding == sharding
    assert [s.device for s in out["w"].addressable_shards] == [
        s.device for s in x.addressable_shards
    ]


WIRE_STAGES = {
    "stage": "wire_stage", "bucket": "wire_concat", "ring": "wire_ring",
    "average": "wire_average", "scatter": "wire_scatter",
}


def wire_stage_counts() -> dict:
    from torchft_tpu import metrics

    return {
        stage: metrics.histogram_stats("tpuft_wire_stage_seconds", stage=stage)["count"]
        for stage in WIRE_STAGES
    }


def test_ft_allreduce_sharded_records_the_five_wire_stages() -> None:
    """One sharded sync: device to host, concatenation, the ring (recorded
    where its future resolves, with the launch's start stamp), the average
    and the scatter, each a journal span of the sync's step and a sample of
    tpuft_wire_stage_seconds under its stage."""
    from torchft_tpu import tracing

    journal = tracing.TraceJournal(maxlen=256)
    before = wire_stage_counts()
    with tracing.use_journal(journal):
        manager = scripted_manager(world=2)
        ft_mesh = ft_init_device_mesh(
            manager, mesh_shape=(4,), axis_names=("fsdp",), devices=jax.devices()[:4]
        )
        x = jax.device_put(
            jnp.arange(16, dtype=jnp.float32).reshape(8, 2), ft_mesh.sharding("fsdp")
        )
        out = ft_allreduce_sharded(manager, {"w": x, "b": jnp.ones(3, jnp.float32)})
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(x) / 2.0)
    spans = {e["name"]: e for e in journal.snapshot() if e["ph"] == "X"}
    for stage, name in WIRE_STAGES.items():
        assert name in spans, (name, sorted(spans))
        assert spans[name]["step"] == manager.current_step()
    # mesh.py stages the shards; allreduce_pytree then finds host arrays,
    # buckets them, launches the ring, averages in the future's callback.
    order = [spans[n]["t_mono"] for n in ("wire_concat", "wire_ring", "wire_average", "wire_scatter")]
    assert order == sorted(order)
    ring, average = spans["wire_ring"], spans["wire_average"]
    assert ring["t_mono"] + ring["dur"] <= average["t_mono"] + 1e-9
    after = wire_stage_counts()
    assert {s: after[s] - before[s] for s in WIRE_STAGES} == {
        "stage": 2, "bucket": 1, "ring": 1, "average": 1, "scatter": 1,
    }


def test_hsdp_two_groups_converge_bitwise() -> None:
    """2 replica groups (threads), each FSDP-sharding params over its own
    4-device sub-mesh; cross-group sync via ft_allreduce_sharded."""
    lighthouse = LighthouseServer(
        min_replicas=1, join_timeout_ms=10000, heartbeat_timeout_ms=1000
    )
    num_steps = 3

    def group_loop(group: int):
        devices = jax.devices()[group * 4 : (group + 1) * 4]
        store = StoreServer()
        client = StoreClient(store.address())
        pg = ProcessGroupTCP(timeout=10.0)
        manager = Manager(
            pg=pg,
            min_replica_size=1,
            store=client,
            store_addr=store.address(),
            group_rank=0,
            lighthouse_addr=lighthouse.address(),
            replica_id=f"hsdp_{group}",
            heartbeat_interval=0.05,
            timeout=10.0,
            quorum_timeout=20.0,
        )
        try:
            ft_mesh = ft_init_device_mesh(
                manager, mesh_shape=(4,), axis_names=("fsdp",), devices=devices
            )
            wsharding = ft_mesh.sharding("fsdp")
            params = {
                "w": jax.device_put(
                    jax.random.normal(jax.random.PRNGKey(0), (16, 8), jnp.float32),
                    wsharding,
                ),
                "b": jax.device_put(
                    jnp.zeros((8,), jnp.float32), ft_mesh.sharding()
                ),
            }
            opt = Optimizer(manager, optax.sgd(0.1), params)

            @jax.jit
            def loss_fn(p, x, y):
                return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

            grad_fn = jax.jit(jax.grad(loss_fn))
            while manager.current_step() < num_steps:
                step = manager.current_step()
                key = jax.random.PRNGKey(100 * group + step)
                kx, ky = jax.random.split(key)
                x = jax.random.normal(kx, (4, 16), jnp.float32)
                y = jax.random.normal(ky, (4, 8), jnp.float32)
                opt.begin_step()
                grads = grad_fn(opt.params, x, y)
                avg = ft_allreduce_sharded(manager, grads)
                # The averaged grads keep their FSDP sharding.
                assert avg["w"].sharding == wsharding
                opt.step(avg)
            return jax.tree_util.tree_map(np.asarray, opt.params)
        finally:
            manager.shutdown(wait=False)
            pg.shutdown()
            store.shutdown()

    before = wire_stage_counts()
    try:
        with ThreadPoolExecutor(max_workers=2) as pool:
            results = list(pool.map(group_loop, range(2)))
        for key in results[0]:
            assert results[0][key].tobytes() == results[1][key].tobytes()
    finally:
        lighthouse.shutdown()
    # Every step that crossed the groups went through the five stages, on a
    # real ring (a step a group ran alone skips the wire).
    grown = {s: n - before[s] for s, n in wire_stage_counts().items()}
    assert all(grown[s] >= 2 for s in WIRE_STAGES), grown
    assert grown["ring"] == grown["average"] == grown["scatter"] == grown["bucket"]
