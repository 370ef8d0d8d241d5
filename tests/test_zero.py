"""ZeRO plane tests (torchft_tpu/zero.py).

Pure-python coverage that runs without the native toolchain: shard
assignment determinism, flat-plane pack/unpack, N=1 degeneration against
the plain Optimizer, bitwise identity across commit orderings, the
re-balance transfer plan, shard-addressable heal (skip_parts), and REAL
multi-rank wire behavior over an in-process loopback ProcessGroup (each
replica a thread — no native store needed). The full kill/heal drill on
the real coordination plane lives in test_zero_integ.py (native-gated).
"""

import os
import threading
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from test_manager import make_manager, make_quorum

from torchft_tpu import metrics
from torchft_tpu.checkpointing.http_transport import HTTPTransport
from torchft_tpu.optim import Optimizer, _align_opt_state, make_jit_shard_update
from torchft_tpu.parallel.process_group import (
    ProcessGroup,
    ProcessGroupDummy,
    ReduceOp,
)
from torchft_tpu.work import _DummyWork
from torchft_tpu.zero import (
    ShardSpec,
    ZeroOptimizer,
    plan_shard_moves,
    shard_assignment,
    shard_part_name,
)


def scripted_manager(num_participants=1, rank=0, pg=None, **kwargs):
    """One-replica-group manager against a scripted coordination client."""
    kwargs.setdefault("min_replica_size", 1)
    manager, client, _pg, transport = make_manager(
        pg=pg if pg is not None else ProcessGroupDummy(), **kwargs
    )
    client._quorum.return_value = make_quorum(
        replica_rank=rank,
        replica_world_size=num_participants,
        max_rank=rank,
        max_world_size=num_participants,
    )
    client.should_commit.side_effect = lambda rank, step, vote, timeout: vote
    return manager


# ---------------------------------------------------------------------------
# shard assignment + transfer plan (pure functions)
# ---------------------------------------------------------------------------


def test_shard_assignment_deterministic_and_complete() -> None:
    for n in (1, 2, 3, 4, 7, 8):
        for policy in ("block", "strided"):
            a = shard_assignment(8, n, step=3, policy=policy)
            b = shard_assignment(8, n, step=3, policy=policy)
            np.testing.assert_array_equal(a, b)  # no communication, no state
            assert a.shape == (8,)
            # Complete: every shard has exactly one owner in range.
            assert set(np.unique(a)) <= set(range(min(n, 8)))
            # Balanced: owner loads differ by at most one shard.
            counts = np.bincount(a, minlength=min(n, 8))
            assert counts.max() - counts.min() <= 1


def test_shard_assignment_block_is_contiguous() -> None:
    owners = shard_assignment(8, 4, policy="block")
    np.testing.assert_array_equal(owners, [0, 0, 1, 1, 2, 2, 3, 3])
    owners = shard_assignment(8, 3, policy="block")
    np.testing.assert_array_equal(owners, [0, 0, 0, 1, 1, 1, 2, 2])


def test_shard_assignment_n1_owns_everything() -> None:
    np.testing.assert_array_equal(shard_assignment(8, 1), np.zeros(8))


def test_shard_assignment_rejects_bad_policy() -> None:
    with pytest.raises(ValueError):
        shard_assignment(8, 2, policy="roulette")


def test_plan_shard_moves_only_moves_changed_ownership() -> None:
    # 2 ranks each holding their block at step 5; same assignment again:
    # nothing moves.
    manifests = [
        (0, 5, [(0, 5), (1, 5)]),
        (1, 5, [(2, 5), (3, 5)]),
    ]
    owners = shard_assignment(4, 2, policy="block")
    moves, lost = plan_shard_moves(manifests, owners, {0: 0, 1: 1}, 5)
    assert moves == [] and lost == []


def test_plan_shard_moves_shrink_reassigns_and_reports_lost() -> None:
    # Rank 1 died holding shards 2, 3: the survivor owns everything under
    # N=1; its held shards stay put, the dead ones are lost.
    manifests = [(0, 5, [(0, 5), (1, 5)])]
    owners = shard_assignment(4, 1)
    moves, lost = plan_shard_moves(manifests, owners, {0: 0}, 5)
    assert moves == [] and lost == [2, 3]


def test_plan_shard_moves_grow_moves_only_new_owners_shards() -> None:
    # Survivor (pg 0) holds all 4 at step 9; a joiner lands at
    # participant rank 1 / pg rank 1: exactly the joiner's block moves.
    manifests = [(0, 9, [(0, 9), (1, 9), (2, 9), (3, 9)]), (1, 9, [])]
    owners = shard_assignment(4, 2, policy="block")
    moves, lost = plan_shard_moves(manifests, owners, {0: 0, 1: 1}, 9)
    assert moves == [(2, 0, 1), (3, 0, 1)] and lost == []


def test_plan_shard_moves_fences_stale_holders() -> None:
    # A rejoiner kept shards from before it died (step 3 < current 7):
    # never chosen as a source; its shards count as lost.
    manifests = [(0, 7, []), (1, 3, [(0, 3), (1, 3)])]
    owners = shard_assignment(2, 1)
    moves, lost = plan_shard_moves(manifests, owners, {0: 0}, 7)
    assert moves == [] and lost == [0, 1]


# ---------------------------------------------------------------------------
# ShardSpec flat plane
# ---------------------------------------------------------------------------


def test_shard_spec_pack_unpack_roundtrip_mixed_dtypes() -> None:
    params = {
        "w": jnp.arange(10, dtype=jnp.float32).reshape(2, 5) / 7,
        "b": jnp.ones((3,), jnp.bfloat16),
        "scalar": jnp.float32(2.5),
    }
    spec = ShardSpec(params, num_shards=4)
    assert spec.total == 14
    assert spec.padded == spec.num_shards * spec.shard_len >= spec.total
    flat = spec.pack(params)
    assert flat.shape == (spec.padded,) and flat.dtype == jnp.float32
    back = spec.unpack(flat)
    for key in params:
        got, want = back[key], params[key]
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_shard_spec_rejects_non_array_leaves() -> None:
    with pytest.raises(ValueError, match="non-array"):
        ShardSpec({"w": jnp.ones(3), "name": "layer0"}, num_shards=2)


def test_make_jit_shard_update_matches_per_shard_eager() -> None:
    tx = optax.adam(0.1)
    update = make_jit_shard_update(tx)
    masters = [jnp.arange(4, dtype=jnp.float32), jnp.ones(4, jnp.float32)]
    states = [tx.init(m) for m in masters]
    grads = [jnp.full((4,), 0.5, jnp.float32), jnp.full((4,), -1.0, jnp.float32)]
    new_masters, new_states = update(grads, states, masters)
    for g, s, m, nm in zip(grads, states, masters, new_masters):
        upd, _ = tx.update(g, s, m)
        np.testing.assert_allclose(
            np.asarray(nm), np.asarray(optax.apply_updates(m, upd)), rtol=1e-6
        )


# ---------------------------------------------------------------------------
# N=1 degeneration + commit orderings (scripted manager, no wire)
# ---------------------------------------------------------------------------

_PARAMS = {"w": jnp.array([1.0, -2.0, 3.0], jnp.float32)}


def _loss(p, batch):
    return jnp.sum((p["w"] - batch) ** 2)


_BATCHES = [jnp.full((3,), 0.1 * i, jnp.float32) for i in range(5)]


_DEPTH_OF = {"strict": 0, "overlapped": 0, "pipelined": 1, "pipelined-deep": 3}


def _run_zero(mode, monkeypatch, tx=None, num_shards=4):
    monkeypatch.setenv("TPUFT_STRICT_COMMIT", "1" if mode == "strict" else "0")
    manager = scripted_manager(commit_pipeline_depth=_DEPTH_OF[mode])
    opt = ZeroOptimizer(
        manager, tx or optax.sgd(0.2, momentum=0.9), _PARAMS,
        num_shards=num_shards,
    )
    step_fn = opt.make_step_fn(_loss)
    losses = []
    for batch in _BATCHES:
        loss, _committed = step_fn(batch)
        losses.append(float(loss))
    if _DEPTH_OF[mode]:
        assert opt.flush_pipeline() is True
    return np.asarray(opt.params["w"]), losses, manager.current_step(), opt


def test_zero_lone_replica_matches_plain_optimizer(monkeypatch) -> None:
    """N=1 degenerates to today's behavior: same trajectory as the plain
    Optimizer (float tolerance — the flat-plane program differs from the
    fused tree program by XLA scheduling, not by math) and full shard
    ownership with zero wire traffic."""
    import torchft_tpu.ddp as ddp_mod

    def _boom(*a, **k):
        raise AssertionError("wire path used on the lone-replica zero step")

    monkeypatch.setattr(ddp_mod, "ft_allreduce_gradients", _boom)
    ref_manager = scripted_manager()
    # A copy: the arrays given to an Optimizer are its own (its lone step
    # updates them in place), and _PARAMS is every test's.
    ref = Optimizer(
        ref_manager, optax.sgd(0.2, momentum=0.9),
        jax.tree_util.tree_map(jnp.copy, _PARAMS),
    )
    ref_fn = ref.make_step_fn(_loss)
    ref_losses = [float(ref_fn(b)[0]) for b in _BATCHES]

    w, losses, step, opt = _run_zero("overlapped", monkeypatch)
    np.testing.assert_allclose(w, np.asarray(ref.params["w"]), rtol=1e-6)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-6)
    assert step == 5
    # Sole participant owns every shard — the degenerate (unsharded) case.
    assert sorted(opt.opt_state.held) == [0, 1, 2, 3]


@pytest.mark.parametrize(
    "mode", ["strict", "overlapped", "pipelined", "pipelined-deep"]
)
def test_zero_orderings_produce_identical_trajectories(monkeypatch, mode) -> None:
    """The sharded step commits bitwise-identical params under all four
    commit orderings — strict / overlapped / pipelined depth 1 / depth 3
    (rollback snapshots of a sharded opt_state included in the pipelined
    window machinery at every depth)."""
    w_ref, losses_ref, _, _ = _run_zero("strict", monkeypatch)
    w, losses, step, _ = _run_zero(mode, monkeypatch)
    np.testing.assert_array_equal(w, w_ref)
    assert losses == losses_ref
    assert step == 5


def test_zero_pipelined_rollback_restores_sharded_state(monkeypatch) -> None:
    monkeypatch.setenv("TPUFT_STRICT_COMMIT", "0")
    manager = scripted_manager(commit_pipeline_depth=1)
    votes = iter([True, False, True, True])
    manager._client.should_commit.side_effect = (
        lambda rank, step, vote, timeout: vote and next(votes)
    )
    opt = ZeroOptimizer(
        manager, optax.sgd(0.1), {"w": jnp.array([1.0, 1.0], jnp.float32)},
        num_shards=2,
    )
    step_fn = opt.make_step_fn(lambda p, b: jnp.sum((p["w"] - b) ** 2))
    flags = []
    for i in range(4):
        _, prev = step_fn(jnp.full((2,), float(i), jnp.float32))
        flags.append(prev)
    assert opt.flush_pipeline() is True
    assert flags == [None, True, False, True]
    assert opt.rollback_count == 1
    assert manager.current_step() == 3
    # The sharded state's committed-step tag tracks the manager exactly
    # (the re-balance manifest's freshness fence).
    assert opt.opt_state.step == 3
    w = np.array([1.0, 1.0], np.float32)
    for b in (0.0, 2.0, 3.0):
        w = w - 0.1 * 2 * (w - b)
    np.testing.assert_allclose(np.asarray(opt.params["w"]), w, rtol=1e-6)


def test_zero_heal_during_barrier_recomputes_on_healed_state() -> None:
    """A heal landing inside the commit barrier: params adopt the
    allgathered (committed) ranges, the healed shard-less state forces a
    re-balance, and nothing stale survives."""
    manager = scripted_manager()
    opt = ZeroOptimizer(
        manager, optax.sgd(0.1), {"w": jnp.array([1.0, 1.0], jnp.float32)},
        num_shards=2,
    )
    step_fn = opt.make_step_fn(lambda p, b: jnp.sum((p["w"] - b) ** 2))
    loss, committed = step_fn(jnp.array([1.0, 2.0], jnp.float32))
    assert committed

    donor_manager = scripted_manager()
    donor = ZeroOptimizer(
        donor_manager, optax.sgd(0.1),
        {"w": jnp.array([10.0, 10.0], jnp.float32)}, num_shards=2,
    )
    donor_fn = donor.make_step_fn(lambda p, b: jnp.sum((p["w"] - b) ** 2))
    donor_fn(jnp.zeros(2, jnp.float32))
    donor_state = donor._state_dict()

    real_should_commit = manager.should_commit
    healed_once = []

    def healing_should_commit(timeout=None):
        ok = real_should_commit(timeout=timeout)
        if not healed_once:
            healed_once.append(True)
            opt._load_state_dict(donor_state)
        return ok

    manager.should_commit = healing_should_commit
    _, committed = step_fn(jnp.array([0.0, 0.0], jnp.float32))
    assert committed
    assert opt._heal_count == 1
    # The healed state forces a fresh re-balance at the next step.
    assert opt.opt_state.balance_key is None
    _, committed = step_fn(jnp.array([0.0, 0.0], jnp.float32))
    assert committed
    assert sorted(opt.opt_state.held) == [0, 1]


# ---------------------------------------------------------------------------
# loopback multi-rank wire (threads as replicas, no native store)
# ---------------------------------------------------------------------------


class _LoopbackWorld:
    """In-memory rendezvous for N thread-replicas: collectives match up by
    per-rank op sequence number (every replica runs the same deterministic
    op order — the same assumption the real byte-stream PG makes)."""

    def __init__(self, world_size: int, timeout: float = 30.0) -> None:
        self.n = world_size
        self.timeout = timeout
        self._cv = threading.Condition()
        self._slots: Dict[int, Dict[int, Any]] = {}
        self._results: Dict[int, Any] = {}
        self._p2p: Dict[tuple, List[Any]] = {}
        # Per-rank collective sequence numbers live on the WORLD (not the
        # PG) so a freshly-joined replica's first collective matches the
        # survivors' next one in this world's epoch.
        self._seq: Dict[int, int] = {}

    def collective(self, rank: int, payload: Any, combine) -> Any:
        with self._cv:
            op_id = self._seq.get(rank, 0)
            self._seq[rank] = op_id + 1
            slot = self._slots.setdefault(op_id, {})
            slot[rank] = payload
            if len(slot) == self.n:
                self._results[op_id] = combine(slot)
                self._cv.notify_all()
            elif not self._cv.wait_for(
                lambda: op_id in self._results, timeout=self.timeout
            ):
                raise TimeoutError(f"loopback collective {op_id} timed out")
            return self._results[op_id]

    def send(self, src: int, dst: int, tag: int, arrays: List[np.ndarray]) -> None:
        with self._cv:
            self._p2p[(src, dst, tag)] = [np.array(a) for a in arrays]
            self._cv.notify_all()

    def recv(self, src: int, dst: int, tag: int) -> List[np.ndarray]:
        with self._cv:
            if not self._cv.wait_for(
                lambda: (src, dst, tag) in self._p2p, timeout=self.timeout
            ):
                raise TimeoutError(f"loopback recv ({src}->{dst}, {tag}) timed out")
            return self._p2p.pop((src, dst, tag))


class LoopbackPG(ProcessGroup):
    """ProcessGroup over a shared :class:`_LoopbackWorld` — real N-rank
    collective semantics, zero sockets. reduce_scatter splits along axis 0
    like the TCP backend; all reductions are bitwise identical across
    ranks (single accumulation order)."""

    def __init__(self, world: _LoopbackWorld, rank: int) -> None:
        super().__init__()
        self._world = world
        self._rank = rank
        self._op = 0
        self.op_counts: Dict[str, int] = {}

    def configure(self, store_addr, replica_id, rank, world_size) -> None:
        pass

    def abort(self) -> None:
        pass

    def shutdown(self) -> None:
        pass

    def errored(self) -> Optional[Exception]:
        return None

    def size(self) -> int:
        return self._world.n

    def rank(self) -> int:
        return self._rank

    def _next(self, name: str) -> int:
        self.op_counts[name] = self.op_counts.get(name, 0) + 1
        self._op += 1
        return self._op

    def allreduce(self, arrays: Sequence[np.ndarray], op: ReduceOp = ReduceOp.SUM):
        self._next("allreduce")

        def combine(slot):
            out = []
            for i in range(len(arrays)):
                acc = np.array(slot[0][i], dtype=np.float64)
                for r in range(1, self._world.n):
                    acc = acc + slot[r][i]
                out.append(acc)
            return out

        result = self._world.collective(
            self._rank, [np.asarray(a) for a in arrays], combine
        )
        return _DummyWork([r.astype(np.asarray(a).dtype) for r, a in zip(result, arrays)])

    def reduce_scatter(self, arrays: Sequence[np.ndarray], op: ReduceOp = ReduceOp.SUM):
        self._next("reduce_scatter")

        def combine(slot):
            out = []
            for i in range(len(arrays)):
                acc = np.array(slot[0][i], dtype=np.float64)
                for r in range(1, self._world.n):
                    acc = acc + slot[r][i]
                out.append(acc)
            return out

        reduced = self._world.collective(
            self._rank, [np.asarray(a) for a in arrays], combine
        )
        outs = []
        for full, a in zip(reduced, arrays):
            outs.append(
                np.split(full.astype(np.asarray(a).dtype), self._world.n, axis=0)[
                    self._rank
                ].copy()
            )
        return _DummyWork(outs)

    def allgather(self, arrays: Sequence[np.ndarray]):
        self._next("allgather")

        def combine(slot):
            return [
                [np.array(a) for a in slot[r]] for r in range(self._world.n)
            ]

        result = self._world.collective(
            self._rank, [np.asarray(a) for a in arrays], combine
        )
        return _DummyWork(result)

    def broadcast(self, arrays, root: int = 0):
        self._next("broadcast")

        def combine(slot):
            return [np.array(a) for a in slot[root]]

        return _DummyWork(
            self._world.collective(self._rank, list(arrays), combine)
        )

    def alltoall(self, arrays):
        # Rank r sends arrays[d] to rank d and receives every rank's
        # chunk r — the quantized-allreduce wire shape (TPUFT_ZERO_CODEC
        # rides parallel/collectives.allreduce_quantized over this).
        self._next("alltoall")

        def combine(slot):
            return [
                [np.array(a) for a in slot[r]] for r in range(self._world.n)
            ]

        matrix = self._world.collective(
            self._rank, [np.asarray(a) for a in arrays], combine
        )
        return _DummyWork([matrix[r][self._rank] for r in range(self._world.n)])

    def send(self, arrays, dst: int, tag: int = 0):
        self._next("send")
        self._world.send(self._rank, dst, tag, list(arrays))
        return _DummyWork(None)

    def recv(self, shapes_like, src: int, tag: int = 0):
        self._next("recv")
        return _DummyWork(self._world.recv(src, self._rank, tag))

    def barrier(self):
        return self.allreduce([np.zeros(1, np.float32)])


def _make_rank(world, rank, nparts, params, tx, num_shards=4, quorum_id=1,
               **manager_kwargs):
    pg = LoopbackPG(world, rank)
    manager = scripted_manager(
        num_participants=nparts, rank=rank, pg=pg, **manager_kwargs
    )
    manager._client._quorum.return_value = make_quorum(
        quorum_id=quorum_id,
        replica_rank=rank,
        replica_world_size=nparts,
        max_rank=rank,
        max_world_size=nparts,
    )
    opt = ZeroOptimizer(manager, tx, params, num_shards=num_shards)
    return manager, opt, pg


def _parallel(fns):
    """Runs one callable per replica on its own thread; re-raises the
    first failure."""
    results: List[Any] = [None] * len(fns)
    errors: List[BaseException] = []

    def runner(i):
        try:
            results[i] = fns[i]()
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [
        threading.Thread(target=runner, args=(i,)) for i in range(len(fns))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    if errors:
        raise errors[0]
    return results


@pytest.mark.parametrize("nparts", [2, 4])
def test_zero_multi_rank_bitwise_identical_params(nparts) -> None:
    """The construction invariant at real multi-rank wire semantics: every
    committed step ends with bitwise-identical params on every replica
    (each range computed once by its owner and allgathered), and each
    replica persists only ~1/N of the optimizer state."""
    tx = optax.adam(0.05)
    params = {"w": jnp.arange(24, dtype=jnp.float32).reshape(4, 6) / 11}
    world = _LoopbackWorld(nparts)
    ranks = [
        _make_rank(world, r, nparts, params, tx, num_shards=4)
        for r in range(nparts)
    ]

    def loss(p, b):
        return jnp.sum((p["w"] - b) ** 2)

    grad = jax.jit(jax.grad(loss))

    def run(r):
        manager, opt, _pg = ranks[r]

        def go():
            for step in range(3):
                manager.start_quorum()
                manager.wait_quorum()
                batch = jnp.full((4, 6), 0.1 * (step + r), jnp.float32)
                assert opt.step(grad(opt.params, batch))
            return np.asarray(opt.params["w"]), opt.opt_state

        return go

    results = _parallel([run(r) for r in range(nparts)])
    w0 = results[0][0]
    for w, _state in results[1:]:
        np.testing.assert_array_equal(w, w0)
    held_sets = [sorted(state.held) for _w, state in results]
    assert sorted(sum(held_sets, [])) == [0, 1, 2, 3]  # disjoint + complete
    sizes = [state.owned_bytes() for _w, state in results]
    if nparts == 4:
        assert all(s == sizes[0] for s in sizes)  # 1 shard each
    # Fast path engaged: the grad reduce rode pg.reduce_scatter.
    assert all(
        pg.op_counts.get("reduce_scatter", 0) >= 2 for _m, _o, pg in ranks
    )


def test_zero_identical_batches_match_lone_trajectory(monkeypatch) -> None:
    """World-size independence of the math: two replicas feeding identical
    batches commit the exact trajectory of a lone replica ((g+g)/2 == g in
    f32), bitwise."""
    tx = optax.sgd(0.2, momentum=0.9)
    params = {"w": jnp.arange(10, dtype=jnp.float32) / 3}

    def loss(p, b):
        return jnp.sum((p["w"] - b) ** 2)

    grad = jax.jit(jax.grad(loss))
    batches = [jnp.full((10,), 0.3 * i, jnp.float32) for i in range(3)]

    lone_manager = scripted_manager()
    lone = ZeroOptimizer(lone_manager, tx, params, num_shards=4)
    for b in batches:
        lone_manager.start_quorum()
        lone_manager.wait_quorum()
        assert lone.step(grad(lone.params, b))

    world = _LoopbackWorld(2)
    ranks = [_make_rank(world, r, 2, params, tx, num_shards=4) for r in range(2)]

    def run(r):
        manager, opt, _pg = ranks[r]

        def go():
            for b in batches:
                manager.start_quorum()
                manager.wait_quorum()
                assert opt.step(grad(opt.params, b))
            return np.asarray(opt.params["w"])

        return go

    results = _parallel([run(r) for r in range(2)])
    np.testing.assert_array_equal(results[0], results[1])
    np.testing.assert_array_equal(results[0], np.asarray(lone.params["w"]))


def test_zero_rebalance_shrink_then_grow_moves_only_needed_shards() -> None:
    """The elasticity protocol end to end on the loopback wire: shrink
    re-owns the dead replica's shards (reinit counter moves — masters
    re-pack from committed params), grow hands the joiner exactly its
    block over the PG (moved counter), and params stay bitwise identical
    throughout."""
    tx = optax.adam(0.05)
    params = {"w": jnp.arange(16, dtype=jnp.float32) / 5}

    def loss(p, b):
        return jnp.sum((p["w"] - b) ** 2)

    grad = jax.jit(jax.grad(loss))

    # Phase 1: two replicas, two steps.
    world = _LoopbackWorld(2)
    ranks = [_make_rank(world, r, 2, params, tx, num_shards=4) for r in range(2)]

    def run_phase(ranks, batches, quorum_id):
        def make(r):
            manager, opt, _pg = ranks[r]
            manager._client._quorum.return_value = make_quorum(
                quorum_id=quorum_id,
                replica_rank=r,
                replica_world_size=len(ranks),
                max_rank=r,
                max_world_size=len(ranks),
            )

            def go():
                for b in batches:
                    manager.start_quorum()
                    manager.wait_quorum()
                    assert opt.step(grad(opt.params, b))
                return np.asarray(opt.params["w"])

            return go

        return _parallel([make(r) for r in range(len(ranks))])

    batches1 = [jnp.full((16,), 0.2 * i, jnp.float32) for i in range(2)]
    run_phase(ranks, batches1, quorum_id=1)
    m0, opt0, _pg0 = ranks[0]
    assert sorted(opt0.opt_state.held) == [0, 1]

    # Phase 2: replica 1 dies. Survivor re-owns everything; shards 2, 3
    # were lost with their holder -> deterministic reconstruction.
    reinits_before = metrics.counter_total("tpuft_zero_shard_reinits_total")
    lone_world = _LoopbackWorld(1)
    opt0.manager._pg._world = lone_world  # type: ignore[attr-defined]
    opt0.manager._pg._rank = 0
    m0._client._quorum.return_value = make_quorum(
        quorum_id=2, replica_rank=0, replica_world_size=1,
        max_rank=0, max_world_size=1,
    )
    for b in [jnp.full((16,), 0.5, jnp.float32)]:
        m0.start_quorum()
        m0.wait_quorum()
        assert opt0.step(grad(opt0.params, b))
    assert sorted(opt0.opt_state.held) == [0, 1, 2, 3]
    reinits = metrics.counter_total("tpuft_zero_shard_reinits_total") - reinits_before
    assert reinits == 2  # exactly the dead replica's shards

    # Phase 3: a fresh replica joins (healed params via the checkpoint
    # path, shard states skipped); re-balance moves exactly its block.
    moved_before = metrics.counter_total("tpuft_zero_shards_moved_total")
    grow_world = _LoopbackWorld(2)
    opt0.manager._pg._world = grow_world
    joiner_manager, joiner, _jpg = _make_rank(
        grow_world, 1, 2, params, tx, num_shards=4, quorum_id=3
    )
    # Simulated heal: adopt the survivor's params + accounting, shard
    # payloads skipped (the skip_parts path) — then balance on the wire.
    donor_payload = opt0._state_dict()
    donor_payload = {
        "params": donor_payload["params"],
        "zero": donor_payload["zero"],
        "shards": {name: None for name in donor_payload["shards"]},
    }
    joiner._load_state_dict(donor_payload)
    joiner_manager.load_state_dict(m0.state_dict())
    m0._client._quorum.return_value = make_quorum(
        quorum_id=3, replica_rank=0, replica_world_size=2,
        max_rank=0, max_world_size=2,
    )

    def run2(r, ranks2):
        manager, opt = ranks2[r]

        def go():
            for i in range(2):
                manager.start_quorum()
                manager.wait_quorum()
                b = jnp.full((16,), 0.1 * (i + r), jnp.float32)
                assert opt.step(grad(opt.params, b))
            return np.asarray(opt.params["w"]), sorted(opt.opt_state.held)

        return go

    ranks2 = [(m0, opt0), (joiner_manager, joiner)]
    results = _parallel([run2(r, ranks2) for r in range(2)])
    np.testing.assert_array_equal(results[0][0], results[1][0])
    assert results[0][1] == [0, 1] and results[1][1] == [2, 3]
    moved = metrics.counter_total("tpuft_zero_shards_moved_total") - moved_before
    assert moved == 2  # ONLY the joiner's block crossed the wire


# ---------------------------------------------------------------------------
# shard-addressable heal (transport parts + manager filter)
# ---------------------------------------------------------------------------


def test_transport_parts_roundtrip_and_skip(tmp_path) -> None:
    manager = scripted_manager()
    opt = ZeroOptimizer(
        manager, optax.adam(0.1), {"w": jnp.arange(20, dtype=jnp.float32)},
        num_shards=4,
    )
    step_fn = opt.make_step_fn(lambda p, b: jnp.sum((p["w"] - b) ** 2))
    for i in range(2):
        step_fn(jnp.full((20,), float(i), jnp.float32))

    donor = HTTPTransport(timeout=10.0, num_chunks=2)
    joiner = HTTPTransport(timeout=10.0)
    try:
        state = {"user": {"zero": opt._state_dict()}, "tpuft": manager.state_dict()}
        donor.send_checkpoint([1], step=2, state_dict=state, timeout=10.0,
                              quorum_id=7)
        addr = donor.metadata()

        full = joiner.recv_checkpoint(0, addr, 2, 10.0, quorum_id=7)
        payload = full["user"]["zero"]["shards"][shard_part_name(0)]
        assert payload is not None and payload["master"] is not None

        skip = {shard_part_name(s) for s in range(4)}
        saved_before = metrics.counter_total("tpuft_zero_heal_bytes_saved_total")
        partial = joiner.recv_checkpoint(
            0, addr, 2, 10.0, quorum_id=7, skip_parts=skip
        )
        saved = (
            metrics.counter_total("tpuft_zero_heal_bytes_saved_total")
            - saved_before
        )
        assert saved > 0
        skipped = partial["user"]["zero"]["shards"][shard_part_name(0)]
        assert skipped is not None and skipped["master"] is None
        np.testing.assert_array_equal(
            np.asarray(partial["user"]["zero"]["params"]["w"]),
            np.asarray(full["user"]["zero"]["params"]["w"]),
        )

        # The joiner-side load treats skipped shards as absent and forces
        # a re-balance; params land exactly.
        manager2 = scripted_manager()
        healed = ZeroOptimizer(
            manager2, optax.adam(0.1), {"w": jnp.zeros(20, jnp.float32)},
            num_shards=4,
        )
        healed._load_state_dict(partial["user"]["zero"])
        assert healed.opt_state.held == {}
        assert healed.opt_state.balance_key is None
        np.testing.assert_array_equal(
            np.asarray(healed.params["w"]), np.asarray(opt.params["w"])
        )
    finally:
        donor.shutdown()
        joiner.shutdown()


def test_transport_part_chunks_fetch_measurably_less() -> None:
    """Acceptance pin: a skip-all-shards heal fetches measurably fewer
    bytes than the full checkpoint (the ~1/N heal-payload claim at its
    strongest — adam carries 2x moments + the f32 masters)."""
    manager = scripted_manager()
    opt = ZeroOptimizer(
        manager, optax.adam(0.1),
        {"w": jnp.arange(4096, dtype=jnp.float32)}, num_shards=4,
    )
    step_fn = opt.make_step_fn(lambda p, b: jnp.sum((p["w"] - b) ** 2))
    step_fn(jnp.zeros(4096, jnp.float32))
    donor = HTTPTransport(timeout=10.0)
    try:
        state = {"user": {"zero": opt._state_dict()}, "tpuft": manager.state_dict()}
        donor.send_checkpoint([1], step=1, state_dict=state, timeout=10.0)
        staged = donor._staged
        total = sum(c.total_size for c in staged.chunks)
        part_bytes = sum(info["nbytes"] for info in staged.parts.values())
        assert len(staged.parts) == 4
        # Shard parts (masters + adam moments, all f32) dominate: the
        # skip-all heal moves less than half the full payload.
        assert part_bytes > total / 2
    finally:
        donor.shutdown()


def test_manager_passes_skip_parts_to_transport() -> None:
    manager, client, pg, transport = make_manager()
    manager.register_heal_parts_filter(lambda: {shard_part_name(0)})
    manager.register_heal_parts_filter(lambda: {shard_part_name(1)})
    manager.register_heal_parts_filter(lambda: (_ for _ in ()).throw(RuntimeError))
    assert manager._heal_skip_parts() == {shard_part_name(0), shard_part_name(1)}

    client._quorum.return_value = make_quorum(
        quorum_id=3, replica_rank=1, replica_world_size=2, heal=True,
        max_step=5, max_world_size=1, max_rank=None,
        recover_src_manager_address="fake:1", recover_src_replica_rank=0,
    )
    pg.errored.return_value = None
    transport.recv_checkpoint.return_value = {
        "user": {"model": {"w": np.ones(2)}},
        "tpuft": {"step": 5, "batches_committed": 5},
    }
    from unittest.mock import patch

    with patch("torchft_tpu.manager.ManagerClient") as client_cls:
        client_cls.return_value._checkpoint_metadata.return_value = "http://d:1"
        manager.start_quorum()
        manager.wait_quorum()
    assert transport.recv_checkpoint.call_count == 1
    kwargs = transport.recv_checkpoint.call_args.kwargs
    assert kwargs["skip_parts"] == {shard_part_name(0), shard_part_name(1)}


# ---------------------------------------------------------------------------
# plumbing satellites
# ---------------------------------------------------------------------------


def test_align_opt_state_passes_sharded_leaves_through() -> None:
    """_align_opt_state must treat opaque sharded containers (ZeroState)
    and non-array leaves as pass-through, aligning only jax.Array moments;
    single-device states come back unchanged."""
    params = {"w": jnp.ones((4,), jnp.float32)}
    manager = scripted_manager()
    opt = ZeroOptimizer(manager, optax.adam(0.1), params, num_shards=2)
    state = opt.opt_state
    aligned = _align_opt_state(state, params)
    assert aligned is state  # opaque container untouched

    tx = optax.adam(0.1)
    plain = tx.init(params)
    aligned = _align_opt_state(plain, params)
    chex_leaves = jax.tree_util.tree_leaves(aligned)
    assert len(chex_leaves) == len(jax.tree_util.tree_leaves(plain))


def test_zero_coexists_with_local_sgd_registration() -> None:
    """DiLoCo/LocalSGD registration composes: distinct manager state-dict
    keys, both serialize into one checkpoint."""
    from torchft_tpu.local_sgd import LocalSGD

    manager = scripted_manager()
    zero = ZeroOptimizer(
        manager, optax.sgd(0.1), {"w": jnp.ones(4, jnp.float32)},
        num_shards=2, register_key="zero_outer",
    )
    local = LocalSGD(
        manager, optax.sgd(0.1), {"v": jnp.ones(3, jnp.float32)}, sync_every=2,
    )
    state = manager._manager_state_dict()
    assert {"zero_outer", "local_sgd"} <= set(state["user"])
    assert shard_part_name(0) in state["user"]["zero_outer"]["shards"]


def test_zero_num_shards_mismatch_rejected() -> None:
    manager = scripted_manager()
    opt = ZeroOptimizer(
        manager, optax.sgd(0.1), {"w": jnp.ones(4, jnp.float32)}, num_shards=2
    )
    payload = opt._state_dict()
    payload["zero"]["num_shards"] = 3
    with pytest.raises(ValueError, match="num_shards"):
        opt._load_state_dict(payload)


def test_zero_quantize_flag_warns_and_runs_f32(monkeypatch, caplog) -> None:
    manager = scripted_manager()
    manager.is_lone_replica = lambda: False
    opt = ZeroOptimizer(
        manager, optax.sgd(0.1), {"w": jnp.ones(4, jnp.float32)}, num_shards=2
    )
    import logging

    import torchft_tpu.zero as zero_mod

    monkeypatch.setattr(zero_mod, "_WARNED_QUANTIZE", [False])
    with caplog.at_level(logging.WARNING, logger="torchft_tpu.zero"):
        step_fn = opt.make_step_fn(
            lambda p, b: jnp.sum(p["w"] * b), should_quantize=True
        )
        manager.start_quorum()
        step_fn(jnp.ones(4, jnp.float32))
    assert any("should_quantize" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# quantized shard wire (TPUFT_ZERO_CODEC)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", ["int8", "fp8"])
def test_zero_codec_multi_rank_bitwise_identical_params(monkeypatch, codec) -> None:
    """THE acceptance drill: with the shard wire quantized, every
    committed step still ends with bitwise-identical params on every
    replica — each master range is encoded once by its owner and EVERY
    replica (owner included) dequantizes the same allgather bytes — and
    the grad reduce actually rode the quantized alltoall pipeline."""
    monkeypatch.setenv("TPUFT_ZERO_CODEC", codec)
    tx = optax.adam(0.05)
    params = {"w": jnp.arange(4096, dtype=jnp.float32).reshape(64, 64) / 977}

    def loss(p, b):
        return jnp.sum((p["w"] - b) ** 2)

    grad = jax.jit(jax.grad(loss))
    world = _LoopbackWorld(2)
    ranks = [_make_rank(world, r, 2, params, tx, num_shards=4) for r in range(2)]

    def run(r):
        manager, opt, _pg = ranks[r]

        def go():
            for step in range(3):
                manager.start_quorum()
                manager.wait_quorum()
                batch = jnp.full((64, 64), 0.1 * (step + r), jnp.float32)
                assert opt.step(grad(opt.params, batch))
            return np.asarray(opt.params["w"])

        return go

    results = _parallel([run(r) for r in range(2)])
    np.testing.assert_array_equal(results[0], results[1])
    # The quantized wire was actually used: alltoall (the quantized
    # allreduce's exchange) ran, the f32 reduce_scatter fast path did not.
    for _m, _o, pg in ranks:
        assert pg.op_counts.get("alltoall", 0) >= 3
        assert pg.op_counts.get("reduce_scatter", 0) == 0
    # And the byte accounting moved: encoded bytes a fraction of raw.
    pre = metrics.counter_total(
        "tpuft_codec_bytes_pre_total", wire="zero", codec=codec
    )
    post = metrics.counter_total(
        "tpuft_codec_bytes_post_total", wire="zero", codec=codec
    )
    assert pre > 0 and post > 0 and post < pre * 0.35


def test_zero_codec_pipelined_ordering_matches_strict(monkeypatch) -> None:
    """Bitwise identity survives the commit orderings under the quantized
    wire: a depth-2 pipelined 2-rank run and a strict-ordered 2-rank run
    commit the IDENTICAL param trajectory (same batches, same codec)."""
    monkeypatch.setenv("TPUFT_ZERO_CODEC", "int8")
    tx = optax.sgd(0.2, momentum=0.9)
    params = {"w": jnp.arange(2048, dtype=jnp.float32) / 311}

    def loss(p, b):
        return jnp.sum((p["w"] - b) ** 2)

    batches = [jnp.full((2048,), 0.25 * i, jnp.float32) for i in range(4)]

    def run_world(mode):
        if mode == "strict":
            monkeypatch.setenv("TPUFT_STRICT_COMMIT", "1")
            mk = {}
        else:
            monkeypatch.delenv("TPUFT_STRICT_COMMIT", raising=False)
            mk = {"commit_pipeline_depth": 2}
        world = _LoopbackWorld(2)
        ranks = [
            _make_rank(world, r, 2, params, tx, num_shards=4, **mk)
            for r in range(2)
        ]

        def run(r):
            manager, opt, _pg = ranks[r]
            step_fn = opt.make_step_fn(loss)

            def go():
                for b in batches:
                    step_fn(b)
                # None in strict mode (no window), True once drained.
                assert opt.flush_pipeline() in (None, True)
                return np.asarray(opt.params["w"])

            return go

        results = _parallel([run(r) for r in range(2)])
        np.testing.assert_array_equal(results[0], results[1])
        return results[0]

    w_strict = run_world("strict")
    w_pipe = run_world("pipelined")
    np.testing.assert_array_equal(w_strict, w_pipe)


def test_zero_codec_kill_rejoin_rebalance_bitwise(monkeypatch) -> None:
    """Kill/rejoin under the quantized wire: the survivor re-owns the dead
    holder's shards, a fresh joiner heals params (skip_parts) and
    re-balances its block from the survivor — and every subsequent
    committed step is bitwise identical across both replicas, because
    params always come from the shared encoded allgather payload."""
    monkeypatch.setenv("TPUFT_ZERO_CODEC", "int8")
    tx = optax.adam(0.05)
    params = {"w": jnp.arange(4096, dtype=jnp.float32) / 631}

    def loss(p, b):
        return jnp.sum((p["w"] - b) ** 2)

    grad = jax.jit(jax.grad(loss))
    world = _LoopbackWorld(2)
    ranks = [_make_rank(world, r, 2, params, tx, num_shards=4) for r in range(2)]

    def run_phase(pairs, batches, quorum_id, world_size):
        def make(i):
            manager, opt = pairs[i]
            manager._client._quorum.return_value = make_quorum(
                quorum_id=quorum_id,
                replica_rank=i,
                replica_world_size=world_size,
                max_rank=i,
                max_world_size=world_size,
            )

            def go():
                for b in batches:
                    manager.start_quorum()
                    manager.wait_quorum()
                    assert opt.step(grad(opt.params, b))
                return np.asarray(opt.params["w"])

            return go

        return _parallel([make(i) for i in range(len(pairs))])

    batches1 = [jnp.full((4096,), 0.2 * i, jnp.float32) for i in range(2)]
    pairs = [(m, o) for m, o, _pg in ranks]
    results = run_phase(pairs, batches1, quorum_id=1, world_size=2)
    np.testing.assert_array_equal(results[0], results[1])

    # Replica 1 dies; the survivor re-owns everything and keeps stepping.
    m0, opt0 = pairs[0]
    lone_world = _LoopbackWorld(1)
    m0._pg._world = lone_world  # type: ignore[attr-defined]
    m0._pg._rank = 0
    m0._client._quorum.return_value = make_quorum(
        quorum_id=2, replica_rank=0, replica_world_size=1,
        max_rank=0, max_world_size=1,
    )
    m0.start_quorum()
    m0.wait_quorum()
    assert opt0.step(grad(opt0.params, jnp.full((4096,), 0.5, jnp.float32)))
    assert sorted(opt0.opt_state.held) == [0, 1, 2, 3]

    # A fresh joiner rejoins: params via the (skip-parts) heal path,
    # shard states via re-balance — then two more lockstep steps.
    grow_world = _LoopbackWorld(2)
    m0._pg._world = grow_world
    joiner_manager, joiner, _jpg = _make_rank(
        grow_world, 1, 2, params, tx, num_shards=4, quorum_id=3
    )
    donor_payload = opt0._state_dict()
    donor_payload = {
        "params": donor_payload["params"],
        "zero": donor_payload["zero"],
        "shards": {name: None for name in donor_payload["shards"]},
    }
    joiner._load_state_dict(donor_payload)
    joiner_manager.load_state_dict(m0.state_dict())
    batches2 = [jnp.full((4096,), 0.15 * i, jnp.float32) for i in range(2)]
    results2 = run_phase(
        [(m0, opt0), (joiner_manager, joiner)], batches2,
        quorum_id=3, world_size=2,
    )
    np.testing.assert_array_equal(results2[0], results2[1])
