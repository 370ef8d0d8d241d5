"""Manager state-machine tests against mocked coordination clients.

Parity target: the reference's manager_test.py — each test scripts a
QuorumResult on a mocked ManagerClient and asserts the per-step state
machine: configure-on-quorum-change, participation math, healing sync/async,
error funnel, commit/max_retries, FIXED_WITH_SPARES.
"""

import threading
from typing import Optional
from unittest.mock import MagicMock, create_autospec, patch

import numpy as np
import pytest

from torchft_tpu.checkpointing.transport import CheckpointTransport
from torchft_tpu.coordination import QuorumResult
from torchft_tpu.manager import ExceptionWithTraceback, Manager, WorldSizeMode
from torchft_tpu.parallel.process_group import ProcessGroup, ProcessGroupDummy
from torchft_tpu.work import _DummyWork


class _FakeStore:
    def __init__(self) -> None:
        self.data = {
            "manager_addr": b"fake:1234",
            "replica_id": b"test_replica:uuid",
        }

    def get(self, key: str, timeout: float = 0, wait: bool = True):
        return self.data.get(key)

    def set(self, key: str, value: bytes, timeout: float = 0) -> None:
        self.data[key] = value


def make_quorum(
    quorum_id: int = 1,
    replica_rank: int = 0,
    replica_world_size: int = 2,
    heal: bool = False,
    max_step: int = 0,
    max_rank: Optional[int] = None,
    max_world_size: int = 2,
    recover_src_manager_address: str = "",
    recover_src_replica_rank: Optional[int] = None,
    recover_dst_replica_ranks=(),
    quorum=None,
) -> QuorumResult:
    if max_rank is None and not heal:
        max_rank = replica_rank
    return QuorumResult(
        quorum_id=quorum_id,
        replica_rank=replica_rank,
        replica_world_size=replica_world_size,
        recover_src_manager_address=recover_src_manager_address,
        recover_src_replica_rank=recover_src_replica_rank,
        recover_dst_replica_ranks=list(recover_dst_replica_ranks),
        store_address="store:0",
        max_step=max_step,
        max_rank=max_rank,
        max_world_size=max_world_size,
        heal=heal,
        quorum=quorum,
    )


def make_manager(
    pg=None,
    use_async_quorum: bool = False,
    min_replica_size: int = 2,
    world_size_mode: WorldSizeMode = WorldSizeMode.DYNAMIC,
    max_retries: Optional[int] = None,
    **kwargs,
):
    pg = pg if pg is not None else create_autospec(ProcessGroup, instance=True)
    transport = kwargs.pop("checkpoint_transport", None)
    if transport is None:
        transport = create_autospec(CheckpointTransport, instance=True)
        transport.metadata.return_value = "http://fake:0"
    with patch("torchft_tpu.manager.ManagerClient", autospec=True) as client_cls:
        manager = Manager(
            pg=pg,
            min_replica_size=min_replica_size,
            store=_FakeStore(),
            store_addr="store:0",
            use_async_quorum=use_async_quorum,
            group_rank=1,  # avoid spawning a native ManagerServer
            group_world_size=2,
            world_size_mode=world_size_mode,
            checkpoint_transport=transport,
            max_retries=max_retries,
            timeout=5.0,
            quorum_timeout=5.0,
            **kwargs,
        )
    manager.register_state_dict_fn(
        "model",
        load_state_dict=MagicMock(),
        state_dict=lambda: {"w": np.ones(2)},
    )
    return manager, manager._client, pg, transport


def test_quorum_configures_pg_and_tracks_participation() -> None:
    manager, client, pg, transport = make_manager()
    client._quorum.return_value = make_quorum(
        quorum_id=7, replica_rank=1, replica_world_size=3, max_rank=1, max_world_size=3
    )
    pg.errored.return_value = None

    manager.start_quorum()
    pg.configure.assert_called_once()
    store_addr, replica_id, rank, world = pg.configure.call_args[0]
    assert store_addr == "store:0/tpuft/7/1"
    assert rank == 1 and world == 3
    assert manager.num_participants() == 3
    assert manager.participating_rank() == 1
    assert manager.is_participating()

    # Same quorum id next step: no reconfigure.
    manager.start_quorum()
    assert pg.configure.call_count == 1


def test_allreduce_averages_by_participants() -> None:
    manager, client, _, _ = make_manager(pg=ProcessGroupDummy())
    client._quorum.return_value = make_quorum(replica_world_size=2, max_world_size=2)
    client.should_commit.return_value = True
    manager.start_quorum()

    # Dummy PG echoes the input, so AVG == input / num_participants.
    out = manager.allreduce(np.array([4.0, 8.0])).wait()
    np.testing.assert_array_equal(out, np.array([2.0, 4.0]))

    tree = {"a": np.array([2.0]), "b": [np.array([6.0])]}
    out_tree = manager.allreduce_pytree(tree).wait()
    np.testing.assert_array_equal(out_tree["a"], np.array([1.0]))
    np.testing.assert_array_equal(out_tree["b"][0], np.array([3.0]))


def test_allreduce_after_error_is_noop() -> None:
    manager, client, _, _ = make_manager(pg=ProcessGroupDummy())
    client._quorum.return_value = make_quorum()
    manager.start_quorum()
    manager.report_error(RuntimeError("boom"))
    work = manager.allreduce(np.array([1.0]))
    assert isinstance(work, _DummyWork)
    np.testing.assert_array_equal(work.wait(), np.array([1.0]))


def test_allreduce_error_reports_and_returns_default() -> None:
    pg = create_autospec(ProcessGroup, instance=True)
    pg.errored.return_value = None
    pg.allreduce.side_effect = RuntimeError("collective failed")
    manager, client, _, _ = make_manager(pg=pg)
    client._quorum.return_value = make_quorum()
    manager.start_quorum()
    work = manager.allreduce(np.array([1.0, 2.0]))
    np.testing.assert_array_equal(work.wait(), np.array([1.0, 2.0]))
    assert manager.errored() is not None


def test_healing_async_skips_participation_and_zeroes_grads() -> None:
    manager, client, pg, transport = make_manager(
        pg=ProcessGroupDummy(), use_async_quorum=True
    )
    client._quorum.return_value = make_quorum(
        quorum_id=2,
        replica_rank=1,
        replica_world_size=2,
        heal=True,
        max_step=5,
        max_rank=None,
        max_world_size=1,
        recover_src_manager_address="donor:1",
        recover_src_replica_rank=0,
    )
    client._checkpoint_metadata.return_value = "http://donor:0"
    client.should_commit.return_value = True
    transport.recv_checkpoint.return_value = {
        "user": {"model": {"w": np.full(2, 9.0)}},
        "tpuft": {"step": 5, "batches_committed": 10},
    }

    with patch("torchft_tpu.manager.ManagerClient", autospec=True) as primary_cls:
        primary_cls.return_value._checkpoint_metadata.return_value = "http://donor:0"
        manager.start_quorum()
        manager.wait_quorum()

    assert manager._healing
    assert not manager.is_participating()
    assert manager.num_participants() == 1
    # Healing replica contributes zeros.
    out = manager.allreduce(np.array([3.0, 3.0])).wait()
    np.testing.assert_array_equal(out, np.zeros(2))
    # Manager accounting restored from the donor.
    assert manager.current_step() == 5

    # should_commit applies the pending user state dict.
    load_fn = manager._load_state_dict_fns["model"]
    assert manager.should_commit()
    load_fn.assert_called_once()
    np.testing.assert_array_equal(load_fn.call_args[0][0]["w"], np.full(2, 9.0))
    assert manager.current_step() == 6


def test_healing_sync_applies_before_return() -> None:
    manager, client, pg, transport = make_manager(
        pg=ProcessGroupDummy(), use_async_quorum=False
    )
    client._quorum.return_value = make_quorum(
        quorum_id=3,
        replica_rank=1,
        replica_world_size=2,
        heal=True,
        max_step=2,
        recover_src_manager_address="donor:1",
        recover_src_replica_rank=0,
    )
    transport.recv_checkpoint.return_value = {
        "user": {"model": {"w": np.zeros(2)}},
        "tpuft": {"step": 2, "batches_committed": 4},
    }
    with patch("torchft_tpu.manager.ManagerClient", autospec=True):
        manager.start_quorum()
    # Sync mode: state applied eagerly, replica participates this step.
    assert not manager._healing
    load_fn = manager._load_state_dict_fns["model"]
    load_fn.assert_called_once()
    assert manager.is_participating()


def test_donor_sends_checkpoint() -> None:
    manager, client, pg, transport = make_manager(pg=ProcessGroupDummy())
    client._quorum.return_value = make_quorum(recover_dst_replica_ranks=[1])
    manager.start_quorum()
    manager.wait_quorum()
    transport.send_checkpoint.assert_called_once()
    kwargs = transport.send_checkpoint.call_args[1]
    assert kwargs["dst_ranks"] == [1]
    assert "user" in kwargs["state_dict"] and "tpuft" in kwargs["state_dict"]


def test_should_commit_false_without_enough_replicas() -> None:
    manager, client, _, _ = make_manager(pg=ProcessGroupDummy(), min_replica_size=2)
    client._quorum.return_value = make_quorum(
        replica_world_size=1, max_world_size=1, replica_rank=0, max_rank=0
    )
    client.should_commit.side_effect = lambda rank, step, vote, timeout: vote
    manager.start_quorum()
    assert not manager.should_commit()
    assert manager.current_step() == 0


def test_pg_errored_blocks_commit() -> None:
    pg = ProcessGroupDummy()
    pg._errored = RuntimeError("pg broke")
    manager, client, _, _ = make_manager(pg=pg)
    client._quorum.return_value = make_quorum()
    client.should_commit.side_effect = lambda rank, step, vote, timeout: vote
    manager.start_quorum()
    assert not manager.should_commit()
    assert manager.errored() is not None


def test_commit_success_advances_step_and_batches() -> None:
    manager, client, _, _ = make_manager(pg=ProcessGroupDummy())
    client._quorum.return_value = make_quorum(replica_world_size=2, max_world_size=2)
    client.should_commit.side_effect = lambda rank, step, vote, timeout: vote
    manager.start_quorum()
    assert manager.should_commit()
    assert manager.current_step() == 1
    assert manager.batches_committed() == 2


def test_max_retries_raises_after_consecutive_failures() -> None:
    manager, client, _, _ = make_manager(pg=ProcessGroupDummy(), max_retries=1)
    client._quorum.return_value = make_quorum()
    client.should_commit.return_value = False
    manager.start_quorum()
    assert not manager.should_commit()  # failure 1
    manager.start_quorum()
    with pytest.raises(RuntimeError, match="max_retries"):
        manager.should_commit()  # failure 2 > max_retries=1


def test_fixed_with_spares_zeroes_spare() -> None:
    manager, client, _, _ = make_manager(
        pg=ProcessGroupDummy(),
        min_replica_size=2,
        world_size_mode=WorldSizeMode.FIXED_WITH_SPARES,
    )
    # This replica is rank 2 of 3 with min size 2: it is a spare.
    client._quorum.return_value = make_quorum(
        replica_rank=2, replica_world_size=3, max_rank=2, max_world_size=3
    )
    manager.start_quorum()
    assert manager.num_participants() == 2
    assert manager.participating_rank() is None
    assert not manager.is_participating()
    out = manager.allreduce(np.array([5.0, 5.0])).wait()
    # Spare contributes zeros (dummy echoes), averaged by 2.
    np.testing.assert_array_equal(out, np.zeros(2))


def test_wrap_work_swallows_error_into_default() -> None:
    manager, client, _, _ = make_manager(pg=ProcessGroupDummy())
    client._quorum.return_value = make_quorum()
    manager.start_quorum()
    from concurrent.futures import Future

    from torchft_tpu.work import Work

    fut: Future = Future()
    wrapped = manager.wrap_work(Work(fut), default="fallback")
    fut.set_exception(RuntimeError("inner"))
    assert wrapped.wait(5) == "fallback"
    assert isinstance(manager.errored(), ExceptionWithTraceback)


def test_wrap_work_timeout() -> None:
    manager, client, _, _ = make_manager(pg=ProcessGroupDummy())
    client._quorum.return_value = make_quorum()
    manager.start_quorum()
    from concurrent.futures import Future

    from torchft_tpu.work import Work

    fut: Future = Future()  # never resolves
    wrapped = manager.wrap_work(Work(fut), default="timed-out", timeout=0.1)
    assert wrapped.wait(5) == "timed-out"
    assert manager.errored() is not None


def test_state_dict_roundtrip() -> None:
    manager, client, _, _ = make_manager(pg=ProcessGroupDummy())
    sd = manager.state_dict()
    assert sd == {"step": 0, "batches_committed": 0}
    manager.load_state_dict({"step": 42, "batches_committed": 84})
    assert manager.current_step() == 42
    assert manager.batches_committed() == 84


def test_quorum_happy_timeouts() -> None:
    """Per-call timeouts thread through to the coordination RPCs (parity:
    manager_test.py:625-652): an explicit start_quorum timeout reaches
    the quorum RPC, the ctor timeout is the should_commit default, and an
    explicit should_commit timeout overrides it."""
    manager, client, _, _ = make_manager(pg=ProcessGroupDummy(), min_replica_size=1)
    client._quorum.return_value = make_quorum()
    client.should_commit.side_effect = lambda rank, step, vote, timeout: vote

    manager.start_quorum(timeout=12.5)
    assert client._quorum.call_args.kwargs["timeout"] == 12.5
    manager.start_quorum()  # falls back to the ctor quorum_timeout
    assert client._quorum.call_args.kwargs["timeout"] == 5.0

    manager.should_commit()
    assert client.should_commit.call_args.kwargs["timeout"] == 5.0
    manager.should_commit(timeout=3.25)
    assert client.should_commit.call_args.kwargs["timeout"] == 3.25


def test_quorum_skip_init() -> None:
    """init_sync=False threads through the quorum request (parity:
    manager_test.py:653-681 — the server-side plan then skips the step-0
    parameter mosaic)."""
    manager, client, _, _ = make_manager(
        pg=ProcessGroupDummy(), min_replica_size=1, init_sync=False
    )
    client._quorum.return_value = make_quorum()
    manager.start_quorum()
    assert client._quorum.call_args.kwargs["init_sync"] is False

    default_manager, default_client, _, _ = make_manager(
        pg=ProcessGroupDummy(), min_replica_size=1
    )
    default_client._quorum.return_value = make_quorum()
    default_manager.start_quorum()
    assert default_client._quorum.call_args.kwargs["init_sync"] is True


def test_quorum_checkpoint_errors() -> None:
    """A failing checkpoint fetch during healing funnels into report_error
    and blocks the commit instead of raising through the train loop
    (parity: manager_test.py:682-724)."""
    manager, client, _, transport = make_manager(
        pg=ProcessGroupDummy(), min_replica_size=1
    )
    client._quorum.return_value = make_quorum(
        heal=True,
        max_step=3,
        recover_src_manager_address="fake:1",
        recover_src_replica_rank=1,
    )
    transport.recv_checkpoint.side_effect = RuntimeError("fetch failed")
    with patch(
        "torchft_tpu.manager.ManagerClient", autospec=True
    ):  # the recovery-source client constructed inside _async_quorum
        manager.start_quorum()
    assert manager.errored() is not None
    client.should_commit.side_effect = lambda rank, step, vote, timeout: vote
    assert manager.should_commit() is False


def test_quorum_configure_errors() -> None:
    """A failing pg.configure funnels into report_error, leaves quorum_id
    unchanged (so the next quorum retries the reconfigure), and blocks the
    commit (parity: manager_test.py:725-754)."""
    pg = create_autospec(ProcessGroup, instance=True)
    pg.configure.side_effect = RuntimeError("configure failed")
    pg.errored.return_value = None
    manager, client, _, _ = make_manager(pg=pg, min_replica_size=1)
    client._quorum.return_value = make_quorum(quorum_id=7)
    manager.start_quorum()
    assert manager.errored() is not None
    assert manager._quorum_id != 7  # retried on the next quorum round
    client.should_commit.side_effect = lambda rank, step, vote, timeout: vote
    assert manager.should_commit() is False


def test_should_commit_async_overlaps_and_heals() -> None:
    """should_commit_async runs the full barrier on the manager's executor:
    the returned future resolves to the commit verdict, a pending heal is
    applied during resolution (not before), and step accounting matches
    the synchronous path."""
    import time as _time

    manager, client, _, transport = make_manager(
        pg=ProcessGroupDummy(), min_replica_size=1
    )
    client._quorum.return_value = make_quorum()
    manager.start_quorum()

    release = threading.Event()

    def slow_commit(rank, step, vote, timeout):
        release.wait(timeout=10)
        return vote

    client.should_commit.side_effect = slow_commit
    future = manager.should_commit_async()
    # The caller thread is free while the RPC is parked on the executor.
    assert not future.done()
    release.set()
    assert future.result(timeout=10) is True
    assert manager.current_step() == 1

    # A heal staged before the barrier is applied during resolution.
    client._quorum.return_value = make_quorum(
        heal=True,
        max_step=5,
        recover_src_manager_address="fake:1",
        recover_src_replica_rank=1,
    )
    healed = {"user": {"model": {"w": np.full(2, 9.0)}}, "tpuft": {"step": 5, "batches_committed": 5}}
    transport.recv_checkpoint.return_value = healed
    client.should_commit.side_effect = lambda rank, step, vote, timeout: vote
    with patch("torchft_tpu.manager.ManagerClient", autospec=True):
        manager.start_quorum(allow_heal=True)
    # Sync-mode quorum applies the heal eagerly at start_quorum; the async
    # barrier must still see the healed step and advance it.
    load_fn = manager._load_state_dict_fns["model"]
    load_fn.assert_called_once()
    assert manager.current_step() == 5
    assert manager.should_commit_async().result(timeout=10) is True
    assert manager.current_step() == 6


def test_start_quorum_drains_unresolved_commit_future() -> None:
    """start_quorum must not wipe the per-step error/heal flags while a
    should_commit_async future is unresolved: it drains the future first so
    the queued barrier votes with THIS step's flags (the ordering contract
    documented on should_commit_async, now enforced rather than advisory)."""
    manager, client, _, _ = make_manager(pg=ProcessGroupDummy(), min_replica_size=1)
    client._quorum.return_value = make_quorum()
    manager.start_quorum()

    manager.report_error(RuntimeError("step math failed"))
    votes = []
    client.should_commit.side_effect = (
        lambda rank, step, vote, timeout: votes.append(vote) or vote
    )

    # Park the single-worker executor so the async barrier stays QUEUED —
    # the dangerous window where a misordered start_quorum used to wipe the
    # flags before the barrier ever read them.
    gate = threading.Event()
    manager._executor.submit(gate.wait, 10)
    future = manager.should_commit_async()

    started = threading.Event()
    finished = threading.Event()

    def second_quorum() -> None:
        started.set()
        manager.start_quorum()
        finished.set()

    t = threading.Thread(target=second_quorum, daemon=True)
    t.start()
    assert started.wait(timeout=5)
    # start_quorum is blocked draining the unresolved commit; the error
    # flag must still be live for the barrier to see.
    assert not finished.wait(timeout=0.5)
    assert manager.errored() is not None
    gate.set()
    t.join(timeout=10)
    assert finished.is_set()
    assert future.done()
    assert future.result() is False  # voted with the real (errored) flags
    assert votes == [False]
    assert manager.current_step() == 0  # the failed commit did not advance


def test_tracked_commit_future_timeout_is_not_consumption() -> None:
    """A result() wait that times out observed nothing: the future must
    stay unconsumed so a later drain still delivers the barrier outcome —
    while a delivered outcome (value or the barrier's own exception) marks
    it consumed."""
    import concurrent.futures

    from torchft_tpu.manager import _TrackedCommitFuture

    pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
    gate = threading.Event()
    try:
        f = _TrackedCommitFuture(pool.submit(gate.wait, 10))
        # py3.10: concurrent.futures.TimeoutError is not yet the builtin.
        with pytest.raises((TimeoutError, concurrent.futures.TimeoutError)):
            f.result(timeout=0.05)
        assert not f.consumed
        gate.set()
        assert f.result(timeout=10) is True
        assert f.consumed

        boom = _TrackedCommitFuture(pool.submit(lambda: 1 / 0))
        with pytest.raises(ZeroDivisionError):
            boom.result(timeout=10)
        assert boom.consumed

        via_exc = _TrackedCommitFuture(pool.submit(lambda: 1 / 0))
        assert isinstance(via_exc.exception(timeout=10), ZeroDivisionError)
        assert via_exc.consumed
    finally:
        gate.set()
        pool.shutdown(wait=False)


def test_start_quorum_propagates_unconsumed_barrier_exception_once() -> None:
    """A barrier exception the caller never observed must surface from the
    drain (else e.g. the max_retries supervisor-restart signal is silently
    dropped) — but one the caller already resolved and handled must NOT
    replay on a later, healthy start_quorum."""
    manager, client, _, _ = make_manager(
        pg=ProcessGroupDummy(), min_replica_size=1, max_retries=0
    )
    client._quorum.return_value = make_quorum()
    client.should_commit.side_effect = lambda rank, step, vote, timeout: False

    # Unconsumed errored future -> the drain raises it.
    manager.start_quorum()
    future = manager.should_commit_async()
    with pytest.raises(RuntimeError, match="max_retries"):
        manager.start_quorum()
    assert future.done()

    # Consumed errored future -> the next start_quorum must NOT replay it.
    manager.start_quorum()
    future = manager.should_commit_async()
    with pytest.raises(RuntimeError, match="max_retries"):
        future.result(timeout=10)
    manager.start_quorum()  # caller handled it; no stale re-raise
    assert manager.errored() is None


def test_commit_pipeline_depth_env_and_validation(monkeypatch) -> None:
    """Depth plumbing: any int >= 0 is a legal window depth (an N-step
    bounded envelope), "auto" selects the adaptive controller starting at
    depth 1, TPUFT_COMMIT_PIPELINE_DEPTH wins over the legacy
    TPUFT_COMMIT_PIPELINE, and junk raises."""
    manager, _, _, _ = make_manager(pg=ProcessGroupDummy())
    assert manager.commit_pipeline_depth == 0
    assert not manager.commit_pipeline_adaptive

    manager, _, _, _ = make_manager(pg=ProcessGroupDummy(), commit_pipeline_depth=1)
    assert manager.commit_pipeline_depth == 1

    manager, _, _, _ = make_manager(pg=ProcessGroupDummy(), commit_pipeline_depth=4)
    assert manager.commit_pipeline_depth == 4

    manager, _, _, _ = make_manager(
        pg=ProcessGroupDummy(), commit_pipeline_depth="auto"
    )
    assert manager.commit_pipeline_adaptive
    assert manager.commit_pipeline_depth == 1  # deepens as evidence arrives

    monkeypatch.setenv("TPUFT_COMMIT_PIPELINE", "1")
    manager, _, _, _ = make_manager(pg=ProcessGroupDummy())
    assert manager.commit_pipeline_depth == 1

    # The new var wins over the legacy one.
    monkeypatch.setenv("TPUFT_COMMIT_PIPELINE_DEPTH", "3")
    manager, _, _, _ = make_manager(pg=ProcessGroupDummy())
    assert manager.commit_pipeline_depth == 3
    monkeypatch.setenv("TPUFT_COMMIT_PIPELINE_DEPTH", "auto")
    manager, _, _, _ = make_manager(pg=ProcessGroupDummy())
    assert manager.commit_pipeline_adaptive
    monkeypatch.delenv("TPUFT_COMMIT_PIPELINE_DEPTH")
    monkeypatch.delenv("TPUFT_COMMIT_PIPELINE")

    with pytest.raises(ValueError, match="commit_pipeline_depth"):
        make_manager(pg=ProcessGroupDummy(), commit_pipeline_depth=-1)
    with pytest.raises(ValueError, match="commit_pipeline_depth"):
        make_manager(pg=ProcessGroupDummy(), commit_pipeline_depth="bogus")


def test_quorum_change_hook_runs_before_reconfigure() -> None:
    """The registered quorum-change hook fires on the quorum thread BEFORE
    pg.configure (the pipelined-commit drain point: no reconfigure — and
    no donor send — while an uncommitted step is in flight), and only when
    the quorum id actually changes. Hook errors funnel into report_error
    instead of aborting the reconfigure."""
    events = []
    pg = create_autospec(ProcessGroup, instance=True)
    pg.errored.return_value = None
    pg.configure.side_effect = lambda *a, **k: events.append("configure")
    manager, client, _, _ = make_manager(pg=pg, min_replica_size=1)
    manager.register_quorum_change_hook(lambda: events.append("drain"))
    client._quorum.return_value = make_quorum(quorum_id=3)

    manager.start_quorum()
    manager.wait_quorum()
    assert events == ["drain", "configure"]

    # Same quorum id: neither fires again.
    manager.start_quorum()
    manager.wait_quorum()
    assert events == ["drain", "configure"]

    # A failing hook reports the error (blocking the commit) but the
    # reconfigure still happens for the new era.
    manager.register_quorum_change_hook(
        lambda: (_ for _ in ()).throw(RuntimeError("drain failed"))
    )
    client._quorum.return_value = make_quorum(quorum_id=4)
    manager.start_quorum()
    manager.wait_quorum()
    assert events == ["drain", "configure", "drain", "configure"]
    assert manager.errored() is not None


def test_allreduce_prequantized_zeroes_spare_contribution() -> None:
    """FIXED_WITH_SPARES: a spare's prequantized payload must contribute
    nothing (scales zeroed) and errors must short-circuit to None."""
    import jax.numpy as jnp

    from torchft_tpu.ops import quantization as q

    manager, client, _, _ = make_manager(
        pg=ProcessGroupDummy(),
        min_replica_size=2,
        world_size_mode=WorldSizeMode.FIXED_WITH_SPARES,
    )
    client._quorum.return_value = make_quorum(
        replica_rank=2, replica_world_size=3, max_rank=2, max_world_size=3
    )
    manager.start_quorum()
    assert not manager.is_participating()

    payload, scales = q.quantize_blocks(np.linspace(-2, 2, 512, dtype=np.float32))
    result = manager.allreduce_prequantized(jnp.asarray(payload), jnp.asarray(scales)).wait()
    out_payload, out_scales = result
    # Spare contribution fully zeroed via scales.
    assert np.all(np.asarray(out_scales) == 0)

    # Errored manager: immediate None without touching the PG.
    manager.report_error(RuntimeError("boom"))
    assert manager.allreduce_prequantized(payload, scales).wait() is None


def test_allreduce_pytree_buckets_mixed_dtypes() -> None:
    """Bucketed pytree sync: multiple dtype buckets reconstruct to the right
    leaves (shapes, dtypes), results don't alias each other, integer leaves
    raise (averaging would silently floor-divide — same contract as the
    scalar allreduce AVG path), and the quantized path stays per-leaf so fp8
    block scales never span parameter boundaries."""
    manager, client, _, _ = make_manager(pg=ProcessGroupDummy(), min_replica_size=1)
    client._quorum.return_value = make_quorum(replica_world_size=2, max_world_size=2)
    manager.start_quorum()

    tree = {
        "w": np.full(5, 4.0, np.float32),
        "b": [np.full(3, 8.0, np.float32)],
        "scalar": np.float64(6.0),
    }
    out = manager.allreduce_pytree(tree).wait()
    np.testing.assert_array_equal(out["w"], np.full(5, 2.0, np.float32))
    np.testing.assert_array_equal(out["b"][0], np.full(3, 4.0, np.float32))
    assert float(out["scalar"]) == 3.0
    assert out["w"].dtype == np.float32

    # Integer leaf: ValueError BEFORE any wire op, step not poisoned.
    with pytest.raises(ValueError, match="floating"):
        manager.allreduce_pytree({"n": np.array([10], np.int64)})
    assert not manager.errored()

    # The check fires before every early return: a LONE replica raises
    # too — otherwise an int leaf would "work" single-replica and start
    # raising only once a second replica joins.
    lone, lone_client, _, _ = make_manager(
        pg=ProcessGroupDummy(), min_replica_size=1
    )
    lone_client._quorum.return_value = make_quorum(
        replica_world_size=1, max_world_size=1
    )
    lone.start_quorum()
    assert lone.is_lone_replica()
    with pytest.raises(ValueError, match="floating"):
        lone.allreduce_pytree({"n": np.array([10], np.int64)})
    # No aliasing between same-bucket leaves.
    out["w"][:] = -1
    np.testing.assert_array_equal(out["b"][0], np.full(3, 4.0, np.float32))

    # Quantized path: per-leaf quantization — a tiny-magnitude leaf next to a
    # huge one must survive (shared-bucket fp8 scales would zero it).
    tree2 = {"big": np.full(512, 300.0, np.float32), "small": np.full(512, 1e-4, np.float32)}
    out2 = manager.allreduce_pytree(tree2, should_quantize=True).wait()
    assert np.all(np.abs(out2["small"]) > 0), "small leaf crushed by shared fp8 scale"
    np.testing.assert_allclose(out2["small"], np.full(512, 5e-5), rtol=0.1)
    np.testing.assert_allclose(out2["big"], np.full(512, 150.0), rtol=0.1)


@pytest.mark.parametrize(
    "depth, adaptive_env, versions_env, want",
    [
        (0, None, None, 1),  # strict: the live committed state, nothing older
        (1, None, None, 2),
        (2, None, None, 3),
        (3, None, None, 4),
        ("auto", None, None, 5),  # DEFAULT_ADAPTIVE_MAX_DEPTH + 1
        ("auto", "2", None, 3),
        (0, None, "3", 3),  # TPUFT_HISTORY_MAX_VERSIONS keeps its meaning
        (2, None, "1", 1),
        ("auto", "2", "1", 1),
    ],
)
def test_history_ring_is_sized_by_the_commit_window(
    monkeypatch, depth, adaptive_env, versions_env, want
) -> None:
    """The committed-state ring holds window + 1 versions at every depth:
    one at depth 0 (a strict step keeps committed N and speculative N + 1,
    never N - 1 as a third copy), depth + 1 pipelined, adaptive max + 1
    under ``auto``; the environment's cap overrides either way."""
    for name in (
        "TPUFT_COMMIT_PIPELINE",
        "TPUFT_COMMIT_PIPELINE_DEPTH",
        "TPUFT_HISTORY_BYTES",
    ):
        monkeypatch.delenv(name, raising=False)
    for name, value in (
        ("TPUFT_COMMIT_PIPELINE_ADAPTIVE", adaptive_env),
        ("TPUFT_HISTORY_MAX_VERSIONS", versions_env),
    ):
        if value is None:
            monkeypatch.delenv(name, raising=False)
        else:
            monkeypatch.setenv(name, value)
    manager, _, _, _ = make_manager(commit_pipeline_depth=depth)
    assert manager.history.max_versions == want


def test_strict_commit_env_keeps_the_ring_the_manager_was_built_with(
    monkeypatch,
) -> None:
    """TPUFT_STRICT_COMMIT=1 is read per make_step_fn and the window may be
    re-entered, so it never narrows a depth >= 1 manager's ring."""
    monkeypatch.delenv("TPUFT_HISTORY_MAX_VERSIONS", raising=False)
    monkeypatch.setenv("TPUFT_STRICT_COMMIT", "1")
    manager, _, _, _ = make_manager(commit_pipeline_depth=2)
    assert manager.history.max_versions == 3
