"""Pipelined gradient-sync tests (parity: reference ddp_test.py, plus the
bucket scheduling that replaces the reference's overlapped comm hook)."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from test_manager import make_manager, make_quorum

from torchft_tpu.ddp import _plan_buckets, ft_allreduce_gradients
from torchft_tpu.optim import Optimizer
from torchft_tpu.parallel.process_group import ProcessGroupDummy


def scripted_manager(**kwargs):
    kwargs.setdefault("min_replica_size", 1)
    manager, client, pg, transport = make_manager(pg=ProcessGroupDummy(), **kwargs)
    client._quorum.return_value = make_quorum(replica_world_size=1, max_world_size=1)
    client.should_commit.side_effect = lambda rank, step, vote, timeout: vote
    return manager


def test_plan_buckets_groups_same_dtype_up_to_cap() -> None:
    leaves = [
        np.ones(10, np.float32),  # 40 B
        np.ones(10, np.float32),  # fits with previous under 100 B
        np.ones(5, np.int32),  # separate dtype bucket
        np.ones(20, np.float32),  # 80 B: overflows the open f32 bucket
        np.ones(2, np.float32),  # joins the new f32 bucket
    ]
    buckets = _plan_buckets(leaves, cap_bytes=100)
    assert buckets == [[0, 1], [2], [3, 4]]
    # Order within and across buckets is flatten order (deterministic).
    assert [i for b in buckets for i in sorted(b)] == sorted(range(5))


def test_pipelined_allreduce_multi_bucket_identity(monkeypatch) -> None:
    """With one participant, the pipelined bucket sync is an identity on the
    gradient pytree — across many leaves, mixed float dtypes, and a bucket
    cap small enough to force several wire messages."""
    monkeypatch.setenv("TPUFT_BUCKET_MB", "0.0001")  # ~100 bytes per bucket
    manager = scripted_manager()
    manager.start_quorum()
    grads = {
        f"layer{i}": {
            "w": jnp.full((7, 3), 0.5 + i, dtype=jnp.float32),
            "b": jnp.full((11,), -1.0 * i, dtype=jnp.bfloat16),
        }
        for i in range(6)
    }
    out = ft_allreduce_gradients(manager, grads)
    assert manager.errored() is None
    for (path_a, leaf_out), (path_b, leaf_in) in zip(
        jax.tree_util.tree_flatten_with_path(out)[0],
        jax.tree_util.tree_flatten_with_path(grads)[0],
    ):
        assert path_a == path_b
        assert isinstance(leaf_out, jax.Array)
        assert leaf_out.dtype == leaf_in.dtype and leaf_out.shape == leaf_in.shape
        np.testing.assert_array_equal(np.asarray(leaf_out), np.asarray(leaf_in))


def test_pipelined_allreduce_int_leaves_fall_back() -> None:
    manager = scripted_manager()
    manager.start_quorum()
    grads = {"w": jnp.ones((4,), jnp.float32), "count": jnp.ones((2,), jnp.int32)}
    out = ft_allreduce_gradients(manager, grads)
    np.testing.assert_array_equal(np.asarray(out["count"]), np.ones(2, np.int32))
    np.testing.assert_array_equal(np.asarray(out["w"]), np.ones(4, np.float32))


def test_optimizer_speculative_update_discarded_on_heal() -> None:
    """If the commit barrier heals this replica, the speculatively dispatched
    update must be recomputed against the healed state, not adopted."""
    manager = scripted_manager()
    manager.start_quorum()
    tx = optax.sgd(0.1)
    params = {"w": jnp.array([1.0, 1.0], dtype=jnp.float32)}
    opt = Optimizer(manager, tx, params)

    healed = {"w": jnp.array([10.0, 10.0], dtype=jnp.float32)}
    real_should_commit = manager.should_commit

    def healing_should_commit(timeout=None):
        ok = real_should_commit(timeout=timeout)
        # Simulate the barrier applying a donor state dict mid-call.
        opt._load_state_dict({"params": healed, "opt_state": opt.opt_state})
        return ok

    manager.should_commit = healing_should_commit
    grads = {"w": jnp.array([1.0, 2.0], dtype=jnp.float32)}
    assert opt.step(grads)
    # Update must apply to the HEALED params: 10 - 0.1*grad.
    np.testing.assert_allclose(
        np.asarray(opt.params["w"]), np.array([9.9, 9.8], np.float32), rtol=1e-6
    )


def test_optimizer_speculative_update_adopted_without_heal() -> None:
    manager = scripted_manager()
    manager.start_quorum()
    tx = optax.sgd(0.1)
    params = {"w": jnp.array([1.0, 1.0], dtype=jnp.float32)}
    opt = Optimizer(manager, tx, params)
    grads = {"w": jnp.array([1.0, 2.0], dtype=jnp.float32)}
    assert opt.step(grads)
    np.testing.assert_allclose(
        np.asarray(opt.params["w"]), np.array([0.9, 0.8], np.float32), rtol=1e-6
    )


def _plain_trajectory(loss_fn, tx, params, batches):
    """Identically-structured fused plain program, for bitwise comparison."""
    @jax.jit
    def fused(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    opt_state = tx.init(params)
    losses = []
    for batch in batches:
        loss, params, opt_state = fused(params, opt_state, batch)
        losses.append(float(loss))
    return params, losses


def test_make_step_fn_lone_replica_runs_fused_and_matches_plain(monkeypatch):
    """A lone replica's step must never touch the wire path and must produce
    the exact plain-JAX trajectory (same fused program shape)."""
    import torchft_tpu.ddp as ddp_mod

    def _boom(*a, **k):
        raise AssertionError("wire path used on the lone-replica fused step")

    monkeypatch.setattr(ddp_mod, "ft_allreduce_gradients", _boom)

    manager = scripted_manager()
    tx = optax.sgd(0.2, momentum=0.9)
    params = {"w": jnp.array([1.0, -2.0, 3.0], jnp.float32)}

    def loss_fn(p, batch):
        return jnp.sum((p["w"] - batch) ** 2)

    # The arrays given to the Optimizer are its own from here on (the lone
    # step updates them in place): the plain trajectory starts from a copy.
    start = jax.tree_util.tree_map(np.asarray, params)
    opt = Optimizer(manager, tx, params)
    quorum_waits = []
    step_fn = opt.make_step_fn(loss_fn, on_quorum=quorum_waits.append)
    batches = [jnp.full((3,), 0.1 * i, jnp.float32) for i in range(5)]
    losses = []
    for batch in batches:
        loss, committed = step_fn(batch)
        assert committed
        losses.append(float(loss))
    assert manager.is_lone_replica()
    want_params, want_losses = _plain_trajectory(
        loss_fn, tx, jax.tree_util.tree_map(jnp.asarray, start), batches
    )
    np.testing.assert_array_equal(
        np.asarray(opt.params["w"]), np.asarray(want_params["w"])
    )
    assert losses == want_losses
    assert len(quorum_waits) == 5 and all(t >= 0 for t in quorum_waits)


@pytest.mark.parametrize("order", ["vote_first", "speculative"])
def test_make_step_fn_heal_during_the_barrier_steps_the_healed_state(monkeypatch, order):
    """Heal during the barrier, by the order the lone step runs in. The loss
    has a params-dependent gradient so the possible semantics give
    different answers.

    - vote_first (the default: verdict, then the donated program): the state
      is read AFTER the verdict, so the healed state is what steps, its own
      gradient and all: the reference's ``if should_commit():
      optimizer.step()`` with the forward on what the barrier left.
    - speculative (``TPUFT_STRICT_COMMIT=1`` here; any step that keeps the
      old state): as ``Optimizer.step`` and the reference's load_state_dict
      + optimizer.step() order, the gradients computed on the PRE-heal
      params apply to the HEALED state."""
    monkeypatch.setenv("TPUFT_STRICT_COMMIT", "1" if order == "speculative" else "0")
    manager = scripted_manager()
    tx = optax.sgd(0.1)
    params = {"w": jnp.array([1.0, 1.0], jnp.float32)}
    opt = Optimizer(manager, tx, params)

    def loss_fn(p, batch):
        return jnp.sum((p["w"] - batch) ** 2)  # grad = 2(w - batch)

    healed = {"w": jnp.array([10.0, 10.0], jnp.float32)}
    real_should_commit = manager.should_commit

    def healing_should_commit(timeout=None):
        ok = real_should_commit(timeout=timeout)
        opt._load_state_dict({"params": healed, "opt_state": opt.opt_state})
        return ok

    manager.should_commit = healing_should_commit
    step_fn = opt.make_step_fn(loss_fn)
    _, committed = step_fn(jnp.array([1.0, 2.0], jnp.float32))
    assert committed
    if order == "vote_first":
        # Grads on the healed params: 2*(10-1)=18, 2*(10-2)=16.
        want = np.array([8.2, 8.4], np.float32)
    else:
        # Pre-heal grads: 2*(1-1)=0, 2*(1-2)=-2; applied to healed [10, 10].
        want = np.array([10.0, 10.2], np.float32)
    np.testing.assert_allclose(np.asarray(opt.params["w"]), want, rtol=1e-6)


def test_make_step_fn_uses_wire_path_when_not_lone():
    manager = scripted_manager()
    manager.is_lone_replica = lambda: False  # other groups participating
    tx = optax.sgd(0.1)
    params = {"w": jnp.array([1.0, 1.0], jnp.float32)}
    opt = Optimizer(manager, tx, params)

    def loss_fn(p, batch):
        return jnp.sum(p["w"] * batch)

    step_fn = opt.make_step_fn(loss_fn)
    _, committed = step_fn(jnp.array([1.0, 2.0], jnp.float32))
    assert committed
    # Dummy PG loopback: averaged grad == local grad == batch.
    np.testing.assert_allclose(
        np.asarray(opt.params["w"]), np.array([0.9, 0.8], np.float32), rtol=1e-6
    )


def test_fp8_wire_worker_cached_per_manager_and_released_on_shutdown():
    """The FIFO wire worker is reused across steps for one manager (no
    per-step thread churn — round-2 advisor) and torn down by
    Manager.shutdown even while the manager object stays referenced."""
    import torchft_tpu.ddp as ddp_mod

    manager = scripted_manager()
    w1 = ddp_mod._wire_worker_for(manager)
    w2 = ddp_mod._wire_worker_for(manager)
    assert w1 is w2
    assert w1.submit(lambda: 7).result() == 7
    manager.shutdown(wait=False)
    with pytest.raises(RuntimeError):  # executor refused after shutdown
        w1.submit(lambda: 0)

def _spy_commit_ordering(monkeypatch, manager, opt):
    """Instruments the device-sync seam and the vote launch; returns the
    event list (entries: ("sync", synced_obj) / ("vote",))."""
    import torchft_tpu.optim as optim_mod

    events = []
    real_sync = optim_mod._bound_device
    real_async = manager.should_commit_async

    def spy_sync(x):
        events.append(("sync", x))
        return real_sync(x)

    def spy_async(timeout=None):
        events.append(("vote",))
        return real_async(timeout)

    real_commit = manager.should_commit

    caller = threading.current_thread()

    def spy_commit(timeout=None):
        # The vote-first step's own call, on the caller's thread; the
        # executor's call of a vote that spy_async already counted is not.
        if threading.current_thread() is caller:
            events.append(("vote",))
        return real_commit(timeout=timeout)

    monkeypatch.setattr(optim_mod, "_bound_device", spy_sync)
    manager.should_commit_async = spy_async
    manager.should_commit = spy_commit
    return events


@pytest.mark.parametrize("mode", ["strict", "vote_first", "overlapped", "pipelined"])
def test_make_step_fn_commit_sync_orderings(monkeypatch, mode):
    """Pins all four commit orderings on the lone-replica step:

    - strict (TPUFT_STRICT_COMMIT=1): vote only after observed completion
      (reference manager.py:816-827) — sync precedes the vote, same call,
      every step.
    - vote_first (default: a ring of one version): the verdict is taken on
      the caller's thread, then the donated program is dispatched and
      synced — vote precedes sync, same call, every step, and the state
      before the call is deleted.
    - overlapped (a ring asked to keep two versions, so nothing may be
      given away): the barrier RPC launches first and rides under the
      readiness wait — vote precedes sync, same call, every step.
    - pipelined (commit_pipeline_depth=1): a step's own call does NO sync
      of its own loss; it syncs the PREVIOUS step's loss (after dispatch,
      so the readiness RTT rides under the new step's device execution)
      and then votes — exactly one step's completion unobserved per vote.
    """
    monkeypatch.setenv("TPUFT_STRICT_COMMIT", "1" if mode == "strict" else "0")
    if mode == "overlapped":
        monkeypatch.setenv("TPUFT_HISTORY_MAX_VERSIONS", "2")
    manager = scripted_manager(
        commit_pipeline_depth=1 if mode == "pipelined" else 0
    )
    tx = optax.sgd(0.1)
    params = {"w": jnp.array([1.0, 1.0], jnp.float32)}
    opt = Optimizer(manager, tx, params)
    events = _spy_commit_ordering(monkeypatch, manager, opt)

    step_fn = opt.make_step_fn(lambda p, b: jnp.sum(p["w"] * b))
    losses = []
    given_away = []
    for _ in range(3):
        before = opt.params["w"]
        loss, committed = step_fn(jnp.array([1.0, 2.0], jnp.float32))
        losses.append(loss)
        given_away.append(before.is_deleted())
    assert given_away == [mode == "vote_first"] * 3
    kinds = [e[0] for e in events]
    if mode == "strict":
        assert kinds == ["sync", "vote"] * 3
        # Each call syncs its OWN loss before its vote leaves.
        assert [e[1] for e in events if e[0] == "sync"] == losses
    elif mode in ("vote_first", "overlapped"):
        assert kinds == ["vote", "sync"] * 3
        assert [e[1] for e in events if e[0] == "sync"] == losses
    else:
        # Call 1 has nothing pending: vote only. Calls 2..n sync the
        # PREVIOUS call's loss, then vote; the flush syncs the last.
        assert kinds == ["vote", "sync", "vote", "sync", "vote"]
        assert [e[1] for e in events if e[0] == "sync"] == losses[:2]
        assert opt.pending_commits() == 1
        assert opt.flush_pipeline() is True
        assert [e[1] for e in events if e[0] == "sync"] == losses
        assert opt.pending_commits() == 0


def test_strict_commit_env_overrides_pipeline(monkeypatch):
    """TPUFT_STRICT_COMMIT=1 wins over commit_pipeline_depth=1: the step
    runs the strict ordering and nothing rides the pipeline."""
    monkeypatch.setenv("TPUFT_STRICT_COMMIT", "1")
    manager = scripted_manager(commit_pipeline_depth=1)
    opt = Optimizer(manager, optax.sgd(0.1), {"w": jnp.ones(2, jnp.float32)})
    events = _spy_commit_ordering(monkeypatch, manager, opt)
    step_fn = opt.make_step_fn(lambda p, b: jnp.sum(p["w"] * b))
    _, committed = step_fn(jnp.array([1.0, 2.0], jnp.float32))
    assert committed is True  # strict mode reports THIS step's verdict
    assert [e[0] for e in events] == ["sync", "vote"]
    assert opt.pending_commits() == 0


def test_pipelined_step_fn_matches_plain_and_skips_wire(monkeypatch):
    """The pipelined lone-replica loop must produce the exact plain-JAX
    trajectory (same fused program) and never touch the wire path."""
    import torchft_tpu.ddp as ddp_mod

    def _boom(*a, **k):
        raise AssertionError("wire path used on the lone-replica pipelined step")

    monkeypatch.setattr(ddp_mod, "ft_allreduce_gradients", _boom)

    manager = scripted_manager(commit_pipeline_depth=1)
    tx = optax.sgd(0.2, momentum=0.9)
    params = {"w": jnp.array([1.0, -2.0, 3.0], jnp.float32)}

    def loss_fn(p, batch):
        return jnp.sum((p["w"] - batch) ** 2)

    opt = Optimizer(manager, tx, params)
    step_fn = opt.make_step_fn(loss_fn)
    batches = [jnp.full((3,), 0.1 * i, jnp.float32) for i in range(5)]
    committed_flags = []
    losses = []
    for batch in batches:
        loss, prev_committed = step_fn(batch)
        committed_flags.append(prev_committed)
        losses.append(float(loss))
    assert committed_flags == [None, True, True, True, True]
    assert opt.flush_pipeline() is True
    assert manager.current_step() == 5

    want_params, want_losses = _plain_trajectory(loss_fn, tx, params, batches)
    np.testing.assert_array_equal(
        np.asarray(opt.params["w"]), np.asarray(want_params["w"])
    )
    assert losses == want_losses


def test_pipelined_rollback_on_failed_commit():
    """A failed commit discovered one step late rolls the live state back
    to the pre-step snapshot before the next dispatch — the speculative
    update never leaks into committed history."""
    manager = scripted_manager(commit_pipeline_depth=1)
    # Step votes: commit 1 succeeds, commit 2 fails, rest succeed.
    votes = iter([True, False, True, True])
    manager._client.should_commit.side_effect = (
        lambda rank, step, vote, timeout: vote and next(votes)
    )
    tx = optax.sgd(0.1)
    opt = Optimizer(manager, tx, {"w": jnp.array([1.0, 1.0], jnp.float32)})

    def loss_fn(p, b):
        return jnp.sum((p["w"] - b) ** 2)  # grad = 2(w - b)

    step_fn = opt.make_step_fn(loss_fn)
    flags = []
    for i in range(4):
        _, prev_committed = step_fn(jnp.full((2,), float(i), jnp.float32))
        flags.append(prev_committed)
    assert opt.flush_pipeline() is True
    assert flags == [None, True, False, True]
    assert opt.rollback_count == 1
    # 4 dispatches, 1 refused: exactly 3 committed steps.
    assert manager.current_step() == 3

    # Recompute the trajectory the commits describe: batches 0, (1 refused
    # and rolled back), 2, 3 applied to the surviving state.
    w = np.array([1.0, 1.0], np.float32)
    for b in (0.0, 2.0, 3.0):
        w = w - 0.1 * 2 * (w - b)
    np.testing.assert_allclose(np.asarray(opt.params["w"]), w, rtol=1e-6)


def test_pipelined_heal_recomputes_on_healed_state():
    """A heal landing inside an in-flight pipelined vote: the resolution
    must apply the PRE-heal gradients to the HEALED state (reference
    load_state_dict + optimizer.step() order), not keep the stale
    speculation."""
    manager = scripted_manager(commit_pipeline_depth=1)
    tx = optax.sgd(0.1)
    opt = Optimizer(manager, tx, {"w": jnp.array([1.0, 1.0], jnp.float32)})

    def loss_fn(p, batch):
        return jnp.sum((p["w"] - batch) ** 2)  # grad = 2(w - batch)

    healed = {"w": jnp.array([10.0, 10.0], jnp.float32)}
    real_should_commit = manager.should_commit
    heal_once = []

    def healing_should_commit(timeout=None):
        ok = real_should_commit(timeout=timeout)
        if not heal_once:
            heal_once.append(True)
            opt._load_state_dict({"params": healed, "opt_state": opt.opt_state})
        return ok

    manager.should_commit = healing_should_commit
    step_fn = opt.make_step_fn(loss_fn)
    _, _ = step_fn(jnp.array([1.0, 2.0], jnp.float32))
    # The heal happened during step 1's (already launched) vote; resolving
    # it must recompute: pre-heal grads 2*(1-1)=0, 2*(1-2)=-2 applied to
    # healed [10, 10] -> [10.0, 10.2].
    assert opt.flush_pipeline() is True
    np.testing.assert_allclose(
        np.asarray(opt.params["w"]), np.array([10.0, 10.2], np.float32),
        rtol=1e-6,
    )


def _spy_deep_commit_ordering(monkeypatch, manager):
    """Depth>=2 windows vote through Manager.speculative_commit_async (the
    concurrent-vote path), not should_commit_async — spy both seams."""
    import torchft_tpu.optim as optim_mod

    events = []
    real_sync = optim_mod._bound_device
    real_spec = manager.speculative_commit_async

    def spy_sync(x):
        events.append(("sync", x))
        return real_sync(x)

    def spy_vote(claimed_step, timeout=None):
        events.append(("vote", claimed_step))
        return real_spec(claimed_step, timeout)

    monkeypatch.setattr(optim_mod, "_bound_device", spy_sync)
    manager.speculative_commit_async = spy_vote
    return events


def test_pipelined_depth2_ordering_and_envelope(monkeypatch):
    """Depth-2 window: the first two calls only vote (the window has
    room), every later call syncs the step-from-two-calls-ago BEFORE its
    own vote leaves (the envelope invariant: vote N is sent only after
    step N-depth's completion was observed), and at most two commits are
    ever unaccounted."""
    manager = scripted_manager(commit_pipeline_depth=2)
    tx = optax.sgd(0.1)
    opt = Optimizer(manager, tx, {"w": jnp.array([1.0, 1.0], jnp.float32)})
    events = _spy_deep_commit_ordering(monkeypatch, manager)
    step_fn = opt.make_step_fn(lambda p, b: jnp.sum(p["w"] * b))
    losses = []
    occupancy = []
    for _ in range(4):
        loss, _ = step_fn(jnp.array([1.0, 2.0], jnp.float32))
        losses.append(loss)
        occupancy.append(opt.pending_commits())
    kinds = [e[0] for e in events]
    # Calls 1-2 fill the window (vote only); calls 3-4 each resolve + sync
    # exactly one oldest step, then vote.
    assert kinds == ["vote", "vote", "sync", "vote", "sync", "vote"]
    # Claimed steps are the speculative window positions 0..3.
    assert [e[1] for e in events if e[0] == "vote"] == [0, 1, 2, 3]
    # Each call's sync observes the step from TWO calls earlier.
    assert [e[1] for e in events if e[0] == "sync"] == losses[:2]
    assert occupancy == [1, 2, 2, 2]
    assert opt.flush_pipeline() is True
    assert [e[1] for e in events if e[0] == "sync"] == losses
    assert opt.pending_commits() == 0
    assert manager.current_step() == 4


def test_pipelined_depth3_matches_plain(monkeypatch):
    """The depth-3 lone-replica loop must produce the exact plain-JAX
    trajectory (same fused program) with verdicts lagging dispatch by the
    window depth, and never touch the wire path."""
    import torchft_tpu.ddp as ddp_mod

    def _boom(*a, **k):
        raise AssertionError("wire path used on the lone-replica deep window")

    monkeypatch.setattr(ddp_mod, "ft_allreduce_gradients", _boom)

    manager = scripted_manager(commit_pipeline_depth=3)
    tx = optax.sgd(0.2, momentum=0.9)
    params = {"w": jnp.array([1.0, -2.0, 3.0], jnp.float32)}

    def loss_fn(p, batch):
        return jnp.sum((p["w"] - batch) ** 2)

    opt = Optimizer(manager, tx, params)
    step_fn = opt.make_step_fn(loss_fn)
    batches = [jnp.full((3,), 0.1 * i, jnp.float32) for i in range(6)]
    flags = []
    losses = []
    for batch in batches:
        loss, verdict = step_fn(batch)
        flags.append(verdict)
        losses.append(float(loss))
    assert flags == [None, None, None, True, True, True]
    assert opt.flush_pipeline() is True
    assert manager.current_step() == 6

    want_params, want_losses = _plain_trajectory(loss_fn, tx, params, batches)
    np.testing.assert_array_equal(
        np.asarray(opt.params["w"]), np.asarray(want_params["w"])
    )
    assert losses == want_losses


def test_pipelined_depth2_rollback_unwinds_younger_speculation():
    """A refusal at window position k rolls the live state back to the
    pre-step-k snapshot AND discards the younger in-flight speculative
    step (its verdict is consumed without accounting — quorum-wide that
    step never happened), and the unwind depth lands in the histogram."""
    from torchft_tpu import metrics as ft_metrics

    manager = scripted_manager(commit_pipeline_depth=2)
    # Barrier verdicts in launch order: b0=True, b1=False, b2 (discarded
    # mid-flight), then the re-dispatches b3, b4 commit.
    votes = iter([True, False, True, True, True])
    manager._client.should_commit.side_effect = (
        lambda rank, step, vote, timeout: vote and next(votes)
    )
    tx = optax.sgd(0.1)
    opt = Optimizer(manager, tx, {"w": jnp.array([1.0, 1.0], jnp.float32)})

    def loss_fn(p, b):
        return jnp.sum((p["w"] - b) ** 2)  # grad = 2(w - b)

    unwind_before = ft_metrics.histogram_stats("tpuft_rollback_unwind_depth")
    step_fn = opt.make_step_fn(loss_fn)
    flags = []
    for i in range(5):
        _, verdict = step_fn(jnp.full((2,), float(i), jnp.float32))
        flags.append(verdict)
    assert opt.flush_pipeline() is True
    # Call 4 resolves b1's refusal (rolls back AND discards b2's in-flight
    # slot in the same call); the re-dispatched steps commit.
    assert flags == [None, None, True, False, None]
    assert opt.rollback_count == 1
    assert manager.current_step() == 3  # b0, b3, b4 committed
    unwind_after = ft_metrics.histogram_stats("tpuft_rollback_unwind_depth")
    assert unwind_after["count"] - unwind_before["count"] == 1
    assert unwind_after["sum"] - unwind_before["sum"] == 2  # refused + 1 younger

    # The committed trajectory: batches 0, 3, 4 applied in order; the
    # refused batch 1 and the discarded batch 2 never touch it.
    w = np.array([1.0, 1.0], np.float32)
    for b in (0.0, 3.0, 4.0):
        w = w - 0.1 * 2 * (w - b)
    np.testing.assert_allclose(np.asarray(opt.params["w"]), w, rtol=1e-6)


def test_pipelined_depth2_heal_replays_whole_window():
    """A heal landing with TWO speculative steps in flight: resolution
    replays the WHOLE window's pre-heal gradients onto the healed state in
    window order (each slot's recompute applies to the state the previous
    slot produced) — the depth-N generalization of the reference
    load_state_dict + optimizer.step() order."""
    manager = scripted_manager(commit_pipeline_depth=2)
    tx = optax.sgd(0.1)
    w0 = jnp.array([1.0, 1.0], jnp.float32)
    opt = Optimizer(manager, tx, {"w": w0})

    def loss_fn(p, batch):
        return jnp.sum((p["w"] - batch) ** 2)  # grad = 2(w - batch)

    step_fn = opt.make_step_fn(loss_fn)
    b1 = jnp.array([1.0, 2.0], jnp.float32)
    b2 = jnp.array([3.0, 3.0], jnp.float32)
    step_fn(b1)
    step_fn(b2)
    assert opt.pending_commits() == 2
    # The donor state lands while both votes are in flight (the barrier
    # would apply it through the vote pre-phase; injected directly for
    # determinism).
    opt._load_state_dict(
        {"params": {"w": jnp.array([10.0, 10.0], jnp.float32)},
         "opt_state": opt.opt_state}
    )
    assert opt.flush_pipeline() is True
    # Slot 1: grads on w0=[1,1] vs b1 -> [0,-2], applied to healed [10,10]
    # -> [10.0, 10.2]. Slot 2: grads on slot-1's SPECULATIVE params
    # [1.0,1.2] vs b2 -> [-4,-3.6], applied to [10.0,10.2] -> [10.4,10.56].
    np.testing.assert_allclose(
        np.asarray(opt.params["w"]), np.array([10.4, 10.56], np.float32),
        rtol=1e-5,
    )


def test_pipelined_depth2_quorum_change_drains_full_window():
    """A quorum membership change must resolve the ENTIRE window on the
    quorum thread BEFORE pg.configure — the R7 invariant at runtime. The
    dummy PG's configure observes zero pending speculative steps."""
    manager = scripted_manager(commit_pipeline_depth=2)
    tx = optax.sgd(0.1)
    opt = Optimizer(manager, tx, {"w": jnp.array([1.0, 1.0], jnp.float32)})
    pending_at_configure = []
    real_configure = manager._pg.configure

    def spy_configure(*args, **kwargs):
        pending_at_configure.append(
            (opt.pending_commits() - sum(
                1 for r in (opt._pipeline.pending() if opt._pipeline else ())
                if r.committed is not None
            ), manager.current_step())
        )
        return real_configure(*args, **kwargs)

    manager._pg.configure = spy_configure
    step_fn = opt.make_step_fn(lambda p, b: jnp.sum(p["w"] * b))
    step_fn(jnp.array([1.0, 2.0], jnp.float32))
    step_fn(jnp.array([1.0, 2.0], jnp.float32))
    assert opt.pending_commits() == 2
    # Membership change: next quorum returns a new id.
    manager._client._quorum.return_value = make_quorum(
        quorum_id=2, replica_world_size=1, max_world_size=1
    )
    step_fn(jnp.array([1.0, 2.0], jnp.float32))
    # Two configures: the initial era (-1 -> 1, empty window) and the
    # change (1 -> 2): every in-flight slot resolved before the wire
    # reconfigured, with the committed step caught up to the window head.
    assert [p for p, _ in pending_at_configure] == [0, 0]
    assert pending_at_configure[1][1] == 2
    assert opt.flush_pipeline() is True


def test_pipelined_depth2_donor_send_drains_and_serves_exact_max_step():
    """A donor send with no quorum-id change (a repeated heal round) must
    still drain the window first and — now that resolved window slots
    promote into the manager's history ring — serve the joiner EXACTLY
    the step it asked for (``quorum.max_step``), even though the drain
    advanced this donor's live committed step past it. The pre-history
    behavior (stage the drained step; the joiner fails cleanly and
    retries next round) remains only as the ring-miss fallback, covered
    by the test below."""
    import numpy as np

    from torchft_tpu import metrics as ft_metrics

    manager = scripted_manager(commit_pipeline_depth=2)
    transport = manager._checkpoint_transport
    # The exact-serve path requires EVERY registered state key to be
    # promoted by its owner at commit resolution; the test fixture's
    # static "model" key has no owner, so drop it (a real training job
    # registers owner-promoted state — the Optimizer here). The ring
    # refusing to serve when an unpromoted key is registered is itself
    # the conservative contract (covered by the miss test below).
    manager._user_state_dicts.pop("model", None)
    manager._load_state_dict_fns.pop("model", None)
    tx = optax.sgd(0.1)
    opt = Optimizer(manager, tx, {"w": jnp.array([1.0, 1.0], jnp.float32)})
    seen = []

    def spy_send(dst_ranks, step, state_dict, timeout, quorum_id=None):
        seen.append(
            (step, opt.pending_commits(), manager.current_step(), state_dict)
        )

    transport.send_checkpoint.side_effect = spy_send
    step_fn = opt.make_step_fn(lambda p, b: jnp.sum(p["w"] * b))
    step_fn(jnp.array([1.0, 2.0], jnp.float32))
    step_fn(jnp.array([1.0, 2.0], jnp.float32))
    assert opt.pending_commits() == 2
    exact_before = ft_metrics.counter_total("tpuft_history_exact_serves_total")
    # Same quorum id, but a joiner was assigned to heal from us; the
    # lighthouse computed max_step=1 from pre-drain reports — the drain
    # below resolves the full window, advancing this donor to step 2.
    manager._client._quorum.return_value = make_quorum(
        quorum_id=1, replica_world_size=1, max_world_size=1,
        recover_dst_replica_ranks=[1], max_step=1,
    )
    step_fn(jnp.array([1.0, 2.0], jnp.float32))
    assert len(seen) == 1
    staged_step, pending, committed, state_dict = seen[0]
    assert pending - sum(
        1 for r in (opt._pipeline.pending() if opt._pipeline else ())
        if r.committed is not None
    ) == 0  # window fully resolved before the send
    # The immediate-serve path: the joiner's requested step, exactly,
    # while the donor's live state had drained past it.
    assert staged_step == 1
    assert committed >= 2
    # The staged bytes ARE committed step 1: w0 - 0.1 * [1, 2].
    np.testing.assert_allclose(
        np.asarray(state_dict["user"]["optimizer"]["params"]["w"]),
        np.array([0.9, 0.8], np.float32),
        rtol=1e-6,
    )
    assert state_dict["tpuft"]["step"] == 1
    assert (
        ft_metrics.counter_total("tpuft_history_exact_serves_total")
        - exact_before
        == 1
    )
    assert opt.flush_pipeline() is True


def test_pipelined_donor_send_history_miss_falls_back_to_drained_step(
    monkeypatch,
):
    """The ring-miss fallback (history evicted down to the live step):
    the donor stages its DRAINED committed step honestly labeled — never
    speculative state, never committed bytes mislabeled with the
    quorum's stale max_step — and the joiner fails that round cleanly,
    exactly the pre-history envelope."""
    monkeypatch.setenv("TPUFT_HISTORY_MAX_VERSIONS", "1")
    manager = scripted_manager(commit_pipeline_depth=2)
    transport = manager._checkpoint_transport
    tx = optax.sgd(0.1)
    opt = Optimizer(manager, tx, {"w": jnp.array([1.0, 1.0], jnp.float32)})
    seen = []

    def spy_send(dst_ranks, step, state_dict, timeout, quorum_id=None):
        seen.append((step, manager.current_step()))

    transport.send_checkpoint.side_effect = spy_send
    step_fn = opt.make_step_fn(lambda p, b: jnp.sum(p["w"] * b))
    step_fn(jnp.array([1.0, 2.0], jnp.float32))
    step_fn(jnp.array([1.0, 2.0], jnp.float32))
    manager._client._quorum.return_value = make_quorum(
        quorum_id=1, replica_world_size=1, max_world_size=1,
        recover_dst_replica_ranks=[1], max_step=1,
    )
    step_fn(jnp.array([1.0, 2.0], jnp.float32))
    assert len(seen) == 1
    staged_step, committed = seen[0]
    # K=1 keeps only the newest committed version: max_step=1 is gone,
    # so the drained step is staged under its true label.
    assert staged_step == committed == 2
    assert opt.flush_pipeline() is True


def test_strict_depth0_donor_send_stages_live_max_step_without_the_ring():
    """The depth-0 twin of the two donor tests above: a strict donor's
    live committed step IS ``quorum.max_step`` (nothing drains past it),
    so the send stages the live state under its own label and never asks
    the ring — which at depth 0 holds that one state and nothing older.
    Neither the exact-serve nor the miss counter moves."""
    from torchft_tpu import metrics as ft_metrics

    manager = scripted_manager()
    transport = manager._checkpoint_transport
    opt = Optimizer(
        manager, optax.sgd(0.1), {"w": jnp.array([1.0, 1.0], jnp.float32)}
    )
    seen = []

    def spy_send(dst_ranks, step, state_dict, timeout, quorum_id=None):
        # As a transport does: host copies staged inside the call. The
        # references are the live state's, which the next step deletes.
        seen.append(
            (step, manager.current_step(), jax.tree_util.tree_map(np.asarray, state_dict))
        )

    transport.send_checkpoint.side_effect = spy_send
    step_fn = opt.make_step_fn(lambda p, b: jnp.sum(p["w"] * b))
    for _ in range(2):
        _, committed = step_fn(jnp.array([1.0, 2.0], jnp.float32))
        assert committed
    exact_before = ft_metrics.counter_total("tpuft_history_exact_serves_total")
    miss_before = ft_metrics.counter_total("tpuft_history_misses_total")
    # A joiner is assigned to heal from us inside the same era: the
    # lighthouse's max_step is the step we reported, our live step.
    manager._client._quorum.return_value = make_quorum(
        quorum_id=1, replica_world_size=1, max_world_size=1,
        recover_dst_replica_ranks=[1], max_step=2,
    )
    _, committed = step_fn(jnp.array([1.0, 2.0], jnp.float32))
    assert committed
    assert len(seen) == 1
    staged_step, live_step, state_dict = seen[0]
    assert staged_step == live_step == 2
    assert state_dict["tpuft"]["step"] == 2
    # Committed step 2 is w0 - 2 * 0.1 * [1, 2], read from the live state.
    np.testing.assert_allclose(
        np.asarray(state_dict["user"]["optimizer"]["params"]["w"]),
        np.array([0.8, 0.6], np.float32),
        rtol=1e-6,
    )
    assert (
        ft_metrics.counter_total("tpuft_history_exact_serves_total")
        == exact_before
    )
    assert ft_metrics.counter_total("tpuft_history_misses_total") == miss_before
    assert manager.current_step() == 3
    assert manager.history.resident_steps() == [3]


# -- the strict step's two copies of the state --------------------------------


def _state_leaves(opt):
    return jax.tree_util.tree_leaves((opt.params, opt.opt_state))


def _ring_state(manager, opt):
    """The ring's version at the manager's committed step, or None."""
    # The entry itself: ``state_dict_at`` hands a ring of one version's out
    # as a device copy, and these tests pin what the ring HOLDS.
    entry = manager.history._entries.get(manager.current_step())
    return None if entry is None else entry.states.get(opt._register_key)


def _strict_setup(path):
    """A depth-0 manager, an Adam optimizer over one leaf, and ``run(batch)
    -> committed`` through one of the strict entry points: the fused
    lone-replica step_fn, its wire branch, ``Optimizer.step``."""
    manager = scripted_manager()
    opt = Optimizer(
        manager, optax.adam(0.1), {"w": jnp.array([1.0, -2.0, 3.0], jnp.float32)}
    )

    def loss_fn(p, batch):
        return jnp.sum((p["w"] - batch) ** 2)

    if path == "step":
        grad_fn = jax.jit(jax.grad(loss_fn))

        def run(batch):
            opt.begin_step()
            return opt.step(grad_fn(opt.params, batch))

        return manager, opt, run
    if path == "wire":
        manager.is_lone_replica = lambda: False  # other groups participating
    step_fn = opt.make_step_fn(loss_fn)
    return manager, opt, lambda batch: step_fn(batch)[1]


@pytest.mark.parametrize("path", ["lone", "wire", "step"])
def test_strict_step_keeps_the_live_state_as_the_rings_one_version(path):
    """Depth 0, every strict entry point (the fused lone-replica step_fn,
    its wire branch, ``Optimizer.step``): after each commit the ring holds
    ONE step, its arrays ARE ``opt.params`` / ``opt.opt_state`` (identity:
    the version costs no memory) and the committed state of the step
    before is collectible — two copies of the state while a step is in
    flight (committed N, speculative N + 1), one between steps, where the
    ring used to pin N - 1 as a third."""
    import gc
    import weakref

    from torchft_tpu import metrics as ft_metrics

    manager, opt, run = _strict_setup(path)
    misses_before = ft_metrics.counter_total("tpuft_history_misses_total")
    older = []  # weak references to every earlier committed state's leaves
    for i in range(4):
        previous = [weakref.ref(leaf) for leaf in _state_leaves(opt)]
        assert run(jnp.full((3,), 0.1 * i, jnp.float32))
        older.append(previous)
        step = manager.current_step()
        assert step == i + 1
        assert manager.history.resident_steps() == [step]
        held = _ring_state(manager, opt)
        assert held["params"] is opt.params
        assert held["opt_state"] is opt.opt_state
        del held
        assert ft_metrics.gauge_value("tpuft_history_versions", ring="state") == 1.0
        assert ft_metrics.gauge_value(
            "tpuft_history_bytes", ring="state"
        ) == opt._snapshot_nbytes((opt.params, opt.opt_state))
        gc.collect()
        dead = [ref() is None for refs in older for ref in refs]
        assert all(dead), f"step {step}: an older committed state is still held"
    assert ft_metrics.counter_total("tpuft_history_misses_total") == misses_before


@pytest.mark.parametrize("path", ["lone", "wire", "step"])
def test_strict_refused_commit_leaves_state_n_live_and_the_rings(path):
    """A refused commit at depth 0 keeps the committed state of N: the same
    arrays stay ``opt.params`` / ``opt.opt_state`` AND the ring's one
    version, the refused speculation is dropped, and the next commit moves
    both to N + 1."""
    import gc
    import weakref

    manager, opt, run = _strict_setup(path)
    batch = jnp.full((3,), 0.5, jnp.float32)
    assert run(batch)
    live = _state_leaves(opt)
    manager._client.should_commit.side_effect = (
        lambda rank, step, vote, timeout: False
    )
    assert not run(batch)
    assert manager.current_step() == 1
    assert manager.history.resident_steps() == [1]
    held = _ring_state(manager, opt)
    assert held["params"] is opt.params and held["opt_state"] is opt.opt_state
    assert all(a is b for a, b in zip(_state_leaves(opt), live))
    manager._client.should_commit.side_effect = (
        lambda rank, step, vote, timeout: vote
    )
    refs = [weakref.ref(leaf) for leaf in live]
    del live, held
    assert run(batch)
    assert manager.current_step() == 2
    assert manager.history.resident_steps() == [2]
    assert _ring_state(manager, opt)["params"] is opt.params
    gc.collect()
    assert all(ref() is None for ref in refs)


@pytest.mark.parametrize("depth", [1, 2])
def test_pipelined_ring_still_holds_the_window(depth):
    """The same run at depth N keeps N + 1 committed versions, as before."""
    manager = scripted_manager(commit_pipeline_depth=depth)
    opt = Optimizer(manager, optax.sgd(0.1), {"w": jnp.ones(2, jnp.float32)})
    step_fn = opt.make_step_fn(lambda p, b: jnp.sum(p["w"] * b))
    for _ in range(depth + 4):
        step_fn(jnp.array([1.0, 2.0], jnp.float32))
    assert opt.flush_pipeline() is True
    newest = manager.current_step()
    assert manager.history.max_versions == depth + 1
    assert manager.history.resident_steps() == list(
        range(newest - depth, newest + 1)
    )


def test_adaptive_depth_deepens_under_stall_and_reevaluates_per_era(monkeypatch):
    """commit_pipeline_depth="auto": a barrier RTT the current window
    cannot hide deepens it (bounded by TPUFT_COMMIT_PIPELINE_ADAPTIVE);
    the per-era re-evaluation shrinks it back when the link recovers."""
    import time as _time

    monkeypatch.setenv("TPUFT_COMMIT_PIPELINE_ADAPTIVE", "2")
    manager = scripted_manager(commit_pipeline_depth="auto")
    assert manager.commit_pipeline_adaptive
    assert manager.commit_pipeline_depth == 1

    real = manager._client.should_commit.side_effect

    def slow_commit(rank, step, vote, timeout):
        _time.sleep(0.03)  # a control-plane RTT dwarfing the tiny step
        return real(rank, step, vote, timeout)

    manager._client.should_commit.side_effect = slow_commit
    tx = optax.sgd(0.1)
    opt = Optimizer(manager, tx, {"w": jnp.array([1.0, 1.0], jnp.float32)})
    step_fn = opt.make_step_fn(lambda p, b: jnp.sum(p["w"] * b))
    for _ in range(12):
        step_fn(jnp.array([1.0, 2.0], jnp.float32))
    assert opt.flush_pipeline() is True
    assert manager.commit_pipeline_depth == 2  # deepened, at the cap
    from torchft_tpu import metrics as ft_metrics

    assert ft_metrics.gauge_value(
        "tpuft_pipeline_depth", **manager._metric_labels
    ) == 2.0

    # Era re-evaluation: the link recovered (fast barrier, real compute)
    # -> ceil(rtt / compute) shrinks the window back to 1.
    manager._barrier_rtt_ewma = 0.0005
    manager._pipeline_interval_ewma = 0.05
    manager._pipeline_stall_ewma = 0.0
    manager._adapt_pipeline_depth()
    assert manager.commit_pipeline_depth == 1


def test_pipelined_wire_path_two_participants():
    """With another participant, the pipelined step runs the wire path:
    dummy-PG loopback averaging, speculative update adopted under the
    in-flight vote, verdicts one step late."""
    manager = scripted_manager(commit_pipeline_depth=1)
    manager.is_lone_replica = lambda: False
    tx = optax.sgd(0.1)
    opt = Optimizer(manager, tx, {"w": jnp.array([1.0, 1.0], jnp.float32)})
    step_fn = opt.make_step_fn(lambda p, b: jnp.sum(p["w"] * b))
    for _ in range(3):
        _, _ = step_fn(jnp.array([1.0, 2.0], jnp.float32))
    assert opt.flush_pipeline() is True
    # Dummy PG loopback: averaged grad == local grad == batch, 3 steps.
    np.testing.assert_allclose(
        np.asarray(opt.params["w"]), np.array([0.7, 0.4], np.float32),
        rtol=1e-5,
    )
    assert manager.current_step() == 3


# -- the step's own spans (tracing.phase) ------------------------------------


def _inside(child, parent) -> bool:
    return (
        parent["t_mono"] <= child["t_mono"]
        and child["t_mono"] + child["dur"] <= parent["t_mono"] + parent["dur"] + 1e-9
    )


@pytest.mark.parametrize("depth", [0, 1])
def test_lone_replica_step_leaves_its_span_tree_in_the_journal(depth):
    """One FT-DDP step of a lone replica, strict and pipelined: a root
    ``step`` span with the synchronous part of start_quorum, the update's
    dispatch, the device sync, the wait for the verdict and the adoption
    inside it on the train thread, all under the root's step."""
    from torchft_tpu import tracing

    journal = tracing.TraceJournal(maxlen=512)
    with tracing.use_journal(journal):
        manager = scripted_manager(commit_pipeline_depth=depth)
        opt = Optimizer(manager, optax.sgd(0.1), {"w": jnp.ones(3, jnp.float32)})
        step_fn = opt.make_step_fn(lambda p, b: jnp.sum((p["w"] - b) ** 2))
        for i in range(3):
            step_fn(jnp.full((3,), float(i), jnp.float32))
        opt.flush_pipeline()
    spans = [e for e in journal.snapshot() if e["ph"] == "X"]
    roots = [e for e in spans if e["name"] == "step"]
    assert [r["step"] for r in roots] == [0, 1, 2]
    want = {"start_quorum", "update_dispatch", "device_sync", "commit_wait", "adopt"}
    for root in roots[-2:]:  # the pipelined window resolves a step late
        children = [
            e for e in spans
            if e["name"] in want and e["thread"] == root["thread"] and _inside(e, root)
        ]
        assert {c["name"] for c in children} == want, [c["name"] for c in children]
        if depth == 0:
            assert {c["step"] for c in children} == {root["step"]}
    # The names the goodput ledger and the health scorer read are still there.
    names = {e["name"] for e in journal.snapshot()}
    assert {"quorum", "commit_barrier", "commit", "vote_send"} <= names
