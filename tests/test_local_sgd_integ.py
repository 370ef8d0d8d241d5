"""DiLoCo integration: multi-group semi-sync training with fault injection
(parity: local_sgd_integ_test.py — recovery, streaming fragments, asserting
per-fragment global state + outer optimizer equality across replicas)."""

import numpy as np
import jax
import pytest

from torchft_tpu.coordination import LighthouseServer

from ft_harness import (
    EventInjector,
    Runner,
    diloco_live_state,
    diloco_train_loop,
    run_replica_groups,
)


@pytest.fixture()
def lighthouse():
    server = LighthouseServer(
        min_replicas=1,
        join_timeout_ms=10000,
        heartbeat_timeout_ms=1000,
        quorum_tick_ms=20,
    )
    yield server
    server.shutdown()


def assert_equal_global_state(results) -> None:
    """Per-fragment backups and outer optimizer state bitwise equal across
    replica groups (parity: local_sgd_integ_test.assert_equal_global_state)."""
    reference = results[0][0]["global_state"]
    for group_result in results[1:]:
        state = group_result[0]["global_state"]
        assert len(state) == len(reference)
        for frag_ref, frag in zip(reference, state):
            for b_ref, b in zip(frag_ref["backup"], frag["backup"]):
                assert b_ref.tobytes() == b.tobytes(), "fragment backup differs"
            leaves_ref = jax.tree_util.tree_leaves(frag_ref["outer_opt"])
            leaves = jax.tree_util.tree_leaves(frag["outer_opt"])
            for l_ref, l in zip(leaves_ref, leaves):
                if hasattr(l_ref, "tobytes"):
                    assert np.asarray(l_ref).tobytes() == np.asarray(l).tobytes()


@pytest.mark.parametrize("n_fragments,delay", [(1, 0), (2, 0), (2, 1)])
def test_diloco_two_groups_healthy(lighthouse, n_fragments, delay) -> None:
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=diloco_train_loop,
            use_async_quorum=False,
            train_loop_args={
                "num_syncs": 4,
                "sync_every": 4,
                "n_fragments": n_fragments,
                "fragment_sync_delay": delay,
            },
        )
        for i in range(2)
    ]
    results = run_replica_groups(runners, timeout=180)
    for group_result in results:
        assert group_result[0]["manager_state"]["step"] == 4
    assert_equal_global_state(results)


def test_diloco_recovery_after_kill(lighthouse) -> None:
    injector = EventInjector().fail_at(group=1, step=1)
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=diloco_train_loop,
            use_async_quorum=False,
            injector=injector,
            train_loop_args={"num_syncs": 4, "sync_every": 4, "n_fragments": 2},
        )
        for i in range(2)
    ]
    results = run_replica_groups(runners, timeout=240)
    assert injector.count == 1
    for group_result in results:
        assert group_result[0]["manager_state"]["step"] == 4
    assert_equal_global_state(results)
    # North star (BASELINE.md): the kill costs the surviving group at most
    # one outer step (the in-flight sync when its peer died).
    assert results[0][0]["failed_syncs"] <= 1, results[0][0]["failed_syncs"]


@pytest.mark.parametrize("wire", ["fp8", "int4"])
def test_diloco_quantized_two_groups(lighthouse, monkeypatch, wire) -> None:
    """The quantized device pipeline: pseudograds quantized on device, only
    the wire payload crosses the host boundary; global state must still
    converge bitwise across groups — for the default fp8 format and the
    packed-int4 half-width format alike (TPUFT_WIRE_DTYPE threads through
    the whole pipeline: device codec -> wire -> fused reduce)."""
    monkeypatch.setenv("TPUFT_WIRE_DTYPE", wire)
    # Spy on the device codec so a silent fallback to fp8 cannot pass the
    # int4 case: record the payload dtype the pipeline actually produces.
    import ml_dtypes

    from torchft_tpu.ops import quantization as q

    seen_dtypes = []
    orig_quantize = q.quantize_blocks_device

    def spy(x, block=q.BLOCK, wire=None):
        payload, scales = orig_quantize(x, block, wire=wire)
        seen_dtypes.append(np.dtype(payload.dtype))
        return payload, scales

    monkeypatch.setattr(q, "quantize_blocks_device", spy)
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=diloco_train_loop,
            use_async_quorum=False,
            train_loop_args={
                "num_syncs": 3,
                "sync_every": 2,
                "n_fragments": 1,
                "should_quantize": True,
            },
        )
        for i in range(2)
    ]
    results = run_replica_groups(runners, timeout=180)
    for group_result in results:
        assert group_result[0]["manager_state"]["step"] == 3
    assert_equal_global_state(results)
    expected = np.uint8 if wire == "int4" else np.dtype(ml_dtypes.float8_e4m3fn)
    assert seen_dtypes and all(d == expected for d in seen_dtypes), seen_dtypes


def _state_digest(user_state, keys) -> str:
    """sha256 over the registered DiLoCo keys' leaves, by copy (a view of a
    CPU jax.Array would pin its buffer and keep it from being donated)."""
    import hashlib

    digest = hashlib.sha256()
    for key in sorted(keys):
        for leaf in jax.tree_util.tree_leaves(user_state[key]):
            if hasattr(leaf, "shape"):
                digest.update(np.array(leaf, copy=True).tobytes())
    return digest.hexdigest()


def test_diloco_joiner_gets_the_quorum_steps_state_while_the_donor_steps_on(
    lighthouse,
) -> None:
    """The inner step deletes the state it replaces, and a heal's capture
    is staged and fetched while the donor runs the inner steps of its
    ``fragment_sync_delay`` window. The guarantee: the joiner applies the
    donor's leaves, inner state, backups and outer state AS OF the quorum
    step, bitwise, whatever the donor has stepped since. The donor's side
    is read off its live attributes at the capture (its train thread waits
    in the quorum), the joiner's off what its load functions are given;
    the joiner's fetch is held back so that the donor's window runs first."""
    import threading
    import time

    from torchft_tpu import metrics

    delay, held_s = 3, 1.0
    lock = threading.Lock()
    donor_captures = {}  # manager step -> digests of the live state at a capture
    donor_steps = []  # (monotonic time, manager step) of the donor's inner steps
    joiner_loads = []  # (manager step, digest) of what a heal applied
    fetches = []  # (start, end) of the held-back fetch

    def on_algo(runner, manager, algo) -> None:
        keys = ["diloco_inner"] + [frag._key for frag in algo._fragments]
        if runner.replica_group == 0:
            capture = manager._manager_state_dict

            def watched_capture():
                with lock:
                    donor_captures.setdefault(manager.current_step(), []).append(
                        _state_digest(diloco_live_state(algo), keys)
                    )
                return capture()

            manager._manager_state_dict = watched_capture
            step = algo.step

            def watched_step(grads):
                with lock:
                    donor_steps.append((time.monotonic(), manager.current_step()))
                return step(grads)

            algo.step = watched_step
            return

        loads = manager._load_state_dict_fns
        pending = {}

        def watch(key, load):
            def watched_load(state):
                pending[key] = state
                load(state)
                if set(keys) <= set(pending):
                    with lock:
                        joiner_loads.append(
                            (manager.current_step(), _state_digest(pending, keys))
                        )
                    pending.clear()

            return watched_load

        for key in keys:
            loads[key] = watch(key, loads[key])
        transport = manager._checkpoint_transport
        recv = transport.recv_checkpoint

        def held_recv(*args, **kwargs):
            start = time.monotonic()
            time.sleep(held_s)
            try:
                return recv(*args, **kwargs)
            finally:
                with lock:
                    fetches.append((start, time.monotonic()))

        transport.recv_checkpoint = held_recv

    copies_before = metrics.counter_total(
        "tpuft_state_snapshot_copies_total", key="diloco_inner"
    )
    injector = EventInjector().fail_at(group=1, step=2)
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=diloco_train_loop,
            use_async_quorum=False,
            injector=injector,
            train_loop_args={
                "num_syncs": 6,
                "sync_every": 8,
                "n_fragments": 2,
                "fragment_sync_delay": delay,
                "should_quantize": True,
                "on_algo": on_algo,
            },
        )
        for i in range(2)
    ]
    results = run_replica_groups(runners, timeout=240)
    assert injector.count == 1
    for group_result in results:
        assert group_result[0]["manager_state"]["step"] == 6
    assert_equal_global_state(results)

    healed = [(step, digest) for step, digest in joiner_loads if step > 0]
    assert healed, "the restarted group never healed from the survivor"
    for step, digest in joiner_loads:
        assert step in donor_captures, (step, sorted(donor_captures))
        assert set(donor_captures[step]) == {digest}, (
            f"the state applied at step {step} is not the donor's at that step"
        )
    # The donor did run its delay window while a fetch was held back.
    stepped_meanwhile = [
        t for t, _ in donor_steps for start, end in fetches if start < t < end
    ]
    assert len(stepped_meanwhile) >= delay, (len(stepped_meanwhile), fetches)
    assert (
        metrics.counter_total("tpuft_state_snapshot_copies_total", key="diloco_inner")
        - copies_before
        >= len(joiner_loads)
    )
