"""Manager integration tests: multi-replica-group training with injected
faults, asserting the master invariant — bitwise state equality across
replica groups after recovery (parity: manager_integ_test.py:334-421)."""

import numpy as np
import jax
import pytest

from torchft_tpu.coordination import LighthouseServer

from ft_harness import (
    EventInjector,
    Runner,
    ddp_train_loop,
    pipelined_ddp_train_loop,
    run_replica_groups,
    step_fn_ddp_train_loop,
)


@pytest.fixture()
def lighthouse():
    # join_timeout must exceed worst-case step skew (GIL scheduling on the
    # 1-core CI box) so a slow-but-alive group is waited for instead of being
    # dropped — dropping it forks the gradient history, which is exactly what
    # the bitwise-equality invariant exists to catch. Dead replicas still
    # leave fast via the 1s heartbeat expiry.
    server = LighthouseServer(
        min_replicas=1,
        join_timeout_ms=10000,
        heartbeat_timeout_ms=1000,
        quorum_tick_ms=20,
    )
    yield server
    server.shutdown()


def assert_pytree_equal(a, b) -> None:
    leaves_a, tree_a = jax.tree_util.tree_flatten(a)
    leaves_b, tree_b = jax.tree_util.tree_flatten(b)
    assert tree_a == tree_b
    for la, lb in zip(leaves_a, leaves_b):
        if hasattr(la, "shape"):
            assert np.asarray(la).tobytes() == np.asarray(lb).tobytes(), "pytree leaves differ"
        else:
            assert la == lb


def assert_groups_converged(results, num_steps: int) -> None:
    """All replica groups reached num_steps with bitwise-identical params."""
    reference = results[0][0]["state_dict"]["params"]
    for group_result in results:
        rank_result = group_result[0]
        assert rank_result["manager_state"]["step"] == num_steps
        assert_pytree_equal(rank_result["state_dict"]["params"], reference)


@pytest.mark.parametrize("use_async_quorum", [True, False])
def test_ddp_two_groups_healthy(lighthouse, use_async_quorum) -> None:
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=ddp_train_loop,
            num_steps=3,
            use_async_quorum=use_async_quorum,
        )
        for i in range(2)
    ]
    results = run_replica_groups(runners)
    assert_groups_converged(results, 3)


def test_ddp_recovery_after_replica_kill(lighthouse) -> None:
    injector = EventInjector().fail_at(group=1, step=1)
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=ddp_train_loop,
            num_steps=4,
            injector=injector,
        )
        for i in range(2)
    ]
    results = run_replica_groups(runners, timeout=180)
    assert injector.count == 1
    assert_groups_converged(results, 4)
    # North star (BASELINE.md): a kill costs the survivor < 1 step — at most
    # the in-flight commit may fail when the peer vanishes mid-allreduce.
    assert results[0][0]["failed_commits"] <= 1, results[0][0]["failed_commits"]


def test_step_fn_kill_and_heal_donor_reads_its_in_place_state_by_reference(
    lighthouse,
) -> None:
    """Two groups through ``make_step_fn`` at depth 0; group 1 dies at step 2
    and comes back only after the survivor has trained alone, its state
    updated IN PLACE by every lone step (``tpuft_step_state_donated_total``
    grows), and then heals from it. The donor's ``send_checkpoint`` reads
    that state by reference, while the train thread waits for the quorum:
    no device copy is paid (``tpuft_state_snapshot_copies_total`` stays), and
    the groups end bitwise equal."""
    from torchft_tpu import metrics

    copies = metrics.counter_total("tpuft_state_snapshot_copies_total", key="optimizer")
    donated = metrics.counter_total("tpuft_step_state_donated_total")
    heals = metrics.counter_total("tpuft_heals_total", role="donor")
    injector = EventInjector().fail_at(group=1, step=2)
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=step_fn_ddp_train_loop,
            train_loop_args={"rejoin_after_step": 4, "lone_step_sleep": 0.05},
            num_steps=100,
            injector=injector,
        )
        for i in range(2)
    ]
    results = run_replica_groups(runners, timeout=180)
    assert injector.count == 1
    assert_groups_converged(results, 100)
    assert metrics.counter_total("tpuft_heals_total", role="donor") > heals
    assert metrics.counter_total("tpuft_step_state_donated_total") >= donated + 2
    assert metrics.counter_total(
        "tpuft_state_snapshot_copies_total", key="optimizer"
    ) == copies


def test_ddp_pipelined_two_groups_healthy(lighthouse) -> None:
    """Pipelined-commit FT-DDP across two replica groups: verdicts resolve
    one step late, batches ride the dispatch prediction, and the groups
    still end bitwise identical at exactly num_steps."""
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=pipelined_ddp_train_loop,
            num_steps=4,
        )
        for i in range(2)
    ]
    results = run_replica_groups(runners, timeout=180)
    assert_groups_converged(results, 4)
    # A healthy run never rolls back.
    for group_result in results:
        assert group_result[0]["rollbacks"] == 0
        assert group_result[0]["failed_commits"] == 0


def test_ddp_pipelined_kill_rolls_back_uncommitted_step(lighthouse) -> None:
    """SIGKILL-equivalent (simulated process death, the harness's kill
    model) of one replica group while the survivor has a pipelined vote in
    flight: the survivor's in-flight step cannot commit once its peer
    vanishes mid-collective, so it must ROLL BACK the speculatively
    adopted update — and after the peer restarts and heals, both groups
    must be bitwise identical at the target step (the uncommitted
    speculation never leaked into committed history)."""
    injector = EventInjector().fail_at(group=1, step=2)
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=pipelined_ddp_train_loop,
            num_steps=5,
            injector=injector,
        )
        for i in range(2)
    ]
    results = run_replica_groups(runners, timeout=240)
    assert injector.count == 1
    assert_groups_converged(results, 5)
    survivor = results[0][0]
    # The survivor discovered the dead peer through a failed pipelined
    # commit and refused the speculative update (rollback >= 1); it lost
    # at most the in-flight step.
    assert survivor["rollbacks"] >= 1, survivor
    assert survivor["failed_commits"] >= 1, survivor
    assert survivor["failed_commits"] <= 2, survivor


def test_ddp_pipelined_depth2_two_groups_healthy(lighthouse) -> None:
    """Depth-2 speculative window across two replica groups: verdicts
    resolve TWO steps late, batches ride the dispatch prediction, and the
    groups still end bitwise identical at exactly num_steps."""
    import functools

    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=functools.partial(pipelined_ddp_train_loop, depth=2),
            num_steps=5,
        )
        for i in range(2)
    ]
    results = run_replica_groups(runners, timeout=180)
    assert_groups_converged(results, 5)
    for group_result in results:
        assert group_result[0]["rollbacks"] == 0
        assert group_result[0]["failed_commits"] == 0


def test_ddp_pipelined_depth2_kill_drains_full_window(lighthouse) -> None:
    """Kill one replica group with the survivor holding a TWO-deep
    speculative window (votes in flight for both uncommitted steps): the
    refused commit must unwind the window — rollback + discard of the
    younger speculation — and the membership change must drain the FULL
    window before the PG reconfigures and the donor serves the rejoiner
    (the R7 invariant, exercised end to end). Both groups bitwise
    identical at the target step proves no speculative state leaked into
    committed history or the heal."""
    import functools

    injector = EventInjector().fail_at(group=1, step=2)
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=functools.partial(pipelined_ddp_train_loop, depth=2),
            num_steps=6,
            injector=injector,
        )
        for i in range(2)
    ]
    results = run_replica_groups(runners, timeout=240)
    assert injector.count == 1
    assert_groups_converged(results, 6)
    survivor = results[0][0]
    # The survivor discovered the dead peer through a failed pipelined
    # commit and unwound its window (>= 1 rollback); with two speculative
    # steps in flight it loses at most the whole window.
    assert survivor["rollbacks"] >= 1, survivor
    assert survivor["failed_commits"] >= 1, survivor
    assert survivor["failed_commits"] <= 3, survivor


def test_quorum_latency_north_star(lighthouse) -> None:
    """BASELINE.md north star: steady-state (fast-quorum) latency p50 stays
    within 2x the lighthouse tick. The first step is excluded — it includes
    the join/rendezvous round. Wall-clock on a 1-core GIL-scheduled box is
    noisy (CLAUDE.md), so a failing measurement is retried once before the
    assertion counts."""
    import statistics

    def measure() -> float:
        runners = [
            Runner(
                replica_group=i,
                lighthouse_addr=lighthouse.address(),
                train_loop=ddp_train_loop,
                num_steps=8,
                use_async_quorum=False,
            )
            for i in range(2)
        ]
        results = run_replica_groups(runners, timeout=180)
        assert_groups_converged(results, 8)
        steady = [t for group in results for t in group[0]["quorum_times"][1:]]
        return 1000 * statistics.median(steady)

    # Lighthouse tick is 100ms (native default, matching the reference's
    # quorum_tick_ms); fast quorum resolves without waiting a full tick.
    # Bounded retry: exactly one re-measure to damp transient 1-core machine
    # load, the first value is logged, and the SECOND measurement is
    # asserted strictly — a retry loop that hides a real regression is a
    # weaker invariant than the reference's hard bound
    # (manager_integ_test.py:539-551).
    p50_ms = measure()
    if p50_ms >= 200.0:
        print(f"first quorum p50 measurement {p50_ms:.1f}ms >= 200ms; re-measuring once")
        p50_ms = measure()
    assert p50_ms < 200.0, f"steady-state quorum p50 {p50_ms:.1f}ms >= 2x tick"


def test_ddp_recovery_after_allreduce_failure(lighthouse, tmp_path, monkeypatch) -> None:
    # Arm the flight recorder: the injected failure is guaranteed to reach
    # report_error, so exactly this test can assert the dump end to end.
    monkeypatch.setenv("TPUFT_FLIGHT_RECORDER", str(tmp_path / "fr"))
    injector = EventInjector().fail_allreduce_at(group=0, step=1)
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=ddp_train_loop,
            num_steps=4,
            injector=injector,
        )
        for i in range(2)
    ]
    results = run_replica_groups(runners, timeout=180)
    assert injector.count == 1
    assert_groups_converged(results, 4)

    import json

    dumps = list((tmp_path / "fr").glob("tpuft_fr_*.jsonl"))
    assert dumps, "injected allreduce failure produced no flight-recorder dump"
    entries = [json.loads(l) for l in dumps[0].read_text().splitlines()]
    assert "flight_recorder_dump_reason" in entries[0]
    assert any(e.get("source") == "manager" for e in entries[1:])


def test_ddp_three_groups_two_failures(lighthouse) -> None:
    injector = (
        EventInjector().fail_at(group=0, step=1).fail_allreduce_at(group=2, step=2)
    )
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=ddp_train_loop,
            num_steps=5,
            injector=injector,
        )
        for i in range(3)
    ]
    results = run_replica_groups(runners, timeout=240)
    assert injector.count == 2
    assert_groups_converged(results, 5)


def test_ddp_upscale_while_training(lighthouse) -> None:
    """A new replica group joins mid-run, heals from a donor, and converges
    (parity: local_sgd_integ_test upscale coverage). The joiner starts only
    once the running pair has visibly committed steps — sleep-based joining
    is flaky under jit-warmup variance."""
    import threading
    import time as _time

    from torchft_tpu.coordination import LighthouseClient

    num_steps = 60
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=ddp_train_loop,
            num_steps=num_steps,
        )
        for i in range(3)
    ]
    results: dict = {}

    def run(idx: int) -> None:
        results[idx] = runners[idx].run_replica()

    def run_late_joiner() -> None:
        client = LighthouseClient(lighthouse.address())
        deadline = _time.monotonic() + 60
        while _time.monotonic() < deadline:
            status = client.status()
            steps = [m.member.step for m in status.members if not m.joining]
            if steps and 2 <= max(steps) <= num_steps // 3:
                break
            _time.sleep(0.1)
        client.close()
        results[2] = runners[2].run_replica()

    threads = [
        threading.Thread(target=run, args=(0,)),
        threading.Thread(target=run, args=(1,)),
        threading.Thread(target=run_late_joiner),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    assert set(results) == {0, 1, 2}
    ordered = [results[i] for i in range(3)]
    # The joiner healed mid-run: it committed fewer batches than a
    # from-the-start member would have.
    assert results[2][0]["manager_state"]["batches_committed"] < num_steps * 3
    assert_groups_converged(ordered, num_steps)


def test_ddp_multi_rank_replica_groups(lighthouse) -> None:
    """2 replica groups x 2 local ranks: per-rank PGs spanning groups, the
    local-rank gather in the manager server, and the commit AND-barrier."""
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=ddp_train_loop,
            num_steps=3,
            world_size=2,
        )
        for i in range(2)
    ]
    results = run_replica_groups(runners, timeout=240)
    # Every rank of every group reaches the step count; params equal across
    # groups (rank 0's view).
    for group_result in results:
        assert len(group_result) == 2
        for rank_result in group_result:
            assert rank_result["manager_state"]["step"] == 3
    assert_groups_converged(results, 3)


def test_quorum_and_commit_timeout_paths_are_fast(lighthouse) -> None:
    """Timeout paths return quickly (parity: manager_integ_test.py:539-551
    asserts <1s; allow CI slack)."""
    import time as _time

    from torchft_tpu.manager import Manager
    from torchft_tpu.parallel.process_group import ProcessGroupDummy
    from torchft_tpu.parallel.store import StoreClient, StoreServer

    store = StoreServer()
    manager = Manager(
        pg=ProcessGroupDummy(),
        min_replica_size=1,
        store=StoreClient(store.address()),
        store_addr=store.address(),
        group_rank=0,
        group_world_size=2,  # rank 1 never arrives -> gather can't complete
        lighthouse_addr=lighthouse.address(),
        replica_id="timeouts",
        heartbeat_interval=0.05,
        timeout=5.0,
    )
    try:
        start = _time.monotonic()
        manager.start_quorum(timeout=0.2)
        # The gather can never complete; the timeout must surface promptly
        # (reference semantics: the quorum error propagates to the train
        # loop, whose supervisor restarts it).
        with pytest.raises(Exception):
            manager.wait_quorum()
        elapsed = _time.monotonic() - start
        assert elapsed < 3.0
    finally:
        manager.shutdown(wait=False)
        store.shutdown()


def test_ddp_fp8_gradient_sync_two_groups(lighthouse, monkeypatch) -> None:
    """fp8 device-quantized DDP gradient sync: converges across groups within
    quantization tolerance and stays bitwise identical between replicas.
    The tiny bucket cap forces the quantized path through MULTIPLE pipelined
    wire messages (one per bucket), not one staged payload."""
    import threading

    monkeypatch.setenv("TPUFT_BUCKET_MB", "0.001")

    from torchft_tpu.ddp import ft_allreduce_gradients
    from torchft_tpu.manager import Manager
    from torchft_tpu.parallel.native_pg import ProcessGroupNative
    from torchft_tpu.parallel.store import StoreClient, StoreServer

    results = {}
    errors = {}

    def group(idx: int) -> None:
        store = StoreServer()
        pg = ProcessGroupNative(timeout=10.0)
        manager = Manager(
            pg=pg,
            min_replica_size=1,
            store=StoreClient(store.address()),
            store_addr=store.address(),
            group_rank=0,
            lighthouse_addr=lighthouse.address(),
            replica_id=f"fp8ddp_{idx}",
            heartbeat_interval=0.05,
            timeout=10.0,
            quorum_timeout=20.0,
            init_sync=False,
        )
        import jax.numpy as jnp

        try:
            grads = {"w": jnp.full((512,), float(idx + 1), jnp.float32),
                     "b": jnp.full((64,), -2.0 * (idx + 1), jnp.float32)}
            manager.start_quorum()
            avg = ft_allreduce_gradients(manager, grads, should_quantize=True)
            assert manager.should_commit()
            results[idx] = jax.tree_util.tree_map(np.asarray, avg)
        except BaseException as e:  # noqa: BLE001 — surfaced by the assert below
            errors[idx] = e
        finally:
            manager.shutdown(wait=False)
            pg.shutdown()
            store.shutdown()

    threads = [threading.Thread(target=group, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not any(t.is_alive() for t in threads), "replica group thread hung"
    assert not errors, f"replica group failed: {errors}"
    assert set(results) == {0, 1}
    # Average of 1s and 2s = 1.5; of -2s and -4s = -3 (fp8 exact for these).
    np.testing.assert_allclose(results[0]["w"], np.full(512, 1.5), rtol=0.05)
    np.testing.assert_allclose(results[0]["b"], np.full(64, -3.0), rtol=0.05)
    for key in results[0]:
        assert results[0][key].tobytes() == results[1][key].tobytes()


def _make_solo_manager(lighthouse, replica_id: str):
    """A world-size-1 Manager on a dummy PG with its own store (shared
    boilerplate for the coordination-focused integ tests)."""
    from torchft_tpu.manager import Manager
    from torchft_tpu.parallel.process_group import ProcessGroupDummy
    from torchft_tpu.parallel.store import StoreClient, StoreServer

    store = StoreServer()
    manager = Manager(
        pg=ProcessGroupDummy(),
        min_replica_size=1,
        store=StoreClient(store.address()),
        store_addr=store.address(),
        group_rank=0,
        lighthouse_addr=lighthouse.address(),
        replica_id=replica_id,
        heartbeat_interval=0.05,
        timeout=5.0,
        quorum_timeout=10.0,
        init_sync=False,
    )
    manager.register_state_dict_fn("s", lambda s: None, lambda: {"x": 1})
    return manager, store


def test_shrink_only_quorum_blocks_new_joiner(lighthouse) -> None:
    """shrink_only end to end: an established group requesting shrink-only
    quorums keeps a new joiner out until it stops shrinking (reference
    lighthouse.rs:195-200 behavior through the whole stack)."""
    import threading
    import time as _time

    from torchft_tpu.coordination import LighthouseClient

    mgr_a, store_a = _make_solo_manager(lighthouse, "shrink_0")
    mgr_b = store_b = None
    joiner_result = {}

    try:
        # Establish a prev quorum containing only A.
        mgr_a.start_quorum()
        mgr_a.wait_quorum()
        assert mgr_a.num_participants() == 1

        # B tries to join while A requests shrink-only quorums.
        mgr_b, store_b = _make_solo_manager(lighthouse, "shrink_1")

        def joiner() -> None:
            try:
                mgr_b.start_quorum()
                mgr_b.wait_quorum()
                joiner_result["participants"] = mgr_b.num_participants()
            except Exception as e:  # noqa: BLE001
                joiner_result["error"] = e

        t = threading.Thread(target=joiner)
        t.start()

        # Gate on OBSERVED state, not thread timing: wait until the
        # lighthouse reports B as a pending (joining) participant.
        client = LighthouseClient(lighthouse.address())
        deadline = _time.monotonic() + 30
        while _time.monotonic() < deadline:
            status = client.status()
            joining = [
                m.member.replica_id for m in status.members if m.joining
            ]
            if any(rid.startswith("shrink_1") for rid in joining):
                break
            _time.sleep(0.05)
        else:
            raise AssertionError("joiner never registered at the lighthouse")

        for _ in range(3):
            mgr_a.start_quorum(shrink_only=True)
            mgr_a.wait_quorum()
            # Shrink-only quorums never admit B.
            assert mgr_a.num_participants() == 1
            _time.sleep(0.1)

        # A relaxes: the next normal quorum admits B and unparks it.
        deadline = _time.monotonic() + 30
        while "participants" not in joiner_result and "error" not in joiner_result:
            mgr_a.start_quorum(shrink_only=False)
            mgr_a.wait_quorum()
            if _time.monotonic() > deadline:
                break
            _time.sleep(0.1)
        t.join(timeout=30)
        client.close()
        assert joiner_result.get("participants") == 2, joiner_result
    finally:
        if mgr_b is not None:
            mgr_b.shutdown(wait=False)
        if store_b is not None:
            store_b.shutdown()
        mgr_a.shutdown(wait=False)
        store_a.shutdown()


# ---------------------------------------------------------------------------
# Heal-path hardening drills (threads-as-replicas; see also the pure-Python
# transport-level versions in tests/test_heal_hardening.py, which carry the
# same properties in containers without the native toolchain).
# ---------------------------------------------------------------------------


def test_donor_dies_mid_heal_joiner_fails_over_and_resumes(lighthouse) -> None:
    """Kill one of three groups, then cut the donor's heal stream partway
    through (chunks 2+ of 4 die for longer than the joiner's fetch
    window — the SIGKILLed-donor shape as seen from the wire): the joiner
    must fail the attempt cleanly, re-enter quorum as joining, and
    complete the heal on a later assignment by re-fetching ONLY the
    missing chunks (the re-fetch counter pins that resume actually
    resumed). min_replica_size=3 freezes the survivors' commits while the
    joiner is out, so the heal target (step, digest) stays stable across
    attempts — the case resume exists for.

    Zero replica divergence is the master assertion, as always."""
    import threading
    import time as _time

    from ft_harness import ft_counter_delta, ft_counter_snapshot
    from torchft_tpu.checkpointing import HTTPTransport

    class DyingDonorHook:
        """Dies on chunks >= 2 for ``window`` seconds from the first death
        — longer than the joiner's 10 s fetch window, so heal attempt 1
        conclusively fails with chunks 0-1 verified and cached."""

        def __init__(self, window: float = 12.0) -> None:
            self.first_die = None
            self.window = window
            self.lock = threading.Lock()

        def __call__(self, step: int, index: int):
            if index < 2:
                return None
            with self.lock:
                now = _time.monotonic()
                if self.first_die is None:
                    self.first_die = now
                if now - self.first_die <= self.window:
                    return "die"
            return None

    hook = DyingDonorHook()

    def faulty_donor_transport(runner, rank):
        transport = HTTPTransport(num_chunks=4)
        if runner.replica_group != 2:  # healthy groups serve; 2 is killed
            transport._fault_hook = hook
        return transport

    injector = EventInjector().fail_at(group=2, step=1)
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=ddp_train_loop,
            num_steps=4,
            injector=injector,
            train_loop_args={
                "min_replica_size": 3,
                "transport_factory": faulty_donor_transport,
            },
        )
        for i in range(3)
    ]
    before = ft_counter_snapshot()
    results = run_replica_groups(runners, timeout=240)
    delta = ft_counter_delta(before, ft_counter_snapshot())
    assert injector.count == 1
    assert_groups_converged(results, 4)
    assert hook.first_die is not None, "the donor fault never fired"
    # Resume exactness: chunks 0-1 were cached by the failed attempt, so
    # only the 2 missing chunks were ever re-transferred — dying-donor
    # connection cuts never reach the wire-transfer counter.
    assert delta["chunk_refetches"] == 2, delta
    assert delta["resumed_bytes"] > 0, delta
    # The data itself was never wrong.
    assert delta["checksum_failures"] == 0, delta


def test_corrupt_heal_stream_rejected_exactly_and_never_adopted(lighthouse) -> None:
    """Kill one of two groups and bit-flip the donor's first chunk-0 serve
    during the heal: the joiner must reject + re-fetch (checksum counter
    moves by EXACTLY the injected count) and both groups must end bitwise
    identical — corrupt state never enters committed history."""
    from ft_harness import ft_counter_delta, ft_counter_snapshot
    from torchft_tpu.checkpointing import HTTPTransport

    injected = []

    def corrupt_once(step: int, index: int):
        if index == 0 and not injected:
            injected.append(1)
            return "corrupt_stream"
        return None

    def faulty_donor_transport(runner, rank):
        transport = HTTPTransport(num_chunks=4)
        if runner.replica_group == 0:  # the survivor = the donor
            transport._fault_hook = corrupt_once
        return transport

    injector = EventInjector().fail_at(group=1, step=1)
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=ddp_train_loop,
            num_steps=4,
            injector=injector,
            train_loop_args={"transport_factory": faulty_donor_transport},
        )
        for i in range(2)
    ]
    before = ft_counter_snapshot()
    results = run_replica_groups(runners, timeout=240)
    delta = ft_counter_delta(before, ft_counter_snapshot())
    assert injector.count == 1
    assert_groups_converged(results, 4)
    assert len(injected) == 1
    assert delta["checksum_failures"] == 1, delta  # exactly the injection


def test_drip_feeding_donor_fenced_by_watchdog(lighthouse, monkeypatch) -> None:
    """Kill one of two groups and make the donor's first heal serve drip
    below the progress floor: the joiner must fence it within the
    watchdog window (seconds) instead of stalling for the full fetch
    timeout, then complete the heal on a later clean serve. The drill's
    liveness bound IS the assertion: with a 10 s fetch timeout per chunk
    and a 240 s drill budget, an unfenced drip (256 B/s against ~16 KB of
    chunks = minutes per serve) would blow the budget."""
    from ft_harness import ft_counter_delta, ft_counter_snapshot
    from torchft_tpu.checkpointing import HTTPTransport
    from torchft_tpu.checkpointing import http_transport as ht

    monkeypatch.setenv(ht.ENV_HEAL_MIN_BPS, "100000")
    stalled = []

    def stall_once(step: int, index: int):
        if index == 0 and not stalled:
            stalled.append(1)
            return "stall_donor"
        return None

    def faulty_donor_transport(runner, rank):
        transport = HTTPTransport(num_chunks=4)
        if runner.replica_group == 0:
            transport._fault_hook = stall_once
        return transport

    injector = EventInjector().fail_at(group=1, step=1)
    runners = [
        Runner(
            replica_group=i,
            lighthouse_addr=lighthouse.address(),
            train_loop=ddp_train_loop,
            num_steps=4,
            injector=injector,
            train_loop_args={"transport_factory": faulty_donor_transport},
        )
        for i in range(2)
    ]
    before = ft_counter_snapshot()
    results = run_replica_groups(runners, timeout=240)
    delta = ft_counter_delta(before, ft_counter_snapshot())
    assert injector.count == 1
    assert_groups_converged(results, 4)
    assert len(stalled) == 1
    assert delta["stalled_fetches"] >= 1, delta
