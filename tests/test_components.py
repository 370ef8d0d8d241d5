"""Component parity tests: parameter server, data sampler, futures/watchdog,
optimizer protocol call counts, launcher supervision, punisher.

Parity targets: parameter_server_test.py, data_test.py, futures_test.py,
optim_test.py, and the slurm runner/punisher behavior.
"""

import sys
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from torchft_tpu import futures as ft_futures
from torchft_tpu.data import DistributedSampler
from torchft_tpu.parameter_server import ParameterServer


# -- parameter server --------------------------------------------------------


class _DoublingPS(ParameterServer):
    def forward(self, session_id, pg) -> None:
        (req,) = pg.recv([np.empty(4, dtype=np.float32)], src=1).wait(self.timeout)
        pg.send([req * 2.0], dst=1).wait(self.timeout)


def test_parameter_server_sessions() -> None:
    server = _DoublingPS(timeout=10.0)
    try:
        # Two independent sessions, each with its own 2-rank PG.
        for i in range(2):
            pg = ParameterServer.connect(server.address(), timeout=10.0)
            try:
                pg.send([np.full(4, float(i + 1), dtype=np.float32)], dst=0).wait(10)
                (result,) = pg.recv([np.empty(4, dtype=np.float32)], src=0).wait(10)
                np.testing.assert_array_equal(result, np.full(4, (i + 1) * 2.0))
            finally:
                pg.shutdown()
    finally:
        server.shutdown()


# -- data sampler ------------------------------------------------------------


def test_sampler_shards_partition_dataset() -> None:
    """All (replica, rank) shards are disjoint and cover ~the dataset."""
    seen = []
    for replica in range(2):
        for rank in range(2):
            sampler = DistributedSampler(
                dataset_size=100,
                replica_rank=replica,
                num_replica_groups=2,
                group_rank=rank,
                num_replicas=2,
                shuffle=True,
                seed=7,
            )
            assert len(sampler) == 25
            seen.append(list(sampler))
    flat = [i for shard in seen for i in shard]
    assert len(flat) == len(set(flat)) == 100


def test_sampler_epoch_changes_order_deterministically() -> None:
    sampler = DistributedSampler(50, 0, 1, shuffle=True, seed=3)
    first = list(sampler)
    sampler.set_epoch(1)
    second = list(sampler)
    assert first != second
    sampler.set_epoch(0)
    assert list(sampler) == first


def test_sampler_batches() -> None:
    sampler = DistributedSampler(64, 0, 2, batch_size=4, shuffle=False)
    batches = list(sampler.batches())
    assert all(len(b) == 4 for b in batches)
    assert len(batches) == 8  # 32 samples / 4


# -- futures / watchdog ------------------------------------------------------


def test_future_timeout_fires() -> None:
    fut: Future = Future()
    timed = ft_futures.future_timeout(fut, 0.1)
    with pytest.raises(TimeoutError):
        timed.result(timeout=5)


def test_future_timeout_passthrough() -> None:
    fut: Future = Future()
    timed = ft_futures.future_timeout(fut, 5.0)
    fut.set_result(42)
    assert timed.result(timeout=1) == 42

    fut2: Future = Future()
    timed2 = ft_futures.future_timeout(fut2, 5.0)
    fut2.set_exception(ValueError("inner"))
    with pytest.raises(ValueError, match="inner"):
        timed2.result(timeout=1)


def test_context_timeout_triggers_callback() -> None:
    fired = threading.Event()
    with ft_futures.context_timeout(fired.set, 0.1):
        time.sleep(0.3)
    assert fired.is_set()

    fired2 = threading.Event()
    with ft_futures.context_timeout(fired2.set, 5.0):
        pass
    time.sleep(0.05)
    assert not fired2.is_set()


def test_timeout_heap_gauge_shows_entries_that_outlive_their_future(monkeypatch) -> None:
    """A finished future's timed wrapper only marks its handle cancelled:
    the heap entry (and through its callback the result) stays until the
    deadline. The gauge is the heap's length, set on schedule and on pop."""
    from torchft_tpu import metrics

    manager = ft_futures._TimeoutManager()
    monkeypatch.setattr(ft_futures, "_TIMEOUT_MANAGER", manager)
    gauge = lambda: metrics.gauge_value("tpuft_timeout_heap_entries")  # noqa: E731
    done: Future = Future()
    timed = ft_futures.future_timeout(done, 0.3)
    slow = ft_futures.future_timeout(Future(), 30.0)
    assert gauge() == 2
    done.set_result("payload")
    assert timed.result(timeout=1) == "payload"
    assert gauge() == 2, "a finished future's entry is still in the heap"
    deadline = time.monotonic() + 5
    while gauge() != 1 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert gauge() == 1 and not slow.done()  # popped at its deadline, not before


def test_commit_pipeline_depth_bookkeeping() -> None:
    """CommitPipeline: depth-bounded admission, oldest-first ordering, and
    a drain that empties it — the bookkeeping the pipelined-commit
    optimizer and the manager's quorum-change hook share across threads."""
    with pytest.raises(ValueError):
        ft_futures.CommitPipeline(0)

    pipe = ft_futures.CommitPipeline(1)
    assert len(pipe) == 0 and pipe.oldest() is None and pipe.depth == 1
    rec_a, rec_b = object(), object()
    pipe.push(rec_a)
    assert len(pipe) == 1 and pipe.oldest() is rec_a
    with pytest.raises(RuntimeError, match="pipeline full"):
        pipe.push(rec_b)
    pipe.remove(rec_a)
    pipe.remove(rec_a)  # idempotent
    pipe.push(rec_b)
    assert pipe.pending() == (rec_b,)
    assert pipe.drain() == (rec_b,)
    assert len(pipe) == 0 and pipe.drain() == ()

    deep = ft_futures.CommitPipeline(2)
    deep.push(rec_a)
    deep.push(rec_b)
    assert deep.pending() == (rec_a, rec_b)  # oldest first
    assert deep.drain() == (rec_a, rec_b)

    # Dynamic re-bounding (the adaptive controller's lever): growing
    # admits more slots immediately; shrinking never evicts — admission
    # respects the new bound while existing records drain normally.
    sized = ft_futures.CommitPipeline(1)
    sized.push(rec_a)
    sized.set_depth(2)
    assert sized.depth == 2
    sized.push(rec_b)
    assert sized.pending() == (rec_a, rec_b)
    sized.set_depth(1)
    assert len(sized) == 2  # no eviction on shrink
    with pytest.raises(RuntimeError, match="pipeline full"):
        sized.push(object())
    sized.remove(rec_a)
    sized.remove(rec_b)
    with pytest.raises(ValueError):
        sized.set_depth(0)


def test_watchdog_exits_on_stalled_scheduler(monkeypatch) -> None:
    """Parity with the reference's watchdog sys.exit test (futures_test.py:97):
    a stalled scheduler loop must trigger the exit hook."""
    manager = ft_futures._TimeoutManager()
    exited = threading.Event()
    monkeypatch.setattr(manager, "_exit", lambda code: exited.set())
    monkeypatch.setattr(ft_futures, "WATCHDOG_TIMEOUT_SEC", 0.2)
    manager._ensure_started()
    # Simulate a wedged scheduler: freeze its last-tick far in the past.
    manager._last_tick = time.monotonic() - 100
    manager._watchdog_enabled = True

    # Watchdog polls at WATCHDOG/4... but it captured module constant at
    # thread start; instead call the check logic via a short wait.
    deadline = time.monotonic() + 10
    while not exited.is_set() and time.monotonic() < deadline:
        manager._last_tick = time.monotonic() - 100
        time.sleep(0.1)
    assert exited.is_set()


# -- optimizer protocol ------------------------------------------------------


def test_optimizer_calls_quorum_and_commit() -> None:
    """optim_test.py parity: begin_step -> start_quorum; step -> should_commit
    exactly once, update applied only on commit."""
    import jax.numpy as jnp
    import optax

    from test_manager import make_manager, make_quorum
    from torchft_tpu.optim import Optimizer
    from torchft_tpu.parallel.process_group import ProcessGroupDummy

    manager, client, _, _ = make_manager(pg=ProcessGroupDummy(), min_replica_size=1)
    client._quorum.return_value = make_quorum(replica_world_size=1, max_world_size=1)
    client.should_commit.side_effect = lambda rank, step, vote, timeout: vote

    params = {"w": jnp.ones(3)}
    opt = Optimizer(manager, optax.sgd(0.5), params)
    opt.begin_step()
    assert client._quorum.call_count == 1
    grads = {"w": jnp.full(3, 2.0)}
    assert opt.step(grads)
    assert client.should_commit.call_count == 1
    np.testing.assert_allclose(np.asarray(opt.params["w"]), np.zeros(3))

    # Failed commit: no update.
    client.should_commit.side_effect = None
    client.should_commit.return_value = False
    opt.begin_step()
    before = np.asarray(opt.params["w"]).copy()
    assert not opt.step(grads)
    np.testing.assert_array_equal(np.asarray(opt.params["w"]), before)


# -- launcher ----------------------------------------------------------------


def test_launch_supervises_and_restarts(tmp_path) -> None:
    """A group that dies once is relaunched; all groups finish -> exit 0."""
    from torchft_tpu.launch import supervise

    marker = tmp_path / "died_once"
    script = tmp_path / "job.py"
    script.write_text(
        "import os, sys, pathlib\n"
        f"marker = pathlib.Path({str(marker)!r})\n"
        "group = os.environ['REPLICA_GROUP_ID']\n"
        "assert 'TPUFT_LIGHTHOUSE' in os.environ\n"
        "assert os.environ['NUM_REPLICA_GROUPS'] == '2'\n"
        "if group == '1' and not marker.exists():\n"
        "    marker.write_text('x')\n"
        "    sys.exit(3)\n"
        "print('group', group, 'ok')\n"
    )
    code = supervise(
        [sys.executable, str(script)],
        num_replica_groups=2,
        relaunch_interval=0.2,
        max_restarts=2,
    )
    assert code == 0
    assert marker.exists()


def test_launch_gives_up_after_max_restarts(tmp_path) -> None:
    from torchft_tpu.launch import supervise

    script = tmp_path / "always_dies.py"
    script.write_text("import sys; sys.exit(7)\n")
    code = supervise(
        [sys.executable, str(script)],
        num_replica_groups=1,
        relaunch_interval=0.1,
        max_restarts=1,
    )
    assert code == 1


def test_coordination_public_api_documented() -> None:
    """coordination_test.py parity: the public coordination surface carries
    docstrings (it is the 'low level API' users script against)."""
    import inspect

    from torchft_tpu import coordination

    for name in coordination.__all__:
        obj = getattr(coordination, name)
        assert inspect.getdoc(obj), f"{name} lacks a docstring"


def test_sampler_state_roundtrip() -> None:
    sampler = DistributedSampler(50, 0, 2, shuffle=True, seed=9)
    sampler.set_epoch(4)
    fresh = DistributedSampler(50, 0, 2, shuffle=True, seed=0)
    fresh.load_state_dict(sampler.state_dict())
    assert list(fresh) == list(sampler)


def test_bootstrap_multi_rank_group() -> None:
    """bootstrap.init_manager wires the group store for both rank 0 (binds a
    server) and rank 1 (waits for + connects to it). Explicit args, no
    os.environ mutation (threads share the environment)."""
    import socket
    import threading
    import time as _time

    from torchft_tpu.bootstrap import init_manager
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.parallel.process_group import ProcessGroupDummy

    lighthouse = LighthouseServer(min_replicas=1, join_timeout_ms=200)
    results = {}
    # Reserve an ephemeral port for the group store.
    probe = socket.socket()
    probe.bind(("", 0))
    store_port = probe.getsockname()[1]
    probe.close()
    store_addr = f"localhost:{store_port}"

    def rank_main(rank: int) -> None:
        try:
            manager, server = init_manager(
                ProcessGroupDummy(),
                min_replica_size=1,
                group_rank=rank,
                group_world_size=2,
                store_addr=store_addr,
                lighthouse_addr=lighthouse.address(),
                heartbeat_interval=0.05,
                timeout=5.0,
                quorum_timeout=10.0,
                init_sync=False,
            )
            manager.register_state_dict_fn("s", lambda s: None, lambda: {"x": 1})
            manager.start_quorum()
            manager.wait_quorum()
            results[rank] = manager.num_participants()
            manager.shutdown(wait=False)
            if server is not None:
                server.shutdown()
        except Exception as e:  # noqa: BLE001
            results[rank] = e

    try:
        t0 = threading.Thread(target=rank_main, args=(0,))
        t1 = threading.Thread(target=rank_main, args=(1,))
        # Rank 1 starts immediately: _wait_for_store gates it on rank 0's
        # bind (observable state, not timing).
        t0.start()
        t1.start()
        t0.join(30)
        t1.join(30)
        assert results.get(0) == 1 and results.get(1) == 1, results
    finally:
        lighthouse.shutdown()


def _safe_pickle_roots():
    from torchft_tpu import _safe_pickle

    return _safe_pickle._ALLOWED_ROOTS


def test_safe_pickle_blocks_rce_gadgets_allows_ml_types() -> None:
    """Network-received pickles resolve ML-ecosystem classes but refuse the
    classic reduce gadgets (docs/security.md)."""
    import pickle

    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu._safe_pickle import (
        RestrictedUnpicklingError,
        allow_module,
        safe_loads,
    )

    # Everything tpuft puts on the wire round-trips.
    import jax

    tree = {"w": np.ones((2, 2), np.float32), "meta": ("a", 3, 2.5)}
    assert safe_loads(pickle.dumps(tree))["meta"] == ("a", 3, 2.5)
    treedef = jax.tree_util.tree_structure({"a": [1, 2], "b": 3})
    assert safe_loads(pickle.dumps(treedef)) == treedef
    _ = jnp  # jax arrays are staged to numpy before pickling

    class Evil:
        def __reduce__(self):
            import os

            return (os.system, ("true",))

    with pytest.raises(RestrictedUnpicklingError, match="os.system|posix.system"):
        safe_loads(pickle.dumps(Evil()))

    class EvilGetattr:
        def __reduce__(self):
            return (getattr, (int, "__add__"))

    with pytest.raises(RestrictedUnpicklingError, match="getattr"):
        safe_loads(pickle.dumps(EvilGetattr()))

    # The allowlist-widening gadget (round-1 review exploit): resolving
    # _safe_pickle.allow_module via REDUCE must be refused even though the
    # torchft_tpu root is allowlisted, and arbitrary module-level functions
    # under allowed roots must not resolve either.
    widen_exploit = (
        b"\x80\x04"
        + b"ctorchft_tpu._safe_pickle\nallow_module\n"
        + b"(X\x02\x00\x00\x00ostR."
    )
    with pytest.raises(RestrictedUnpicklingError, match="denied module"):
        safe_loads(widen_exploit)
    assert "os" not in _safe_pickle_roots()

    func_gadget = b"\x80\x04" + b"cnumpy\nload\n" + b"(X\x01\x00\x00\x00xtR."
    with pytest.raises(RestrictedUnpicklingError, match="non-class"):
        safe_loads(func_gadget)

    # Opt-outs: explicit allowlist extension (restored after — the allowlist
    # is process-global).
    import uuid

    from torchft_tpu import _safe_pickle

    with pytest.raises(RestrictedUnpicklingError):
        safe_loads(pickle.dumps(uuid.uuid4()))
    snapshot = set(_safe_pickle._ALLOWED_ROOTS)
    try:
        allow_module("uuid")
        value = uuid.uuid4()
        assert safe_loads(pickle.dumps(value)) == value
    finally:
        _safe_pickle._ALLOWED_ROOTS.clear()
        _safe_pickle._ALLOWED_ROOTS.update(snapshot)


def test_chrome_trace_capture_writes_span_events(tmp_path) -> None:
    """trace_span regions inside a chrome_trace capture land in a valid
    chrome://tracing JSON with name/ts/dur (reference chrome-trace export
    parity, train_ddp.py:159-174)."""
    import json

    from torchft_tpu.utils.profiling import chrome_trace, trace_span

    path = tmp_path / "trace.json"
    with chrome_trace(str(path)):
        with trace_span("tpuft::test::outer"):
            with trace_span("tpuft::test::inner"):
                time.sleep(0.01)
    data = json.loads(path.read_text())
    names = [e["name"] for e in data["traceEvents"]]
    assert "tpuft::test::outer" in names and "tpuft::test::inner" in names
    inner = next(e for e in data["traceEvents"] if e["name"] == "tpuft::test::inner")
    assert inner["ph"] == "X" and inner["dur"] >= 10_000  # >= 10ms in us
    # Spans outside a capture don't record anywhere.
    with trace_span("tpuft::test::outside"):
        pass
    assert "outside" not in path.read_text()


def test_telemetry_file_export_through_real_manager(tmp_path) -> None:
    """The telemetry attach path end to end: file-mode export captures the
    quorum/commit events a real manager emits, with the structured fields
    (job/replica/rank/quorum/step) present."""
    import json as _json

    from torchft_tpu import telemetry
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.manager import Manager
    from torchft_tpu.parallel.process_group import ProcessGroupDummy
    from torchft_tpu.parallel.store import StoreClient, StoreServer

    out = tmp_path / "events.jsonl"
    event_loggers = (
        telemetry.quorums_logger,
        telemetry.commits_logger,
        telemetry.errors_logger,
    )
    before = {id(h) for lg in event_loggers for h in lg.handlers}
    telemetry.configure_telemetry(f"file:{out}")
    added = [
        h for lg in event_loggers for h in lg.handlers if id(h) not in before
    ]
    manager = store = lighthouse = None
    try:
        lighthouse = LighthouseServer(min_replicas=1, join_timeout_ms=100)
        store = StoreServer()
        pg = ProcessGroupDummy()
        manager = Manager(
            pg=pg,
            min_replica_size=1,
            store=StoreClient(store.address()),
            store_addr=store.address(),
            lighthouse_addr=lighthouse.address(),
            replica_id="telemetry-test",
            timeout=20.0,
            quorum_timeout=30.0,
            use_async_quorum=False,
        )
        manager.register_state_dict_fn("m", lambda s: None, lambda: {"x": 1})
        manager.start_quorum()
        assert manager.should_commit()
    finally:
        if manager is not None:
            manager.shutdown(wait=False)
        if store is not None:
            store.shutdown()
        if lighthouse is not None:
            lighthouse.shutdown()
        # Detach and close ONLY the handler this test attached (an
        # application-configured TPUFT_TELEMETRY handler must survive).
        for lg in event_loggers:
            for handler in list(lg.handlers):
                if id(handler) in {id(h) for h in added}:
                    lg.removeHandler(handler)
        for handler in added:
            stream = getattr(handler, "_stream", None)
            if stream is not None and stream not in (sys.stderr, sys.stdout):
                stream.close()
    events = [_json.loads(line) for line in out.read_text().splitlines()]
    kinds = {e["event"] for e in events}
    assert "tpuft_quorums" in kinds and "tpuft_commits" in kinds
    commit = next(e for e in events if e["event"] == "tpuft_commits")
    for field in ("replica_id", "rank", "step"):
        assert field in commit, commit


def test_telemetry_otlp_mode_reports_missing_sdk() -> None:
    """The otlp attach path fails loudly (not silently) when the optional
    opentelemetry SDK is absent, naming the fix."""
    from torchft_tpu import telemetry

    try:
        import opentelemetry.sdk  # noqa: F401

        pytest.skip("opentelemetry-sdk installed; attach would succeed")
    except ImportError:
        pass
    with pytest.raises(RuntimeError, match="opentelemetry-sdk"):
        telemetry.configure_telemetry("otlp")


def test_microbatch_grad_matches_full_batch() -> None:
    """make_microbatch_grad: mean-of-means over equal chunks equals the
    full-batch gradient (token-mean loss), and the fused step with
    num_microbatches>1 produces the same update as the plain fused step.

    Deliberately an MLP with a token-mean CE, not the Llama: the numerics
    under test (scan accumulation, f32 accumulators, mean-of-means) are
    model-independent, and the Llama version compiled 5 transformer vjps
    (~19s of suite time); the microbatch x Llama composition stays covered
    by test_all_fit_levers_compose_in_one_step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchft_tpu.optim import make_jit_fused_step, make_microbatch_grad

    vocab, dim = 64, 16
    key_e, key_w, key_t = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {
        "embed": jax.random.normal(key_e, (vocab, dim), jnp.float32) * 0.1,
        "w": jax.random.normal(key_w, (dim, vocab), jnp.float32) * 0.1,
    }
    tokens = jax.random.randint(key_t, (4, 17), 0, vocab)

    def loss_fn(p, batch):
        h = jnp.tanh(p["embed"][batch[:, :-1]])
        logits = h @ p["w"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, batch[:, 1:, None], axis=-1)
        return -jnp.mean(picked)

    loss_full, g_full = jax.jit(jax.value_and_grad(loss_fn))(params, tokens)
    loss_mb, g_mb = jax.jit(make_microbatch_grad(loss_fn, 4))(params, tokens)
    np.testing.assert_allclose(float(loss_mb), float(loss_full), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
        ),
        g_mb, g_full,
    )

    tx = optax.sgd(0.1)
    opt_state = tx.init(params)
    _, p_full, _ = make_jit_fused_step(tx, loss_fn)(params, opt_state, tokens)
    _, p_mb, _ = make_jit_fused_step(tx, loss_fn, num_microbatches=2)(
        params, opt_state, tokens
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
        ),
        p_mb, p_full,
    )

    # Indivisible batch fails loudly at trace time.
    try:
        jax.jit(make_microbatch_grad(loss_fn, 3))(params, tokens)
    except ValueError as e:
        assert "not divisible" in str(e)
    else:
        raise AssertionError("expected ValueError for indivisible batch")


def test_device_prefetcher_orders_places_and_propagates() -> None:
    """DevicePrefetcher: preserves order, lands batches on device (with a
    NamedSharding when given), re-raises source exceptions, and close()
    unblocks a producer stalled on a full queue."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchft_tpu.data import DevicePrefetcher

    batches = [
        {"x": np.full((8, 4), i, np.float32), "y": np.arange(8) + i}
        for i in range(5)
    ]
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    with DevicePrefetcher(iter(batches), depth=2, sharding=sharding) as pf:
        got = list(pf)
    assert len(got) == 5
    for i, b in enumerate(got):
        assert float(b["x"][0, 0]) == i  # order preserved
        assert isinstance(b["x"], jax.Array)
        assert b["x"].sharding == sharding

    # Source exception surfaces at the consumer.
    def boom():
        yield np.zeros(2)
        raise RuntimeError("loader died")

    pf = DevicePrefetcher(boom(), depth=1)
    next(pf)
    with pytest.raises(RuntimeError, match="loader died"):
        next(pf)

    # close() releases a producer blocked on the full queue (depth=1,
    # many batches) and the thread terminates.
    pf = DevicePrefetcher((np.zeros(2) for _ in range(100)), depth=1)
    next(pf)
    pf.close()
    pf._thread.join(timeout=5)
    assert not pf._thread.is_alive()
    with pytest.raises(StopIteration):
        next(pf)

    # An ABANDONED prefetcher (reference dropped, no close) is reaped by
    # its GC finalizer: the worker only shares _PrefetchState — never the
    # prefetcher itself — so collection fires weakref.finalize, which
    # closes the state and the worker exits instead of polling forever
    # with queued device batches pinned (round-3 advisor).
    import gc
    import time as _time

    pf = DevicePrefetcher((np.zeros(2) for _ in range(100)), depth=1)
    next(pf)
    worker = pf._thread
    del pf
    gc.collect()
    deadline = _time.monotonic() + 5
    while worker.is_alive() and _time.monotonic() < deadline:
        _time.sleep(0.05)
    assert not worker.is_alive()


def test_flight_recorder_ring_and_dump(tmp_path, monkeypatch) -> None:
    """Ring records bounded entries, dump() writes JSONL, and the
    TPUFT_FLIGHT_RECORDER env turns failure hooks into dumps (the
    reference's TRIGGER_FR_ON_ABORT semantics)."""
    import json

    from torchft_tpu.utils import flight_recorder as fr

    fr.record("test", "hello", op="allreduce", n=3)
    entries = fr.snapshot()
    assert entries[-1]["event"] == "hello" and entries[-1]["op"] == "allreduce"
    seqs = [e["seq"] for e in entries]
    assert seqs == sorted(seqs)

    # Explicit dump path.
    path = tmp_path / "fr.jsonl"
    fr.dump(str(path), reason="unit")
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert lines[0]["flight_recorder_dump_reason"] == "unit"
    assert any(e.get("event") == "hello" for e in lines[1:])

    # Without the env, failure hooks are silent; with it, they dump.
    monkeypatch.delenv(fr.ENV_DIR, raising=False)
    assert fr.dump_on_failure("test", "no-env") is None
    monkeypatch.setenv(fr.ENV_DIR, str(tmp_path / "frdir"))
    out = fr.dump_on_failure("test", "boom")
    assert out is not None
    dumped = [json.loads(l) for l in open(out)]
    assert any(
        e.get("event") == "failure" and e.get("reason") == "boom"
        for e in dumped
    )

    # Non-JSON detail values are coerced, never raise.
    fr.record("test", "weird", obj=object())
    fr.dump(str(path))

    # Clean snapshots carry no truncation marker...
    entries, truncated = fr._snapshot_meta()
    assert entries and not truncated

    # ...but when the list() copy keeps losing to concurrent appends and
    # the index-walk fallback fires, the dump header records it so readers
    # know the sample may be non-contiguous.
    class _Mutating:
        def __iter__(self):
            raise RuntimeError("deque mutated during iteration")

        def __len__(self):
            return 1

        def __getitem__(self, i):
            if i == 0:
                return {"seq": 0, "event": "walked"}
            raise IndexError

    monkeypatch.setattr(fr, "_RING", _Mutating())
    entries, truncated = fr._snapshot_meta()
    assert truncated and entries == [{"seq": 0, "event": "walked"}]
    tpath = tmp_path / "fr_trunc.jsonl"
    fr.dump(str(tpath))
    tlines = [json.loads(l) for l in tpath.read_text().splitlines()]
    assert tlines[0]["truncated"] is True


def test_doctor_checks_pass_and_catch_problems(monkeypatch, capsys) -> None:
    """run_checks passes on a healthy box (live lighthouse), flags unknown
    TPUFT_* vars, and KNOWN_ENV tracks every env var the tree reads."""
    import subprocess

    from torchft_tpu import doctor
    from torchft_tpu.coordination import LighthouseServer

    lh = LighthouseServer(min_replicas=1, join_timeout_ms=500)
    try:
        rc = doctor.run_checks(lh.address(), skip_device=True)
    finally:
        lh.shutdown()
    out = capsys.readouterr().out
    assert rc == 0 and "doctor: OK" in out
    assert "lighthouse" in out and "answered" in out

    monkeypatch.setenv("TPUFT_DEFINITELY_A_TYPO", "1")
    rc = doctor.run_checks("", skip_device=True)
    out = capsys.readouterr().out
    assert "TPUFT_DEFINITELY_A_TYPO" in out

    monkeypatch.delenv("TPUFT_DEFINITELY_A_TYPO")
    monkeypatch.setenv("TPUFT_WIRE_DTYPE", "fp4")
    rc = doctor.run_checks("", skip_device=True)
    out = capsys.readouterr().out
    assert rc == 1 and "TPUFT_WIRE_DTYPE" in out
    monkeypatch.delenv("TPUFT_WIRE_DTYPE")

    # Drift guard: every TPUFT_* name used anywhere in the repo (package,
    # tests, benchmarks, scripts, top-level drivers) must be declared in
    # doctor.KNOWN_ENV, or doctor would cry typo on a real knob.
    used = _tpuft_names_in_tree()
    # Per-pair WAN link envs embed region names (TPUFT_EMULATED_LINK_US_EU,
    # ...) so they can't be enumerated; doctor's env check carries the same
    # prefix allowance and the topology check validates them instead.
    used = {n for n in used if not n.startswith("TPUFT_EMULATED_LINK_")}
    # A trailing underscore is prose naming a family ("TPUFT_SLO_*"), not
    # a variable anything reads.
    used = {n for n in used if not n.endswith("_")}
    missing = used - doctor.KNOWN_ENV - {"TPUFT_DEFINITELY_A_TYPO"}
    assert not missing, f"doctor.KNOWN_ENV missing: {sorted(missing)}"


def _tpuft_names_in_tree(skip=()) -> set:
    """Every TPUFT_* name the drift guard's directories mention, ``skip``
    (paths relative to the repo) left out."""
    import re
    from pathlib import Path

    from torchft_tpu import doctor

    repo = Path(doctor.__file__).parent.parent
    files = [
        py
        for sub in ("torchft_tpu", "tests", "benchmarks", "scripts")
        for py in (repo / sub).rglob("*.py")
    ] + [repo / "__graft_entry__.py"]
    return {
        name
        for py in files
        if str(py.relative_to(repo)) not in skip
        for name in re.findall(r"TPUFT_[A-Z_0-9]+", py.read_text())
    }


def test_doctor_known_env_keeps_no_name_whose_reader_is_gone() -> None:
    """The other direction of the drift guard: a name stays in the registry
    only while some file besides the registry mentions it. A deleted entry
    point takes its knobs out of ``doctor`` with it."""
    from torchft_tpu import doctor

    dead = doctor.KNOWN_ENV - _tpuft_names_in_tree(skip=("torchft_tpu/doctor.py",))
    assert not dead, f"doctor.KNOWN_ENV lists names nothing reads: {sorted(dead)}"


def test_metric_names_match_registry_table() -> None:
    """METRICS.md drift is now analyzer rule R8 `metric-doc-drift` (part
    of the exit-nonzero `python -m torchft_tpu.analysis` gate); this test
    wraps the rule so the suite still fails fast on drift, and pins that
    the rule actually scans (an empty emitted-set would mean the grep
    pattern rotted, which R8 would misread as "nothing to document")."""
    from torchft_tpu.analysis import core, rules

    metrics_py = core.PACKAGE_ROOT / "metrics.py"
    module = core.load_module(metrics_py)
    findings = rules.RULES_BY_ID["metric-doc-drift"].checker(module)
    assert findings == [], "\n".join(
        f"{f.file}:{f.line} {f.message}" for f in findings
    )
    # Anchor guard: the rule only fires from metrics.py — any other module
    # must yield nothing, or the repo-wide scan would run once per file.
    other = core.load_module(core.PACKAGE_ROOT / "doctor.py")
    assert rules.RULES_BY_ID["metric-doc-drift"].checker(other) == []
    # Scan-health guard: the emission grep still finds real call sites.
    emitted = set()
    for py in core.PACKAGE_ROOT.rglob("*.py"):
        if "__pycache__" in py.parts or py.name == "tpuft_pb2.py":
            continue
        emitted |= set(rules._R8_EMIT_RE.findall(py.read_text()))
    assert "tpuft_goodput_seconds_total" in emitted
    assert len(emitted) > 50, f"emission grep rotted? only {len(emitted)} names"


def test_netem_shim_pacing() -> None:
    """The emulated-DCN shim: disabled by default (zero-cost no-op), and
    when configured injects RTT/2 + bytes/bandwidth per message."""
    import time as _time

    from torchft_tpu.utils import netem

    try:
        netem.configure(0, 0)
        assert not netem.enabled()
        netem.pace(10_000_000)  # no-op when disabled (no timing assert:
        # wall-clock upper bounds flake on this 1-core box)

        # 20 ms RTT -> 10 ms one-way; 0.008 Gbps = 1e6 B/s -> 100 ms for
        # 100 KB. Lower bound is exact (sleep never undershoots); upper
        # bound generous for the GIL-loaded box.
        netem.configure(rtt_ms=20, gbps=0.008)
        assert netem.enabled()
        t0 = _time.perf_counter()
        netem.pace(100_000)
        dt = _time.perf_counter() - t0
        assert 0.11 <= dt < 2.0, dt
    finally:
        netem.configure(0, 0)


def test_heal_wall_times_helper() -> None:
    """Shared kill->first-commit timing used by bench + dryrun drills:
    role labels, post-kill filtering, and the no-kill/no-commit cases."""
    from torchft_tpu.utils.profiling import heal_wall_times

    assert heal_wall_times(None, {0: [1.0]}) is None
    out = heal_wall_times(10.0, {0: [9.0, 12.5, 14.0], 1: [9.5, 16.25], 2: []})
    assert out == {"survivor": 2.5, "joiner": 6.25, "g2": None}
