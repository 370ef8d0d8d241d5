"""Fleet trace plane unit tests (torchft_tpu/tracing.py).

Pure python, no native toolchain: journal ring semantics, the causal
tuple, per-event cost bound, thread-local journals, store-mediated clock
sampling, deterministic incident ids + auto-capture dumps (including the
flight-recorder filename satellite), the /trace.json HTTP surface, and the
Manager-level integration (events recorded at the real call sites, trace
segments pushed to the group store on the metrics cadence).
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from test_manager import _FakeStore, make_manager, make_quorum

from torchft_tpu import metrics, tracing
from torchft_tpu.parallel.process_group import ProcessGroupDummy
from torchft_tpu.utils import flight_recorder


# ---------------------------------------------------------------------------
# journal semantics
# ---------------------------------------------------------------------------


def test_journal_records_causal_tuple_and_identity() -> None:
    j = tracing.TraceJournal(maxlen=128)
    j.configure(job_id="job1", replica_id="r0", group_rank=3)
    j.set_step(7, 2)
    j.record("vote_send", vote=True)
    with j.span("commit_barrier", step=7, quorum_id=2):
        pass
    events = j.snapshot()
    assert [e["name"] for e in events] == ["vote_send", "commit_barrier"]
    instant = events[0]
    assert instant["job_id"] == "job1"
    assert instant["replica_id"] == "r0"
    assert instant["group_rank"] == 3
    assert instant["step"] == 7 and instant["quorum_id"] == 2
    assert instant["seq"] == 0 and events[1]["seq"] == 1
    assert instant["args"] == {"vote": True}
    assert "t_wall" in instant and "t_mono" in instant and "thread" in instant
    span = events[1]
    assert span["ph"] == "X" and span["dur"] >= 0
    # Span stamps are the START (merged timelines sort by entry).
    assert span["t_mono"] <= instant["t_mono"] + 10  # sanity: monotonic scale


def test_journal_ring_bound_and_drop_accounting() -> None:
    j = tracing.TraceJournal(maxlen=64)
    for i in range(200):
        j.record("e", i=i)
    assert len(j.snapshot()) == 64
    assert j.dropped() == 200 - 64
    # Everything still in the ring drains; the overwritten events count as
    # dropped-before-export exactly once.
    metrics.REGISTRY.reset()
    segment = j.drain_segment()
    assert len(segment) == 64
    assert metrics.counter_total("tpuft_trace_events_total") == 64
    assert metrics.counter_total("tpuft_trace_dropped_total") == 200 - 64
    # Incremental: nothing new -> empty segment, no double counting.
    assert j.drain_segment() == []
    j.record("late")
    seg2 = j.drain_segment()
    assert [e["name"] for e in seg2] == ["late"]
    assert metrics.counter_total("tpuft_trace_dropped_total") == 200 - 64


def test_journal_disabled_records_nothing(monkeypatch) -> None:
    j = tracing.TraceJournal(maxlen=64, enabled=False)
    j.record("e")
    with j.span("s"):
        pass
    assert j.snapshot() == []
    # Env switch honored at construction.
    monkeypatch.setenv(tracing.ENV_TRACE, "0")
    j2 = tracing.TraceJournal(maxlen=64)
    j2.record("e")
    assert j2.snapshot() == [] and not j2.enabled


def test_journal_never_raises_on_unjsonable_args() -> None:
    class Bad:
        def __repr__(self) -> str:
            raise RuntimeError("no repr")

    j = tracing.TraceJournal(maxlen=16)
    j.record("e", weird=Bad(), ok=1)
    event = j.snapshot()[0]
    assert event["args"]["ok"] == 1
    assert "unreprable" in event["args"]["weird"]
    json.dumps(event)  # the whole record stays JSON-safe


def test_recording_overhead_is_bounded() -> None:
    """The acceptance bound: recording is a dict build + deque append.
    Measured ~2 us/event on this box; the pin is 50x that so a loaded
    1-core CI container cannot flake it, while still guaranteeing the
    per-event cost cannot silently grow to something step-visible."""
    j = tracing.TraceJournal(maxlen=4096)
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        j.record("device_sync", ph="X", dur=0.001, step=1, quorum_id=2)
    per_event = (time.perf_counter() - t0) / n
    assert per_event < 100e-6, f"record() cost {per_event * 1e6:.1f} us/event"
    t0 = time.perf_counter()
    for i in range(n):
        with j.span("s", step=1):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 200e-6, f"span() cost {per_span * 1e6:.1f} us/span"


def test_thread_local_journals_isolate_replicas() -> None:
    """Threads-as-replicas: each replica thread installs its own journal;
    module-level record() routes to it, and a Manager created on that
    thread keeps recording there from its quorum thread."""
    j_a, j_b = tracing.TraceJournal(maxlen=64), tracing.TraceJournal(maxlen=64)

    def replica(journal, tag):
        with tracing.use_journal(journal):
            assert tracing.current() is journal
            tracing.record("hello", tag=tag)

    threads = [
        threading.Thread(target=replica, args=(j_a, "a")),
        threading.Thread(target=replica, args=(j_b, "b")),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert [e["args"]["tag"] for e in j_a.snapshot()] == ["a"]
    assert [e["args"]["tag"] for e in j_b.snapshot()] == ["b"]
    assert tracing.current() is tracing.default()


def test_phase_rollup_groups_by_step() -> None:
    j = tracing.TraceJournal(maxlen=256)
    for step in (1, 2):
        with j.span("quorum", step=step, quorum_id=5):
            pass
        j.record("commit_barrier", ph="X", dur=0.25 * step, step=step, quorum_id=5)
        j.record("wire_bucket", ph="X", dur=0.1, step=step)
        j.record("wire_bucket", ph="X", dur=0.2, step=step)
        j.record("commit" if step == 1 else "commit_failed", step=step)
    rollup = j.phase_rollup()
    assert [r["step"] for r in rollup] == [1, 2]
    assert rollup[0]["committed"] is True and rollup[1]["committed"] is False
    assert rollup[0]["phases"]["commit_barrier"] == pytest.approx(0.25)
    # Repeated spans at one step accumulate.
    assert rollup[0]["phases"]["wire_bucket"] == pytest.approx(0.3)
    assert rollup[1]["phases"]["commit_barrier"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# incidents + dumps (flight-recorder filename satellite)
# ---------------------------------------------------------------------------


def test_incident_id_is_deterministic_across_processes() -> None:
    a = tracing.incident_id("rollback", 12, 4)
    b = tracing.incident_id("rollback", 12, 4)
    assert a == b == "inc-rollback-q4-s12"
    assert tracing.incident_id("heal_exhausted", 12, 4) != a


def test_open_incident_dumps_journal_and_flight_recorder(
    tmp_path, monkeypatch
) -> None:
    monkeypatch.setenv("TPUFT_FLIGHT_RECORDER", str(tmp_path))
    j = tracing.TraceJournal(maxlen=64)
    j.configure(replica_id="train_0", group_rank=1)
    j.record("rollback", step=9, quorum_id=3)
    with tracing.use_journal(j):
        iid = tracing.open_incident("rollback", 9, 3, journal=j, reason="refused")
        assert iid == "inc-rollback-q3-s9"
        assert tracing.active_incident(j) == iid

        trace_dumps = list(tmp_path.glob("tpuft_trace_*.jsonl"))
        fr_dumps = list(tmp_path.glob("tpuft_fr_*.jsonl"))
    assert len(trace_dumps) == 1 and len(fr_dumps) == 1
    # Satellite: both filenames carry the replica identity AND the
    # incident id — correlatable across hosts by name alone.
    for dump in (trace_dumps[0], fr_dumps[0]):
        assert "train_0" in dump.name and iid in dump.name
    lines = [json.loads(l) for l in trace_dumps[0].read_text().splitlines()]
    assert lines[0]["trace_header"] and lines[0]["incident"] == iid
    assert any(rec.get("name") == "incident" for rec in lines[1:])
    fr_lines = [json.loads(l) for l in fr_dumps[0].read_text().splitlines()]
    assert fr_lines[0]["incident"] == iid
    # A commit clears the incident window: the next dump gets no stamp.
    tracing.clear_incident(j)
    assert tracing.active_incident(j) is None


def test_dump_on_failure_reuses_active_incident(tmp_path, monkeypatch) -> None:
    monkeypatch.setenv("TPUFT_FLIGHT_RECORDER", str(tmp_path))
    j = tracing.TraceJournal(maxlen=64)
    j.configure(replica_id="train_1", group_rank=0)
    with tracing.use_journal(j):
        j.active_incident = "inc-rollback-q1-s5"
        path = flight_recorder.dump_on_failure("test", "late failure")
        assert path is not None
        assert "inc-rollback-q1-s5" in os.path.basename(path)
        assert "train_1_0" in os.path.basename(path)
        j.active_incident = None
        path2 = flight_recorder.dump_on_failure("test", "clean era")
        assert "inc-" not in os.path.basename(path2)


# ---------------------------------------------------------------------------
# store-mediated clock sampling
# ---------------------------------------------------------------------------


def test_clock_sampler_recovers_gross_skew() -> None:
    """Two processes sharing a store, one 7.5 s ahead: the beacon owner
    claims the key, the skewed sampler estimates its offset within the
    sampling window bound."""
    store = _FakeStore()
    j_ref = tracing.TraceJournal(maxlen=64)  # reference clock: real time
    skew = 7.5
    j_skew = tracing.TraceJournal(maxlen=64, wall=lambda: time.time() + skew)
    ref = tracing.StoreClockSampler(j_ref, owner_key="a/0", claim=True)
    other = tracing.StoreClockSampler(j_skew, owner_key="b/0", claim=False)

    ref.tick(store)  # writes the beacon
    assert store.data.get(tracing.CLOCK_REF_KEY) is not None
    other.tick(store)  # first read: no prev window yet -> no sample
    assert other.last_offset_s is None
    ref.tick(store)  # beacon counter advances
    other.tick(store)  # second read: write landed inside (prev, now]
    assert other.last_offset_s == pytest.approx(skew, abs=0.5)
    assert j_skew.clock_offset_s == pytest.approx(skew, abs=0.5)
    samples = [e for e in j_skew.snapshot() if e["name"] == "clock_sample"]
    assert len(samples) == 1
    assert samples[0]["args"]["offset_s"] == pytest.approx(skew, abs=0.5)
    # The owner's own frame is the reference: offset 0.
    ref.tick(store)
    assert ref.last_offset_s == 0.0


def test_clock_beacon_ownership_converges_to_smallest_claimer() -> None:
    store = _FakeStore()
    j1, j2 = tracing.TraceJournal(maxlen=16), tracing.TraceJournal(maxlen=16)
    big = tracing.StoreClockSampler(j1, owner_key="zz/0", claim=True)
    small = tracing.StoreClockSampler(j2, owner_key="aa/0", claim=True)
    big.tick(store)
    small.tick(store)  # smaller key takes over
    big.tick(store)  # larger key backs off
    beacon = json.loads(store.data[tracing.CLOCK_REF_KEY].decode())
    assert beacon["owner"] == "aa/0"


def test_clock_beacon_stale_takeover() -> None:
    store = _FakeStore()
    j = tracing.TraceJournal(maxlen=16)
    backup = tracing.StoreClockSampler(j, owner_key="zz/0", claim=True)
    # A dead owner's beacon: counter never advances.
    store.data[tracing.CLOCK_REF_KEY] = json.dumps(
        {"owner": "aa/0", "n": 5, "wall": time.time()}
    ).encode()
    for _ in range(backup.STALE_TAKEOVER_READS + 1):
        backup.tick(store)
    beacon = json.loads(store.data[tracing.CLOCK_REF_KEY].decode())
    assert beacon["owner"] == "zz/0"


def test_clock_sampler_survives_dead_store() -> None:
    class DeadStore:
        def get(self, *a, **k):
            raise ConnectionError("down")

        def set(self, *a, **k):
            raise ConnectionError("down")

    j = tracing.TraceJournal(maxlen=16)
    sampler = tracing.StoreClockSampler(j, owner_key="a/0", claim=True)
    sampler.tick(DeadStore())  # must not raise


# ---------------------------------------------------------------------------
# /trace.json HTTP surface
# ---------------------------------------------------------------------------


def test_trace_json_served_on_metrics_http() -> None:
    default = tracing.default()
    default.record("probe_event", step=1)
    server = metrics.start_http_server(0)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/trace.json", timeout=5
        ) as resp:
            payload = json.loads(resp.read().decode())
    finally:
        server.shutdown()
    assert payload["replica_id"] == default.replica_id
    assert "clock" in payload and "wall" in payload["clock"]
    assert any(e["name"] == "probe_event" for e in payload["events"])
    assert isinstance(payload["phases"], list)


# ---------------------------------------------------------------------------
# Manager integration: real call sites + store push
# ---------------------------------------------------------------------------


def _run_manager_steps(monkeypatch, steps=2):
    monkeypatch.setenv("TPUFT_METRICS_PUSH_SEC", "0.001")
    journal = tracing.TraceJournal(maxlen=1024)
    with tracing.use_journal(journal):
        manager, client, pg, transport = make_manager(
            pg=ProcessGroupDummy(), min_replica_size=1
        )
        client._quorum.return_value = make_quorum(
            quorum_id=4, replica_rank=0, replica_world_size=2,
            max_rank=0, max_world_size=2,
        )
        client.should_commit.side_effect = (
            lambda rank, step, vote, timeout: vote
        )
        for _ in range(steps):
            manager.start_quorum()
            manager.wait_quorum()
            manager.allreduce(np.ones(2, np.float32)).wait()
            assert manager.should_commit()
            time.sleep(0.002)  # past the push rate limit
    return manager, journal


def test_manager_records_ft_phases_and_pushes_trace(monkeypatch) -> None:
    manager, journal = _run_manager_steps(monkeypatch)
    assert manager._trace is journal  # captured the constructing thread's
    names = [e["name"] for e in journal.snapshot()]
    for expected in (
        "quorum", "quorum_ready", "quorum_change", "pg_configure",
        "vote_send", "commit_barrier", "commit",
    ):
        assert expected in names, f"missing {expected} in {names}"
    # The causal tuple tracks the manager: commits at steps 0..N, era 4.
    commits = [e for e in journal.snapshot() if e["name"] == "commit"]
    assert [c["step"] for c in commits] == [0, 1]
    assert all(c["quorum_id"] == 4 for c in commits)
    assert all(c["replica_id"] == "test_replica" for c in commits)
    # Straggler gauge: the barrier wait landed.
    assert (
        metrics.gauge_value(
            "tpuft_trace_barrier_wait_seconds",
            replica_id="test_replica", group_rank="1",
        )
        is not None
    )
    # Trace segments rode the metrics push cadence into the group store.
    key = f"trace/{manager._replica_id}/1"
    raw = manager._store.data.get(key)
    assert raw is not None, f"no trace push at {key}"
    payload = json.loads(raw.decode())
    assert payload["replica_id"] == manager._replica_id
    assert any(e["name"] == "commit" for e in payload["events"])
    assert isinstance(payload["phases"], list) and payload["phases"]
    assert "commit_barrier" in payload["phases"][-1]["phases"]


def test_manager_report_error_lands_in_journal(monkeypatch) -> None:
    journal = tracing.TraceJournal(maxlen=256)
    with tracing.use_journal(journal):
        manager, client, pg, transport = make_manager(pg=ProcessGroupDummy())
        manager.report_error(RuntimeError("injected kill"))
    events = [e for e in journal.snapshot() if e["name"] == "report_error"]
    assert len(events) == 1
    assert "injected kill" in events[0]["args"]["error"]
    assert events[0]["args"]["error_type"] == "RuntimeError"


def test_quorum_timeout_stamps_incident(tmp_path, monkeypatch) -> None:
    monkeypatch.setenv("TPUFT_FLIGHT_RECORDER", str(tmp_path))
    journal = tracing.TraceJournal(maxlen=256)
    with tracing.use_journal(journal):
        manager, client, pg, transport = make_manager(pg=ProcessGroupDummy())
        client._quorum.side_effect = TimeoutError("quorum timed out after 5s")
        # make_manager's sync-quorum mode resolves the future inside
        # start_quorum, so the timeout surfaces right there.
        with pytest.raises(TimeoutError):
            manager.start_quorum()
    incidents = [e for e in journal.snapshot() if e["name"] == "incident"]
    assert len(incidents) == 1
    assert incidents[0]["args"]["kind"] == "quorum_timeout"
    iid = incidents[0]["args"]["incident"]
    # Auto-capture: journal + flight recorder dumped under the incident id.
    assert any(iid in p.name for p in tmp_path.glob("tpuft_trace_*.jsonl"))
    assert any(iid in p.name for p in tmp_path.glob("tpuft_fr_*.jsonl"))


def test_rollback_stamps_shared_incident(tmp_path, monkeypatch) -> None:
    """The pipelined ordering's refused commit: rollback event + the
    deterministic incident id every survivor derives independently."""
    import jax.numpy as jnp
    import optax

    from torchft_tpu.optim import Optimizer

    monkeypatch.setenv("TPUFT_FLIGHT_RECORDER", str(tmp_path))
    monkeypatch.setenv("TPUFT_STRICT_COMMIT", "0")
    journal = tracing.TraceJournal(maxlen=1024)
    with tracing.use_journal(journal):
        manager, client, pg, transport = make_manager(
            pg=ProcessGroupDummy(), min_replica_size=1,
            commit_pipeline_depth=1,
        )
        client._quorum.return_value = make_quorum(
            quorum_id=2, replica_rank=0, replica_world_size=1,
            max_rank=0, max_world_size=1,
        )
        votes = iter([True, False, True])
        client.should_commit.side_effect = (
            lambda rank, step, vote, timeout: vote and next(votes)
        )
        opt = Optimizer(
            manager, optax.sgd(0.1), {"w": jnp.ones(2, jnp.float32)}
        )
        step_fn = opt.make_step_fn(lambda p, b: jnp.sum((p["w"] - b) ** 2))
        for i in range(3):
            step_fn(jnp.full((2,), float(i), jnp.float32))
        opt.flush_pipeline()
    rollbacks = [e for e in journal.snapshot() if e["name"] == "rollback"]
    assert len(rollbacks) == 1
    incidents = [
        e for e in journal.snapshot()
        if e["name"] == "incident" and e["args"]["kind"] == "rollback"
    ]
    assert len(incidents) == 1
    # Deterministic: another process at the same (step, quorum) derives it.
    assert incidents[0]["args"]["incident"] == tracing.incident_id(
        "rollback", rollbacks[0]["step"], rollbacks[0]["quorum_id"]
    )
    assert any(
        incidents[0]["args"]["incident"] in p.name
        for p in tmp_path.glob("tpuft_trace_*.jsonl")
    )


# ---------------------------------------------------------------------------
# the phase primitive: one recording site, three sinks
# ---------------------------------------------------------------------------


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: remembers what opened and
    closed, and with which keyword arguments."""

    log: list = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name, self.kwargs))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name, self.kwargs))


@pytest.fixture
def fake_annotations(monkeypatch):
    _FakeAnnotation.log = []
    monkeypatch.setattr(
        tracing, "_annotation_types", (_FakeAnnotation, _FakeAnnotation)
    )
    return _FakeAnnotation.log


def _stepping_clock(step: float = 0.25):
    now = [100.0]

    def mono() -> float:
        now[0] += step
        return now[0]

    return mono


@pytest.mark.parametrize("name", sorted(tracing.PHASES))
def test_phase_feeds_the_sinks_its_table_row_names(name, fake_annotations) -> None:
    """Every row of PHASES: the histogram, the journal event and the
    annotation it names get ONE duration from two clock reads, and a sink
    the row leaves out gets nothing."""
    spec = tracing.PHASES[name]
    journal = tracing.TraceJournal(maxlen=64, mono=_stepping_clock())
    labels = {"replica_id": f"phase_test_{name}"}
    before = (
        metrics.histogram_stats(spec.histogram, **labels)
        if spec.histogram else None
    )
    with tracing.phase(name, journal, labels, step=7, quorum_id=3, fragment=1, note="x"):
        pass
    events = journal.snapshot()
    if spec.journal is None:
        assert events == []
    else:
        (event,) = events
        assert event["name"] == spec.journal and event["ph"] == "X"
        assert (event["step"], event["quorum_id"]) == (7, 3)
        assert event["args"] == {"fragment": 1, "note": "x"}
        assert event["dur"] == pytest.approx(0.25)  # two reads of the clock
    if spec.histogram is not None:
        after = metrics.histogram_stats(spec.histogram, **labels)
        assert after["count"] - before["count"] == 1
        if spec.journal is not None:
            assert after["sum"] - before["sum"] == pytest.approx(event["dur"])
        if spec.stage is not None:
            staged = metrics.histogram_stats(
                spec.histogram, stage=spec.stage, **labels
            )
            assert staged["count"] >= 1
    if spec.annotation is None:  # a journal-only row (heal, ZeRO, ddp's buckets)
        assert fake_annotations == [] and spec.journal is not None
        return
    enter, leave = fake_annotations
    assert enter[:2] == ("enter", spec.annotation) and leave[0] == "exit"
    # The ids ride on the annotation; the free-form argument does not.
    want = {"quorum_id": 3, "fragment": 1}
    want["step_num" if spec.root else "step"] = 7
    if spec.root:  # a root names its OS thread, for the runtime's own lines (PR 59)
        want["tid"] = threading.get_native_id()
    assert enter[2] == want


def test_phase_with_the_journal_off_feeds_the_other_two(fake_annotations) -> None:
    journal = tracing.TraceJournal(maxlen=16)
    journal.set_enabled(False)
    labels = {"replica_id": "phase_off"}
    with tracing.phase("quorum", journal, labels, step=1):
        pass
    assert journal.snapshot() == []
    assert metrics.histogram_stats("tpuft_quorum_seconds", **labels)["count"] == 1
    assert [e[0] for e in fake_annotations] == ["enter", "exit"]
    journal.set_enabled(True)
    with tracing.phase("quorum", journal, labels, step=2):
        pass
    assert [e["step"] for e in journal.snapshot()] == [2]


def test_phase_closes_every_sink_when_the_body_raises(fake_annotations) -> None:
    journal = tracing.TraceJournal(maxlen=16)
    labels = {"replica_id": "phase_raises"}
    with pytest.raises(KeyError):
        with tracing.phase("pg_configure", journal, labels, step=4):
            raise KeyError("boom")
    assert [e["name"] for e in journal.snapshot()] == ["pg_configure"]
    assert metrics.histogram_stats("tpuft_pg_configure_seconds", **labels)["count"] == 1
    assert [e[0] for e in fake_annotations] == ["enter", "exit"]


def test_children_of_a_root_take_its_step(fake_annotations) -> None:
    """The commit advances the journal's step on another thread while the
    step still runs: what opens under the root on this thread keeps the
    root's step, in the journal and on the annotation."""
    journal = tracing.TraceJournal(maxlen=16)
    with tracing.phase("optim_step", journal, step=11):
        journal.set_step(12)  # the commit, elsewhere
        with tracing.phase("device_sync", journal):
            pass
        with tracing.phase("adopt", journal, step=5):  # its own wins
            pass
    with tracing.phase("device_sync", journal):  # no root open: the journal's
        pass
    got = [(e["name"], e["step"]) for e in journal.snapshot()]
    assert got == [("device_sync", 11), ("adopt", 5), ("step", 11), ("device_sync", 12)]
    steps = [e[2].get("step") for e in fake_annotations if e[0] == "enter"]
    assert steps == [None, 11, 5, None]  # the root carries step_num instead


def test_record_phase_times_work_that_began_on_another_thread() -> None:
    journal = tracing.TraceJournal(maxlen=16, mono=_stepping_clock(0.5))
    labels = {"replica_id": "ring_test"}
    start = journal._mono()
    dur = tracing.record_phase("wire_ring", start, journal, labels, step=9)
    assert dur == pytest.approx(0.5)
    (event,) = journal.snapshot()
    assert (event["name"], event["step"], event["t_mono"]) == ("wire_ring", 9, start)
    stats = metrics.histogram_stats("tpuft_wire_stage_seconds", stage="ring", **labels)
    assert stats["count"] == 1 and stats["sum"] == pytest.approx(0.5)


def test_trace_span_rides_the_same_primitive(fake_annotations, tmp_path) -> None:
    from torchft_tpu.utils.profiling import chrome_trace, trace_span

    path = tmp_path / "t.json"
    with chrome_trace(str(path)):
        with trace_span("tpuft::test::heal", step=3, quorum_id=2, donor="a"):
            pass
        with tracing.phase("quorum", tracing.TraceJournal(maxlen=4), step=3):
            pass
    assert fake_annotations[0] == (
        "enter", "tpuft::test::heal", {"step": 3, "quorum_id": 2}
    )
    spans = [e for e in json.loads(path.read_text())["traceEvents"] if e["ph"] == "X"]
    assert [s["name"] for s in spans] == [
        "tpuft::test::heal", "tpuft::manager::_client::_quorum",
    ]
    assert spans[0]["args"]["donor"] == "a" and spans[1]["args"]["step"] == 3


# ---------------------------------------------------------------------------
# the capture control
# ---------------------------------------------------------------------------


def test_capture_twice_returns_the_journals_own_events_and_growth(tmp_path) -> None:
    journal = tracing.TraceJournal(maxlen=256)
    labels = {"replica_id": "capture_test"}
    journal.record("before_any_capture")
    with tracing.phase("quorum", journal, labels, step=0):
        pass
    metrics.inc("tpuft_commits_total", **labels)
    for round_ in range(2):  # any number of captures in one process
        tracing.start_capture(str(tmp_path / f"c{round_}"), journal=journal)
        with pytest.raises(RuntimeError, match="already running"):
            tracing.start_capture(str(tmp_path / "nested"), journal=journal)
        with tracing.phase("quorum", journal, labels, step=round_ + 1):
            pass
        with tracing.phase("sync_wait", journal, fragment=2):
            pass
        metrics.inc("tpuft_commits_total", 3, **labels)
        seq_before_stop = journal._last_seq
        got = tracing.stop_capture()
        assert got["trace_dir"] == str(tmp_path / f"c{round_}")
        assert list((tmp_path / f"c{round_}").glob("plugins/profile/*/*.xplane.pb"))
        # Exactly the journal's events of the capture: no second store.
        mine = [e for e in journal.snapshot() if e["seq"] <= seq_before_stop][-2:]
        assert got["events"] == mine
        assert [e["name"] for e in got["events"]] == ["quorum", "sync_wait"]
        assert got["events"][0]["step"] == round_ + 1 and got["dropped"] == 0
        # Growth only: one more quorum sample and three more commits, not
        # the totals; what did not move is left out.
        (quorum,) = [
            c for c in got["counters"]["tpuft_quorum_seconds"] if c["labels"] == labels
        ]
        assert quorum["count"] == 1 and quorum["sum"] > 0
        (commits,) = [
            c for c in got["counters"]["tpuft_commits_total"] if c["labels"] == labels
        ]
        assert commits["value"] == 3
        (wait,) = got["counters"]["tpuft_outer_sync_seconds"]
        assert wait["labels"] == {"stage": "wait"} and wait["count"] == 1
        assert "tpuft_pg_configure_seconds" not in got["counters"]
        clock = got["clock"]
        assert clock["begin_mono_ns"] < clock["end_mono_ns"] <= time.monotonic_ns()
        json.dumps(got)  # plain data
    with pytest.raises(RuntimeError, match="no capture"):
        tracing.stop_capture()


def test_capture_reports_what_the_ring_dropped(tmp_path) -> None:
    journal = tracing.TraceJournal(maxlen=64)
    tracing.start_capture(str(tmp_path / "c"), journal=journal)
    for i in range(100):
        journal.record("tick", i=i)
    got = tracing.stop_capture()
    assert len(got["events"]) == 64 and got["dropped"] == 36


def test_xplane_holds_the_anchors_and_bare_phase_names(tmp_path) -> None:
    """The trap of ISSUE 25: an annotation with keyword arguments must come
    back under its bare name (the ids as the event's stats), or the
    benchmark's idle-gap attribution would split by step."""
    from jax.profiler import ProfileData

    journal = tracing.TraceJournal(maxlen=64)
    tracing.start_capture(str(tmp_path), journal=journal)
    with tracing.phase("optim_step", journal, step=41):
        with tracing.phase("should_commit", journal, step=41, quorum_id=6):
            time.sleep(0.002)
    with tracing.phase("sync_wait", journal, step=8, fragment=3):
        pass
    got = tracing.stop_capture()
    (path,) = (tmp_path / "plugins" / "profile").glob("*/*.xplane.pb")
    found = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("tpuft::"):
                    found[event.name] = (dict(event.stats), event.start_ns, event.duration_ns)
    assert set(found) == {
        "tpuft::capture_begin", "tpuft::capture_end", "tpuft::optim::step",
        "tpuft::manager::should_commit", "tpuft::local_sgd::wait",
    }
    assert found["tpuft::manager::should_commit"][0] == {"step": 41, "quorum_id": 6}
    assert found["tpuft::optim::step"][0]["step_num"] == 41
    assert found["tpuft::local_sgd::wait"][0] == {"step": 8, "fragment": 3}
    # The two anchors carry the monotonic clock, so a journal instant lands
    # on the profiler's clock: the commit span's journal start, mapped
    # through the begin anchor, is the annotation's start to a millisecond.
    begin_stats, begin_ns, _ = found["tpuft::capture_begin"]
    end_stats, end_ns, _ = found["tpuft::capture_end"]
    assert begin_stats["mono_ns"] == got["clock"]["begin_mono_ns"]
    assert end_stats["mono_ns"] == got["clock"]["end_mono_ns"]
    assert (end_ns - begin_ns) == pytest.approx(
        end_stats["mono_ns"] - begin_stats["mono_ns"], abs=2e6
    )
    barrier = next(e for e in got["events"] if e["name"] == "commit_barrier")
    mapped = begin_ns + (barrier["t_mono"] * 1e9 - begin_stats["mono_ns"])
    assert mapped == pytest.approx(found["tpuft::manager::should_commit"][1], abs=2e6)
    assert found["tpuft::manager::should_commit"][2] >= 2e6


# -- what the runtime did under a span (PR 59) --------------------------------


def _many_buffers(n=60):
    """A jitted call of ``n`` input and ``n`` output arrays, compiled."""
    import jax
    import jax.numpy as jnp

    xs = [jnp.full((8,), float(i)) for i in range(n)]
    f = jax.jit(lambda *a: [x + 1 for x in a])
    jax.block_until_ready(f(*xs))
    return jax, f, xs


def test_capture_returns_what_the_runtime_did_under_update_dispatch(tmp_path) -> None:
    jax, f, xs = _many_buffers()
    journal = tracing.TraceJournal(maxlen=64)
    tracing.start_capture(str(tmp_path), journal=journal)
    for step in range(3):
        with tracing.phase("optim_step", journal, step=step):
            with tracing.phase("update_dispatch", journal):
                out = f(*xs)
            jax.block_until_ready(out)
    got = tracing.stop_capture()
    json.dumps(got)  # plain data, the new key too
    span = got["runtime"]["tpuft::optim::update_dispatch"]
    assert span["count"] == 3 and span["seconds"] > 0
    under = span["under"]
    assert len([n for n in under if n != "other"]) <= 12
    executes = {n: slot for n, slot in under.items() if "Execute" in n and n != "other"}
    assert executes, sorted(under)
    outermost = max(executes.values(), key=lambda slot: slot["seconds"])
    assert 0 < outermost["seconds"] <= span["seconds"]
    assert outermost["count"] == 3
    for name, slot in under.items():
        assert 0 <= slot["self_seconds"] <= slot["seconds"] + 1e-12, name
        assert slot["seconds"] <= span["seconds"] + 1e-12, name
        assert slot["count"] >= 1
        if name != "other":
            assert 0 <= slot["first_at_s"] <= span["seconds"], name
            assert "(" not in name
    # 120 buffers a call: more names than the table keeps, the rest summed.
    assert set(under["other"]) == {"count", "seconds", "self_seconds"}
    # The self times tile the span's runtime work: they sum to the outermost
    # runtime events' inclusive seconds, which the span holds.
    assert sum(slot["self_seconds"] for slot in under.values()) <= span["seconds"] + 1e-9
    # The root held no runtime event of its child's.
    root = got["runtime"]["tpuft::optim::step"]
    assert root["count"] == 3 and not any("Execute" in n for n in root["under"])
    assert not [e for e in got["events"] if e["name"] == "capture_runtime_unread"]


def test_a_runtime_event_belongs_to_the_innermost_span(tmp_path) -> None:
    jax, f, xs = _many_buffers(4)
    journal = tracing.TraceJournal(maxlen=64)
    tracing.start_capture(str(tmp_path), journal=journal)
    for _ in range(2):
        with tracing.phase("adopt", journal):
            with tracing.phase("state_swap", journal):
                jax.block_until_ready(f(*xs))
    jax.block_until_ready(f(*xs))  # under no span of the program: in no table
    got = tracing.stop_capture()
    parent = got["runtime"]["tpuft::optim::adopt"]
    child = got["runtime"]["tpuft::optim::state_swap"]
    assert parent["count"] == child["count"] == 2
    assert any("Execute" in n for n in child["under"])
    assert not any("Execute" in n or n.startswith("PjitFunction") for n in parent["under"])
    assert child["under"]["PjitFunction"]["count"] >= 2
    assert set(got["runtime"]) == {
        "tpuft::capture_begin", "tpuft::capture_end",
        "tpuft::optim::adopt", "tpuft::optim::state_swap",
    }


def test_two_captures_in_a_row_do_not_mix_their_runtime_tables(tmp_path) -> None:
    jax, f, xs = _many_buffers(4)
    journal = tracing.TraceJournal(maxlen=64)
    tables = []
    for phase_name, calls in (("update_dispatch", 2), ("inner_dispatch", 3)):
        tracing.start_capture(str(tmp_path), journal=journal)  # the same directory
        for _ in range(calls):
            with tracing.phase(phase_name, journal):
                jax.block_until_ready(f(*xs))
        tables.append(tracing.stop_capture()["runtime"])
    first, second = tables
    assert first["tpuft::optim::update_dispatch"]["count"] == 2
    assert "tpuft::local_sgd::inner_dispatch" not in first
    assert second["tpuft::local_sgd::inner_dispatch"]["count"] == 3
    assert "tpuft::optim::update_dispatch" not in second


@pytest.mark.parametrize("fault", ["missing", "garbage", "no_reader", "stale_only"])
def test_an_unreadable_trace_gives_an_empty_runtime_and_raises_nothing(
    tmp_path, monkeypatch, fault
) -> None:
    import jax.profiler

    journal = tracing.TraceJournal(maxlen=64)
    if fault == "stale_only":
        # An earlier capture's file is there; this capture writes none.
        tracing.start_capture(str(tmp_path), journal=journal)
        with tracing.phase("update_dispatch", journal):
            pass
        assert tracing.stop_capture()["runtime"]
    real_stop = jax.profiler.stop_trace

    def stop_then_spoil():
        real_stop()
        for path in tmp_path.glob("plugins/profile/*/*.xplane.pb"):
            if fault == "missing":
                path.unlink()
            elif fault == "garbage":
                path.write_bytes(b"\xff not an xplane \x00" * 50)

    tracing.start_capture(str(tmp_path), journal=journal)
    if fault == "stale_only":
        monkeypatch.setattr(tracing, "_xplane_files", lambda log_dir: dict(
            tracing._capture["files"]
        ))
    elif fault == "no_reader":
        monkeypatch.delattr(jax.profiler, "ProfileData")
    else:
        monkeypatch.setattr(jax.profiler, "stop_trace", stop_then_spoil)
    with tracing.phase("update_dispatch", journal):
        pass
    got = tracing.stop_capture()
    assert got["runtime"] == {}
    assert [e["name"] for e in got["events"]] == ["update_dispatch"]
    (instant,) = [e for e in journal.snapshot() if e["name"] == "capture_runtime_unread"]
    assert instant["ph"] == "i" and instant["args"]["error"]
    # The control is whole: the next capture runs and reads.
    monkeypatch.undo()
    tracing.start_capture(str(tmp_path / "next"), journal=journal)
    with tracing.phase("update_dispatch", journal):
        pass
    assert tracing.stop_capture()["runtime"]["tpuft::optim::update_dispatch"]["count"] == 1


class _FakeEvent:
    def __init__(self, name, start_ns, duration_ns, **stats):
        self.name, self.start_ns, self.duration_ns = name, start_ns, duration_ns
        self.stats = list(stats.items())


class _FakeLine:
    def __init__(self, events, name="thread"):
        self.name, self.events = name, events


class _FakePlane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _fake_profile(monkeypatch, planes):
    import jax.profiler

    class Data:
        @staticmethod
        def from_file(path):
            return type("D", (), {"planes": planes})()

    monkeypatch.setattr(jax.profiler, "ProfileData", Data)


def test_runtime_table_arithmetic_on_a_hand_made_line(monkeypatch) -> None:
    """Two dispatches on one thread line (events out of order, as a file may
    hold them), a runtime event outside any span, a span on a device plane
    and a line with no span of the program."""
    E = _FakeEvent
    main = [
        E("tpuft::optim::step", 0, 10_000),
        E("tpuft::optim::update_dispatch", 1_000, 5_000),
        E("PjitFunction(fused)", 1_100, 4_800),
        E("PjitFunction(fused)", 1_200, 4_600),  # its own name inside it
        E("ParseArguments", 1_300, 200),
        E("Runtime::Execute", 2_000, 3_000),
        E("Wait for holds", 2_100, 100),
        E("Wait for holds", 2_300, 100),
        E("chipbench/inner", 2_500, 300),  # the benchmark's: in no table
        E("Wait for holds", 2_600, 100),  # ... but what is under it still is
        E("GC", 7_000, 500),  # under the root, not the dispatch
        E("tpuft::optim::step", 20_000, 10_000),
        E("Runtime::Execute", 23_000, 1_000),
        E("tpuft::optim::update_dispatch", 22_000, 3_000),
        E("PjitFunction(fused)", 22_500, 2_000),
        E("outside", 40_000, 100),
    ]
    other_thread = [E("Runtime::Execute", 1_000, 9_000)]
    device = [E("tpuft::optim::update_dispatch", 0, 1_000_000)]
    _fake_profile(monkeypatch, [
        _FakePlane("/device:TPU:0", [_FakeLine(device)]),
        _FakePlane("/host:CPU", [_FakeLine(main), _FakeLine(other_thread)]),
    ])
    table = tracing._runtime_under_spans("ignored")
    assert set(table) == {"tpuft::optim::step", "tpuft::optim::update_dispatch"}
    root, span = table["tpuft::optim::step"], table["tpuft::optim::update_dispatch"]
    assert root == {"count": 2, "seconds": pytest.approx(20e-6), "under": {"GC": {
        "count": 1, "seconds": pytest.approx(0.5e-6), "self_seconds": pytest.approx(0.5e-6),
        "first_at_s": pytest.approx(7e-6),
    }}}
    assert span["count"] == 2 and span["seconds"] == pytest.approx(8e-6)
    under = span["under"]
    assert list(under) == ["PjitFunction", "Runtime::Execute", "Wait for holds", "ParseArguments"]
    # Inclusive seconds count the outer of two nested events of one name.
    assert under["PjitFunction"]["count"] == 3
    assert under["PjitFunction"]["seconds"] == pytest.approx((4_800 + 2_000) * 1e-9)
    # Self: outer 4800 - 4600, inner 4600 - 200 - 3000, second 2000 - 1000.
    assert under["PjitFunction"]["self_seconds"] == pytest.approx((200 + 1_400 + 1_000) * 1e-9)
    assert under["PjitFunction"]["first_at_s"] == pytest.approx((100 + 500) / 2 * 1e-9)
    execute = under["Runtime::Execute"]
    assert execute["count"] == 2 and execute["seconds"] == pytest.approx(4_000e-9)
    # Less the two waits directly in it and the benchmark's span (with its wait).
    assert execute["self_seconds"] == pytest.approx((3_000 - 200 - 300 + 1_000) * 1e-9)
    assert execute["first_at_s"] == pytest.approx((1_000 + 1_000) / 2 * 1e-9)
    assert under["Wait for holds"] == {
        "count": 3, "seconds": pytest.approx(300e-9), "self_seconds": pytest.approx(300e-9),
        "first_at_s": pytest.approx(1_100e-9),
    }
    # Self times tile the outermost runtime events (less the benchmark's span's own).
    assert sum(s["self_seconds"] for s in under.values()) == pytest.approx(
        (4_800 - 200 + 2_000) * 1e-9
    )


def test_runtime_table_lays_the_runtimes_own_line_into_its_thread(monkeypatch) -> None:
    """The TPU's PJRT plugin records its events on a line of its own, named
    ``<thread name>/<OS thread id>``; the root's ``tid`` says whose it is. A
    line of another thread's id, and one with no id, stay out."""
    E = _FakeEvent
    python = [
        E("tpuft::optim::step", 0, 30_000, step_num=3, tid=557),
        E("tpuft::optim::update_dispatch", 1_000, 24_000, step=3),
        E("PjitFunction(fused)", 1_010, 23_900),
        E("PJRT_LoadedExecutable_Execute linkage", 1_500, 1),
    ]
    plugin = [
        E("PJRT_LoadedExecutable_Execute", 1_505, 22_900),
        E("CommonPjRtLoadedExecutable::Execute", 1_510, 22_800),
        E("AllocateRawBuffer", 1_600, 60), E("AllocateRawBuffer", 1_700, 60),
        E("MemoryDeallocation", 26_000, 40),  # under the root, after the dispatch
    ]
    worker = [E("EnqueueProgram", 2_000, 500)]
    quorum = [E("tpuft::manager::should_commit", 2_000, 300, step=3), E("rpc", 2_100, 100)]
    _fake_profile(monkeypatch, [_FakePlane("/host:CPU", [
        _FakeLine(python, "python"), _FakeLine(plugin, "main/557"),
        _FakeLine(worker, "tfrt-non-blocking-queue/616"), _FakeLine(quorum, "python"),
    ])])
    table = tracing._runtime_under_spans("ignored")
    under = table["tpuft::optim::update_dispatch"]["under"]
    assert list(under) == [
        "PjitFunction", "PJRT_LoadedExecutable_Execute", "CommonPjRtLoadedExecutable::Execute",
        "AllocateRawBuffer", "PJRT_LoadedExecutable_Execute linkage",
    ]
    assert under["PJRT_LoadedExecutable_Execute"]["first_at_s"] == pytest.approx(505e-9)
    assert under["PjitFunction"]["self_seconds"] == pytest.approx((23_900 - 1 - 22_900) * 1e-9)
    assert under["CommonPjRtLoadedExecutable::Execute"]["self_seconds"] == pytest.approx(
        (22_800 - 120) * 1e-9
    )
    assert under["AllocateRawBuffer"]["count"] == 2
    assert list(table["tpuft::optim::step"]["under"]) == ["MemoryDeallocation"]
    assert list(table["tpuft::manager::should_commit"]["under"]) == ["rpc"]
    assert "EnqueueProgram" not in str(table)


def test_a_root_and_the_captures_marks_name_their_os_thread(tmp_path) -> None:
    from jax.profiler import ProfileData

    journal = tracing.TraceJournal(maxlen=16)
    tracing.start_capture(str(tmp_path), journal=journal)
    with tracing.phase("optim_step", journal, step=5):
        with tracing.phase("update_dispatch", journal):
            pass
    tracing.stop_capture()
    (path,) = (tmp_path / "plugins" / "profile").glob("*/*.xplane.pb")
    stats = {
        event.name: dict(event.stats)
        for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines for event in line.events if event.name.startswith("tpuft::")
    }
    tid = threading.get_native_id()
    assert stats["tpuft::optim::step"]["tid"] == tid
    assert stats["tpuft::capture_begin"]["tid"] == stats["tpuft::capture_end"]["tid"] == tid
    assert "tid" not in stats["tpuft::optim::update_dispatch"]


def test_runtime_table_keeps_twelve_names_and_sums_the_rest(monkeypatch) -> None:
    E = _FakeEvent
    events = [E("tpuft::optim::update_dispatch", 0, 100_000)]
    for i in range(15):  # op 0 the longest
        events.append(E(f"op.{i}", 1_000 + 5_000 * i, 1_500 - 100 * i))
    _fake_profile(monkeypatch, [_FakePlane("/host:CPU", [_FakeLine(events)])])
    under = tracing._runtime_under_spans("ignored")["tpuft::optim::update_dispatch"]["under"]
    assert list(under) == [f"op.{i}" for i in range(12)] + ["other"]
    rest = sum(1_500 - 100 * i for i in (12, 13, 14)) * 1e-9
    assert under["other"] == {
        "count": 3, "seconds": pytest.approx(rest), "self_seconds": pytest.approx(rest),
    }


def test_two_captures_in_one_process_through_the_harness(tmp_path, monkeypatch) -> None:
    """The benchmark's ``Tracer`` is a caller of the capture control: a
    capture thrown away and then one kept, as ``--trace 2`` makes them, each
    with its own events and growth and nothing of the other's."""
    from chipbench import harness

    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    journal = tracing.current()
    labels = {"replica_id": "harness_capture"}
    with tracing.phase("device_sync", journal, labels, step=1):
        pass  # before any capture: in neither
    kept = []
    for step in (2, 3):
        with harness.Tracer(True) as tracer:
            with tracing.phase("device_sync", journal, labels, step=step):
                pass
        kept.append(tracer.capture)
        assert tracer.reduce() is None and not os.path.exists(tracer.dir)
    for step, capture in zip((2, 3), kept):
        assert "trace_dir" not in capture and capture["dropped"] == 0
        assert [(e["name"], e["step"]) for e in capture["events"]] == [("device_sync", step)]
        (sync,) = [
            c for c in capture["counters"]["tpuft_device_sync_seconds"] if c["labels"] == labels
        ]
        assert sync["count"] == 1
        json.dumps(capture)
    assert kept[0]["clock"]["end_mono_ns"] <= kept[1]["clock"]["begin_mono_ns"]
    with harness.Tracer(False) as off:  # --trace 0: the control is never called
        pass
    assert off.capture is None and off.reduce() is None


def test_wait_quorum_is_a_journal_event_inside_its_root_on_the_roots_thread() -> None:
    """Every child of the step's root is in the journal, so the root's self
    time (its duration less what its children on its thread cover) can be
    computed from the events alone. Only a wait that waits is a span: the
    accessors' looks at a quorum that is there record nothing."""
    journal = tracing.TraceJournal(maxlen=256)
    with tracing.use_journal(journal):
        manager, client, _, _ = make_manager(
            pg=ProcessGroupDummy(), min_replica_size=1, use_async_quorum=True
        )
        quorum = make_quorum(
            quorum_id=4, replica_rank=0, replica_world_size=1, max_rank=0, max_world_size=1
        )

        def slow_quorum(**_):
            time.sleep(0.02)
            return quorum

        client._quorum.side_effect = slow_quorum
        with tracing.phase("optim_step", journal, step=0):
            manager.start_quorum()
            manager.wait_quorum()
            manager.wait_quorum()  # resolved: no second span
            assert manager.num_participants() == 1  # an accessor: none either
    events = {e["name"]: e for e in journal.snapshot() if e["ph"] == "X"}
    assert [e["name"] for e in journal.snapshot()].count("wait_quorum") == 1
    root, wait, rpc = events["step"], events["wait_quorum"], events["quorum"]
    assert wait["thread"] == root["thread"] == threading.current_thread().name
    assert rpc["thread"] != root["thread"]
    assert root["t_mono"] <= wait["t_mono"]
    assert wait["t_mono"] + wait["dur"] <= root["t_mono"] + root["dur"]
    assert wait["step"] == 0 and wait["dur"] >= 0.01
    # The root's children on its thread cover all of it but its self time.
    children = [e for e in (events["start_quorum"], wait) if e["thread"] == root["thread"]]
    self_time = root["dur"] - sum(e["dur"] for e in children)
    assert 0 <= self_time < 0.01
    # What reads the journal by name reads what it read: the wait is in no
    # bucket of the goodput fold and in no phase of the rollup.
    from torchft_tpu import goodput

    assert "wait_quorum" not in dict(goodput.SPAN_BUCKETS)
    assert "wait_quorum" not in journal.phase_rollup()[-1]["phases"]
    assert "quorum" in journal.phase_rollup()[-1]["phases"]


# The rollup's list as it stood beside PHASES until PR 43 folded it in.
_PHASE_SPANS_BEFORE_THE_FOLD = (
    "quorum", "pg_configure", "wire_bucket", "device_sync", "update_dispatch",
    "commit_barrier", "heal_send", "heal_recv", "zero_rebalance", "pipeline_drain",
)


def test_phase_rollup_of_a_recorded_ring_is_what_it_was_before_the_fold() -> None:
    assert not hasattr(tracing, "PHASE_SPANS")
    assert tracing.ROLLUP_SPANS == frozenset(_PHASE_SPANS_BEFORE_THE_FOLD)
    assert tracing.ROLLUP_SPANS == {
        spec.journal for spec in tracing.PHASES.values() if spec.rollup
    }
    journal = tracing.TraceJournal(maxlen=512)
    names = sorted(
        {spec.journal for spec in tracing.PHASES.values() if spec.journal}
        | set(_PHASE_SPANS_BEFORE_THE_FOLD) | {"vote_send", "quorum_ready"}
    )
    for step in (3, 4, 5):
        for n, name in enumerate(names):
            for repeat in range(2):
                journal.record(name, ph="X", dur=0.001 * (n + 1), step=step, quorum_id=9)
        journal.record("commit" if step != 4 else "commit_failed", step=step)
    ring = journal._copy_ring()
    want = []
    for step in (3, 4, 5):
        phases = {}
        for event in ring:
            if event["step"] == step and event["ph"] == "X" and event["name"] in _PHASE_SPANS_BEFORE_THE_FOLD:
                phases[event["name"]] = round(phases.get(event["name"], 0.0) + event["dur"], 6)
        want.append({"step": step, "quorum_id": 9, "phases": phases, "committed": step != 4})
    assert journal.phase_rollup() == want
    assert set(want[0]["phases"]) == set(_PHASE_SPANS_BEFORE_THE_FOLD)


def test_compile_listener_journals_a_compile_at_the_current_step() -> None:
    import jax
    import jax.numpy as jnp

    tracing.install_compile_listener()
    tracing.install_compile_listener()  # once a process
    journal = tracing.TraceJournal(maxlen=64)
    journal.set_step(step=17)
    x = jnp.arange(23, dtype=jnp.float32)
    assert journal.snapshot() == []  # not this thread's journal yet
    with tracing.use_journal(journal):
        jax.jit(lambda x: x * 3 + len("a shape and a body no other test compiles"))(
            x
        ).block_until_ready()
    compiles = [e for e in journal.snapshot() if e["name"] == "compile"]
    assert compiles and all(e["step"] == 17 for e in compiles)
    assert all(e["args"]["seconds"] > 0 for e in compiles)
