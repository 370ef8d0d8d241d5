"""Deterministic interleaving explorer for the commit/quorum protocol.

The static rules (R1-R11) prove invariants lexically; this module checks
the ones only an *interleaving* can break, by running the REAL Manager +
pipelined Optimizer protocol under the controlled scheduler in
:mod:`torchft_tpu.utils.schedules` and enumerating thread orders at the
instrumented seams (lock acquisitions, commit-barrier entry, pipeline
push/drain, window resolution, tentative adoption, publication, pending
state apply).

Every scenario drives mocked-coordination managers — the exact harness
the manager state-machine tests use (scripted ``ManagerClient``, dummy
PG, fake store) — through a micro-protocol with at least two scheduled
threads, then asserts CLAUDE.md invariants that must hold under EVERY
schedule:

- ``commit-vs-drain``     depth-2 pipelined commits racing the
                          quorum-change window drain: the committed
                          trajectory is schedule-independent (the
                          replica-identity invariant seen from one
                          replica: resolution order never changes
                          committed state).
- ``rollback-unwind``     a scripted barrier refusal racing the drain:
                          exactly one rollback, and the final state is
                          one of the two lawful unwind outcomes (the
                          younger in-flight speculation either discarded
                          with the refusal or re-dispatched after it) —
                          never a half-unwound hybrid.
- ``adopt-vs-capture``    a joiner applying its pending (healed) state
                          dict while a donor-style capture samples under
                          the state-dict read lock: every sample is a
                          consistent (params, opt_state) pair — torn
                          reads are impossible.
- ``publish-vs-drain``    ``Manager._maybe_publish`` racing the window
                          drain: every published state lies exactly on
                          the committed trajectory at its published step
                          (publication never samples speculation — R7's
                          runtime face).

``DEMO_SCENARIOS`` hold *seeded* violations — deliberately buggy
mini-protocols (a torn two-field write, a verify-then-adopt TOCTOU) the
explorer must catch deterministically and print a replay token for; the
tests pin that, and the docs use them to demonstrate the replay
workflow.

CLI: ``python -m torchft_tpu.analysis --explore [scenario ...]`` (see
``--replay`` there for token replay). A violating schedule opens a
``schedule`` incident (:func:`torchft_tpu.tracing.open_incident`), so
the journal + flight-recorder dump correlates with the printed token.
Budgets come from the ``TPUFT_EXPLORE_*`` env knobs
(:func:`torchft_tpu.utils.schedules.explore_defaults`).
"""

from __future__ import annotations

import concurrent.futures
import threading
import zlib
from typing import Any, Callable, Dict, List, Optional, Sequence

from torchft_tpu.utils import schedules

__all__ = [
    "SCENARIOS",
    "DEMO_SCENARIOS",
    "REAL_STALL_TIMEOUT",
    "explore_scenarios",
    "replay_scenario",
    "run_explore_cli",
]

# Real-protocol scenarios re-trace tiny jitted programs per schedule; give
# the controller more slack than the toy default before it declares a
# thread stalled on a real lock.
REAL_STALL_TIMEOUT = 2.0

# Golden outcomes are computed ONCE per scenario by a serial twin run
# (same jit pipeline => bitwise-identical trajectories) — this also warms
# the XLA executable cache before the first scheduled run, so scheduled
# threads never sit in a multi-second compile mid-schedule.
_GOLDEN: Dict[str, Any] = {}


def _force_cpu() -> None:
    """Pin jax to CPU before any backend init: the explorer checks the
    commit protocol on a tiny golden model, not a device workload, and its
    CLI must never take the chip from a trainer on the same host."""
    import jax

    try:
        if jax.config.jax_platforms != "cpu":
            jax.config.update("jax_platforms", "cpu")
    except Exception:  # noqa: BLE001 — backend already initialized elsewhere
        pass


# ---------------------------------------------------------------------------
# mocked-coordination harness (the manager state-machine tests' pattern)
# ---------------------------------------------------------------------------


class _FakeStore:
    def __init__(self) -> None:
        self.data = {
            "manager_addr": b"fake:1234",
            "replica_id": b"explore_replica:uuid",
        }

    def get(self, key: str, timeout: float = 0, wait: bool = True):
        return self.data.get(key)

    def set(self, key: str, value: bytes, timeout: float = 0) -> None:
        self.data[key] = value


def _scripted_manager(depth: int, refuse_step: Optional[int] = None):
    """A real Manager over a scripted ManagerClient + dummy PG, lone
    topology (the fused single-group step: fully deterministic compute).
    ``refuse_step`` refuses the FIRST barrier vote claiming that step —
    keyed by step, not call order, so concurrent commit-pool deliveries
    cannot reorder the script."""
    from unittest import mock

    from torchft_tpu.checkpointing.transport import CheckpointTransport
    from torchft_tpu.coordination import QuorumResult
    from torchft_tpu.manager import Manager
    from torchft_tpu.parallel.process_group import ProcessGroupDummy

    _force_cpu()
    transport = mock.create_autospec(CheckpointTransport, instance=True)
    transport.metadata.return_value = "http://fake:0"
    with mock.patch("torchft_tpu.manager.ManagerClient", autospec=True):
        manager = Manager(
            pg=ProcessGroupDummy(),
            min_replica_size=1,
            store=_FakeStore(),
            store_addr="store:0",
            use_async_quorum=False,
            group_rank=1,  # no native ManagerServer
            group_world_size=2,
            checkpoint_transport=transport,
            timeout=5.0,
            quorum_timeout=5.0,
            commit_pipeline_depth=depth,
        )
    client = manager._client
    client._quorum.return_value = QuorumResult(
        quorum_id=1,
        replica_rank=0,
        replica_world_size=1,
        store_address="store:0",
        max_step=0,
        max_rank=0,
        max_world_size=1,
        heal=False,
    )
    refused: List[int] = []

    def should_commit(rank, step, vote, timeout):
        if refuse_step is not None and step == refuse_step and not refused:
            refused.append(step)
            return False
        return vote

    client.should_commit.side_effect = should_commit
    return manager


def _build_opt(manager, momentum: float = 0.0):
    import jax.numpy as jnp
    import optax

    from torchft_tpu.optim import Optimizer

    tx = optax.sgd(0.1, momentum=momentum) if momentum else optax.sgd(0.1)
    return Optimizer(manager, tx, {"w": jnp.array([1.0, 1.0], jnp.float32)})


def _loss_fn(p, b):
    import jax.numpy as jnp

    return jnp.sum((p["w"] - b) ** 2)  # grad = 2(w - b)


def _batch(i: int):
    import jax.numpy as jnp

    return jnp.full((2,), float(i), jnp.float32)


def _w(opt) -> Any:
    import numpy as np

    return np.asarray(opt.params["w"]).copy()


def _golden_train(
    key: str,
    depth: int,
    nsteps: int,
    refuse_step: Optional[int] = None,
    flush_after: Optional[int] = None,
) -> Dict[str, Any]:
    """Serial twin run: same jit pipeline, no scheduler => the bitwise
    reference outcome. ``flush_after`` forces the window resolved right
    after that loop iteration — modelling the drain thread winning the
    race before the next dispatch."""
    if key in _GOLDEN:
        return _GOLDEN[key]
    manager = _scripted_manager(depth, refuse_step)
    opt = _build_opt(manager)
    step_fn = opt.make_step_fn(_loss_fn)
    trajectory = [_w(opt)]
    for i in range(nsteps):
        step_fn(_batch(i))
        if flush_after is not None and i == flush_after:
            opt.flush_pipeline(raise_on_error=False)
        trajectory.append(_w(opt))
    opt.flush_pipeline(raise_on_error=False)
    result = {
        "params": _w(opt),
        "step": manager.current_step(),
        "rollbacks": opt.rollback_count,
        # Post-flush live state per prefix is only the committed
        # trajectory when every vote commits; refusal goldens use
        # params/step only.
        "trajectory": trajectory + [_w(opt)],
    }
    manager.shutdown()
    _GOLDEN[key] = result
    return result


# ---------------------------------------------------------------------------
# real-protocol scenarios
# ---------------------------------------------------------------------------


def _scenario_commit_vs_drain(sched: schedules.Scheduler):
    """Depth-2 pipelined commits, then the quorum-change window drain
    racing the train loop's own flush: both may resolve the same window
    records concurrently (the idempotency `_resolve_pipelined_record`
    claims), and the committed trajectory must be schedule-independent.

    The drain thread is GATED until every dispatch has happened: the
    production contract is that the quorum-change drain never overlaps
    *new* dispatches (the train thread is parked in ``wait_quorum`` while
    the hook runs — optim._drain_pipeline_for_quorum_change's docstring)
    — an ungated drain mid-dispatch skews speculative vote labels, which
    is a scenario modelling error, not a protocol bug."""
    import numpy as np

    nsteps = 3
    golden = _golden_train("commit_vs_drain", depth=2, nsteps=nsteps)
    manager = _scripted_manager(depth=2)
    opt = _build_opt(manager)
    step_fn = opt.make_step_fn(_loss_fn)
    dispatched = threading.Event()

    def train():
        for i in range(nsteps):
            step_fn(_batch(i))
        dispatched.set()
        opt.flush_pipeline(raise_on_error=False)

    def drain():
        # The quorum thread's drain hook: held behind the dispatch gate
        # (see scenario docstring), then racing the flush and a second
        # drain pass at every schedule point.
        schedules.point("drain.gate", until=dispatched.is_set)
        dispatched.wait(timeout=10.0)
        opt._drain_pipeline_for_quorum_change()
        schedules.point("drain.again")
        opt._drain_pipeline_for_quorum_change()

    sched.spawn("train", train)
    sched.spawn("drain", drain)

    def check():
        assert opt.pending_commits() == 0, "window not drained"
        assert opt.rollback_count == 0, "spurious rollback"
        assert manager.current_step() == golden["step"], (
            f"committed-step drift: {manager.current_step()} != "
            f"{golden['step']}"
        )
        assert np.array_equal(_w(opt), golden["params"]), (
            "committed trajectory depends on the schedule: "
            f"{_w(opt)} != {golden['params']}"
        )

    check.cleanup = manager.shutdown
    return check


def _scenario_rollback_unwind(sched: schedules.Scheduler):
    """A scripted barrier refusal at claimed step 1 racing the drain:
    exactly one rollback, and the final state is one of the two lawful
    unwind outcomes — the younger in-flight speculation discarded with
    the refusal (batches 0,3,4 commit) or, when the refusal resolved
    before the next dispatch, re-speculated on the rolled-back state
    (batches 0,2,3,4 commit). Anything else is a half-unwound hybrid."""
    nsteps = 5
    # Twin A: refusal resolves under window pressure (younger discarded).
    late = _golden_train(
        "rollback_late", depth=2, nsteps=nsteps, refuse_step=1
    )
    # Twin B: refusal resolved right after its dispatch (a quorum-change
    # drain lands before batch 2 is dispatched — nothing younger to
    # discard). The gated live run below always realizes twin A; twin B
    # keeps the lawful-outcome set honest about the envelope a real
    # quorum change can produce.
    early = _golden_train(
        "rollback_early", depth=2, nsteps=nsteps, refuse_step=1,
        flush_after=1,
    )
    manager = _scripted_manager(depth=2, refuse_step=1)
    opt = _build_opt(manager)
    step_fn = opt.make_step_fn(_loss_fn)
    dispatched = threading.Event()

    def train():
        for i in range(nsteps):
            step_fn(_batch(i))
        dispatched.set()
        opt.flush_pipeline(raise_on_error=False)

    def drain():
        # Gated like commit-vs-drain: the quorum-change drain never
        # overlaps new dispatches, but its resolution of the refused
        # window tail races the train loop's flush freely.
        schedules.point("drain.gate", until=dispatched.is_set)
        dispatched.wait(timeout=10.0)
        opt._drain_pipeline_for_quorum_change()
        schedules.point("drain.again")
        opt._drain_pipeline_for_quorum_change()

    sched.spawn("train", train)
    sched.spawn("drain", drain)

    def check():
        assert opt.pending_commits() == 0, "window not drained"
        assert opt.rollback_count == 1, (
            f"refusal must roll back exactly once, saw {opt.rollback_count}"
        )
        outcome = (manager.current_step(), tuple(_w(opt)))
        lawful = {
            (late["step"], tuple(late["params"])),
            (early["step"], tuple(early["params"])),
        }
        assert outcome in lawful, (
            f"unlawful unwind outcome {outcome}; lawful: {sorted(lawful)}"
        )

    check.cleanup = manager.shutdown
    return check


def _scenario_adopt_vs_capture(sched: schedules.Scheduler):
    """A joiner applying its pending (healed) state dict while a
    donor-style capture samples under the state-dict read lock: every
    sample must be a consistent (params, opt_state) pair — the write
    lock makes torn reads structurally impossible."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    manager = _scripted_manager(depth=0)
    opt = _build_opt(manager, momentum=0.9)  # momentum: paired trace state

    def _paint(tree, value):
        return jax.tree_util.tree_map(
            lambda x: jnp.full_like(x, value)
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
            else x,
            tree,
        )

    pending = {
        "optimizer": {
            "params": _paint(opt.params, 5.0),
            "opt_state": _paint(opt.opt_state, 7.0),
        }
    }
    done: concurrent.futures.Future = concurrent.futures.Future()
    done.set_result(None)
    manager._healing = True
    manager._quorum_future = done
    manager._pending_state_dict = {"user": pending}

    def _sample():
        state = opt._state_dict()
        w = float(np.asarray(state["params"]["w"])[0])
        traces = [
            leaf
            for leaf in jax.tree_util.tree_leaves(state["opt_state"])
            if hasattr(leaf, "dtype")
            and jnp.issubdtype(leaf.dtype, jnp.floating)
        ]
        m = float(np.asarray(traces[0]).ravel()[0])
        return w, m

    samples: List[Any] = []

    def joiner():
        manager._apply_pending_state_dict()

    def capture():
        for _ in range(3):
            schedules.point("capture.sample")
            with manager._state_dict_lock.r_lock(timeout=5.0):
                samples.append(_sample())

    sched.spawn("capture", capture)
    sched.spawn("joiner", joiner)

    def check():
        consistent = {(1.0, 0.0), (5.0, 7.0)}  # pre-heal / post-heal pairs
        for pair in samples:
            assert pair in consistent, (
                f"torn state capture {pair}: params and opt_state from "
                f"different heal epochs (lawful: {sorted(consistent)})"
            )
        assert _sample() == (5.0, 7.0), "pending state not adopted"
        assert manager._pending_state_dict is None

    check.cleanup = manager.shutdown
    return check


class _RecordingPublisher:
    """Minimal publisher: records every sampled state so the check can
    prove publication only ever sees committed-trajectory points."""

    def __init__(self) -> None:
        import numpy as np

        self._np = np
        self._due = False
        self.published: List[Any] = []
        self.retracted: List[int] = []

    def register_error_callback(self, cb) -> None:  # Manager.attach seam
        pass

    def note_commit(self, step: int, quorum_id: int) -> None:
        self._due = True

    def due(self) -> bool:
        return self._due

    def publish(self, step: int, quorum_id: int, state: Any) -> None:
        self._due = False
        w = self._np.asarray(state["optimizer"]["params"]["w"]).copy()
        self.published.append((step, w))

    def retract_after(self, step: int) -> None:
        self.retracted.append(step)

    def shutdown(self, wait: bool = True) -> None:
        pass


def _scenario_publish_vs_drain(sched: schedules.Scheduler):
    """``Manager._maybe_publish`` racing the window drain: every
    published state must lie exactly on the committed trajectory at its
    published step — the drain inside publication (R7's runtime face)
    means speculation can never be sampled."""
    import numpy as np

    nsteps = 5
    golden = _golden_train(
        "publish_traj", depth=2, nsteps=nsteps, flush_after=-1
    )
    # flush_after=-1 never matches an iteration: trajectory[k] is the
    # LIVE state after dispatch k, which for an all-commit run equals the
    # committed state after k steps (speculative adoption IS the serial
    # application when every vote commits). trajectory[0] is the init.
    manager = _scripted_manager(depth=2)
    opt = _build_opt(manager)
    publisher = _RecordingPublisher()
    manager.attach_publisher(publisher)
    step_fn = opt.make_step_fn(_loss_fn)
    dispatched = threading.Event()

    def train():
        for i in range(nsteps):
            step_fn(_batch(i))
        dispatched.set()
        opt.flush_pipeline(raise_on_error=False)
        # The loop-boundary publication a real trainer runs after its
        # final flush.
        manager._maybe_publish()

    def drain():
        # Dispatch-gated (see commit-vs-drain); the drain races the
        # flush AND the publication sampling the post-flush state.
        schedules.point("drain.gate", until=dispatched.is_set)
        dispatched.wait(timeout=10.0)
        opt._drain_pipeline_for_quorum_change()
        schedules.point("drain.again")
        opt._drain_pipeline_for_quorum_change()

    sched.spawn("train", train)
    sched.spawn("drain", drain)

    def check():
        assert publisher.published, "publisher never ran"
        assert not publisher.retracted, "spurious retraction"
        trajectory = golden["trajectory"]
        for step, w in publisher.published:
            assert 0 <= step < len(trajectory), f"published step {step}"
            assert np.array_equal(w, trajectory[step]), (
                f"published state at step {step} is off the committed "
                f"trajectory: {w} != {trajectory[step]} — speculation "
                "was sampled"
            )
        steps = [s for s, _ in publisher.published]
        assert steps == sorted(steps), f"publication went backwards: {steps}"

    check.cleanup = manager.shutdown
    return check


SCENARIOS: Dict[str, schedules.Scenario] = {
    "commit-vs-drain": _scenario_commit_vs_drain,
    "rollback-unwind": _scenario_rollback_unwind,
    "adopt-vs-capture": _scenario_adopt_vs_capture,
    "publish-vs-drain": _scenario_publish_vs_drain,
}


# ---------------------------------------------------------------------------
# seeded-violation demos (buggy by construction; the explorer must catch
# each one and print a replay token — pinned by tests, used by the docs)
# ---------------------------------------------------------------------------


def _demo_torn_read(sched: schedules.Scheduler):
    """A two-field version swap with no lock: a reader landing between
    the writes observes a torn pair."""
    box = {"a": 0, "b": 0}
    seen: List[Any] = []

    def writer():
        for i in (1, 2):
            schedules.point("demo.write_a")
            box["a"] = i
            schedules.point("demo.write_b")
            box["b"] = i

    def reader():
        schedules.point("demo.read")
        seen.append((box["a"], box["b"]))

    sched.spawn("reader", reader)
    sched.spawn("writer", writer)

    def check():
        for a, b in seen:
            assert a == b, f"torn read: a={a} b={b}"

    return check


def _demo_unverified_adopt(sched: schedules.Scheduler):
    """A verify-then-adopt TOCTOU: the reader CRC-checks the payload it
    fetched, then adopts a RE-READ of the store — a donor swapping the
    payload between the check and the adopt slips unverified bytes in
    (the dynamic twin of analyzer rule R9)."""
    good = b"committed-state"
    store = {"payload": good, "crc": zlib.crc32(good)}
    adopted: List[bytes] = []

    def donor():
        schedules.point("demo.donor_swap")
        store["payload"] = b"corrupt-state"

    def reader():
        data = store["payload"]
        schedules.point("demo.verify")
        if zlib.crc32(data) == store["crc"]:
            schedules.point("demo.adopt")
            adopted.append(store["payload"])  # BUG: re-read, not `data`

    sched.spawn("donor", donor)
    sched.spawn("reader", reader)

    def check():
        for blob in adopted:
            assert zlib.crc32(blob) == store["crc"], (
                f"adopted unverified bytes: {blob!r}"
            )

    return check


DEMO_SCENARIOS: Dict[str, schedules.Scenario] = {
    "demo-torn-read": _demo_torn_read,
    "demo-unverified-adopt": _demo_unverified_adopt,
}


# ---------------------------------------------------------------------------
# driver + CLI
# ---------------------------------------------------------------------------


def _open_schedule_incident(name: str, v: schedules.ScheduleViolation) -> str:
    from torchft_tpu import tracing

    return tracing.open_incident(
        "schedule", step=-1, quorum_id=-1,
        reason=f"{name}: {v.error} (replay: {v.token})",
    )


def explore_scenarios(
    names: Optional[Sequence[str]] = None,
    budget: Optional[int] = None,
    preemption_bounds: Optional[Sequence[int]] = None,
    random_runs: Optional[int] = None,
    seed: Optional[int] = None,
    emit: Optional[Callable[[str], None]] = None,
    incidents: bool = True,
    include_demos: bool = False,
) -> List[schedules.ExploreResult]:
    """Explores the named scenarios (default: every real-protocol one)
    under the ``TPUFT_EXPLORE_*`` budgets. Violations open a ``schedule``
    tracing incident so the journal dump correlates with the replay
    token."""
    registry = dict(SCENARIOS)
    if include_demos:
        registry.update(DEMO_SCENARIOS)
    if names:
        unknown = [n for n in names if n not in registry]
        if unknown:
            raise KeyError(
                f"unknown scenario(s): {', '.join(unknown)}; known: "
                + ", ".join(sorted(registry))
            )
        selected = {n: registry[n] for n in names}
    else:
        selected = dict(SCENARIOS)
    say = emit or (lambda line: None)
    results = []
    for name, scenario in selected.items():
        result = schedules.explore(
            scenario,
            name=name,
            budget=budget,
            preemption_bounds=preemption_bounds,
            random_runs=random_runs,
            seed=seed,
            stall_timeout=REAL_STALL_TIMEOUT,
        )
        if result.violation is not None:
            say(f"{name}: VIOLATION after {result.schedules_run} schedule(s)")
            say("  " + result.violation.format().replace("\n", "\n  "))
            if incidents:
                iid = _open_schedule_incident(name, result.violation)
                say(f"  incident: {iid}")
        else:
            say(
                f"{name}: ok ({result.schedules_run} schedule(s), "
                f"{result.tokens_seen} unique prefixes)"
            )
        results.append(result)
    return results


def replay_scenario(
    name: str, token: str
) -> Optional[schedules.ScheduleViolation]:
    """Replays ``token`` against ``name`` (real or demo scenario);
    returns the reproduced violation or None when the schedule passes."""
    registry = {**SCENARIOS, **DEMO_SCENARIOS}
    if name not in registry:
        raise KeyError(f"unknown scenario: {name}")
    return schedules.replay(
        registry[name], token, stall_timeout=REAL_STALL_TIMEOUT
    )


def run_explore_cli(
    scenario_names: Sequence[str],
    replay_token: Optional[str] = None,
    emit: Callable[[str], None] = print,
) -> int:
    """The ``python -m torchft_tpu.analysis --explore`` leg: explore (or
    replay) and return the process exit code (0 clean, 1 violation)."""
    if replay_token:
        if len(scenario_names) != 1:
            emit("--replay needs exactly one scenario name")
            return 2
        violation = replay_scenario(scenario_names[0], replay_token)
        if violation is None:
            emit(f"{scenario_names[0]}: schedule passed (no violation)")
            return 0
        emit(violation.format())
        _open_schedule_incident(scenario_names[0], violation)
        return 1
    results = explore_scenarios(
        names=scenario_names or None, emit=emit, include_demos=True
    )
    return 1 if any(not r.ok for r in results) else 0
