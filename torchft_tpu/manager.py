"""Manager: the per-rank fault-tolerance state machine.

Role-equivalent of the reference's ``torchft/manager.py:137`` — the heart of
the library. Embedded in the train loop, it:

- computes quorums (async, overlapped with the forward pass) via the native
  ManagerServer/Lighthouse plane;
- reconfigures the replica-axis process group when membership changes
  (``configure`` under a fresh store prefix keyed by quorum_id);
- runs fault-tolerant gradient allreduces: zeros contributions from
  non-participating replicas, converts AVG to SUM + divide by the live
  participant count so numerics stay N-independent, and swallows collective
  errors into a sticky per-step error state;
- live-heals joining replicas by streaming the state pytree from a healthy
  donor via a :class:`CheckpointTransport`;
- arbitrates per-step commits via the all-local-rank AND barrier
  (``should_commit``), incrementing the step only on quorum-wide success.

Step protocol (see also optim.OptimizerWrapper)::

    manager.start_quorum()          # before forward
    grads = grad_fn(params, batch)  # forward/backward
    work = manager.allreduce_pytree(grads)
    grads = work.wait()
    if manager.should_commit():     # commit barrier
        params = apply_update(params, grads)

On TPU the collectives here ride host DCN between replica groups
(parallel/process_group.py); intra-slice collectives stay inside the jitted
step as XLA psums over the device mesh (parallel/mesh.py).
"""

from __future__ import annotations

import concurrent.futures
import json
import logging
import math
import os
import socket
import threading
import time
import traceback
import uuid
from enum import Enum
from typing import Any, Callable, Dict, List, Optional, TypeVar, cast

import numpy as np

from torchft_tpu import goodput as goodput_plane
from torchft_tpu import health as health_plane
from torchft_tpu import metrics, tracing
from torchft_tpu.checkpointing import (
    CheckpointTransport,
    HTTPTransport,
    heal_delta_enabled,
    heal_stripe_enabled,
    heal_stripe_max_donors,
)
from torchft_tpu.checkpointing._rwlock import RWLock
from torchft_tpu.coordination import ManagerClient, ManagerServer
from torchft_tpu.history import WeightHistory
from torchft_tpu.parallel.process_group import ProcessGroup, ReduceOp
from torchft_tpu.parallel.store import StoreClient
from torchft_tpu.telemetry import commits_logger, errors_logger, quorums_logger
from torchft_tpu.utils import lockcheck, netem, schedules
from torchft_tpu.utils.profiling import trace_span
from torchft_tpu.utils.transfer import prefetch_to_host
from torchft_tpu.work import Work, _DummyWork

T = TypeVar("T")

logger = logging.getLogger(__name__)

__all__ = [
    "Manager",
    "WorldSizeMode",
    "ExceptionWithTraceback",
    "HealExhaustedError",
    "DegradedReplicaError",
]

# Re-exported for train loops/supervisors that catch the escalation
# family in one place (quorum timeout / HealExhaustedError /
# DegradedReplicaError all mean "supervisor territory").
DegradedReplicaError = health_plane.DegradedReplicaError

# Env overrides (reference: manager.py:82-89).
TIMEOUT_SEC_ENV = "TPUFT_TIMEOUT_SEC"
QUORUM_TIMEOUT_SEC_ENV = "TPUFT_QUORUM_TIMEOUT_SEC"
CONNECT_TIMEOUT_SEC_ENV = "TPUFT_CONNECT_TIMEOUT_SEC"
QUORUM_RETRIES_ENV = "TPUFT_QUORUM_RETRIES"
LIGHTHOUSE_ENV = "TPUFT_LIGHTHOUSE"
MANAGER_PORT_ENV = "TPUFT_MANAGER_PORT"
COMMIT_PIPELINE_ENV = "TPUFT_COMMIT_PIPELINE"
COMMIT_PIPELINE_DEPTH_ENV = "TPUFT_COMMIT_PIPELINE_DEPTH"
COMMIT_PIPELINE_ADAPTIVE_ENV = "TPUFT_COMMIT_PIPELINE_ADAPTIVE"
HEAL_MAX_ATTEMPTS_ENV = "TPUFT_HEAL_MAX_ATTEMPTS"

# Adaptive-mode ceiling when $TPUFT_COMMIT_PIPELINE_ADAPTIVE is unset. The
# snapshot ring holds one (params, opt_state) copy per window slot, so the
# ceiling is a memory bound, not a latency one — doctor warns past 8.
DEFAULT_ADAPTIVE_MAX_DEPTH = 4


def _env_timeout(env: str, default: float) -> float:
    value = os.environ.get(env)
    return float(value) if value is not None else default


class WorldSizeMode(Enum):
    """Numerics policy when more than ``min_replica_size`` replicas are live
    (reference: manager.py:112-127).

    DYNAMIC: world size grows to all available replicas; gradients are
        normalized by the live count.
    FIXED_WITH_SPARES: exactly ``min_replica_size`` replicas participate;
        spares contribute zero gradients and are normalized away.
    """

    DYNAMIC = 0
    FIXED_WITH_SPARES = 1


class HealExhaustedError(RuntimeError):
    """Raised out of the quorum future (``wait_quorum``/``start_quorum``)
    when ``TPUFT_HEAL_MAX_ATTEMPTS`` consecutive heal attempts all failed:
    this replica cannot catch up from any donor it is being assigned, so —
    like a quorum timeout or the ``max_retries`` commit RuntimeError — it
    escalates past the step boundary into supervisor-restart territory
    instead of looping on a heal that will never land."""


class _DonorRecentlyFailed(Exception):
    """Internal: the assigned recovery donor failed us on the immediately
    preceding attempt; fail this heal round fast (no transfer) so the next
    quorum round can rotate the assignment. One-shot per failure — a
    consecutive reassignment of the same donor is attempted for real."""


def storm_stripe_rotation(
    replica_id: str,
    joining_replica_ids: List[str],
    group_rank: int,
    quorum_id: int,
) -> int:
    """The coordinated mass-rejoin-storm stripe offset: a pure function of
    the joiner's identity inside the quorum view — its ordinal among the
    joining members (sorted replica ids, so every observer derives the
    same ordering from the same quorum), its group rank, and the quorum
    id. No negotiation, no randomness, same spirit as the ZeRO
    ``shard_assignment``: N joiners healing in the same era derive N
    distinct offsets and seed their stripe plans at different donors
    instead of all hammering donor 0's first stripe simultaneously. A
    replica not in the joining list (or a lone joiner) degrades to the
    pre-storm rotation — a function of (group rank, quorum id) alone."""
    ordinal = 0
    if replica_id in joining_replica_ids:
        ordinal = sorted(joining_replica_ids).index(replica_id)
    return ordinal + max(group_rank, 0) + max(int(quorum_id), 0)


class ExceptionWithTraceback(Exception):
    """Carries a worker-thread exception across the report_error funnel with
    its formatted stack attached, so the thread hop cannot strand the
    traceback (reference manager.py:130-134 behavior).

    Formats from the exception's own ``__traceback__`` rather than the
    ambient ``format_exc`` state, so wrapping works from any thread — not
    only inside the original ``except`` block."""

    def __init__(self, e: Exception) -> None:
        tb = "".join(traceback.format_exception(type(e), e, e.__traceback__))
        super().__init__(f"{e}\n{tb}")
        self.original_exception = e
        self.stack_trace: str = tb


class _TrackedCommitFuture:
    """Proxy around should_commit_async's executor future that records
    whether the caller ever observed its outcome, so start_quorum's drain
    can tell "caller already handled the barrier result/exception" (skip)
    from "caller never looked" (drain, propagating any stored exception).

    A RESTRICTED future proxy, not a concurrent.futures.Future subclass:
    it supports result/exception/done/running/cancelled/cancel/
    add_done_callback, but not the module-level ``concurrent.futures.wait``
    / ``as_completed`` helpers (which poke Future internals). Callers
    coordinating multiple futures should resolve this one directly."""

    def __init__(self, inner: concurrent.futures.Future) -> None:
        self._inner = inner
        self.consumed = False

    def result(self, timeout: Optional[float] = None) -> Any:
        # Only a DELIVERED outcome (value or the barrier's own exception)
        # counts as consumption: a wait that merely timed out — or was cut
        # short by KeyboardInterrupt/SystemExit — observed nothing, and
        # checking done() after the fact would race a barrier completing
        # just after the wait expires. Future re-raises the stored
        # exception OBJECT itself, so identity against the stored exception
        # tells a delivered outcome from an interrupted wait.
        try:
            value = self._inner.result(timeout)
        except BaseException as e:
            try:
                delivered = (
                    self._inner.done() and self._inner.exception(timeout=0) is e
                )
            except concurrent.futures.CancelledError:
                delivered = False
            if delivered:
                self.consumed = True
            raise
        self.consumed = True
        return value

    def exception(self, timeout: Optional[float] = None) -> Optional[BaseException]:
        # Future.exception RETURNS a stored exception and only raises
        # TimeoutError/CancelledError for the wait itself, so any return
        # means the outcome was delivered.
        exc = self._inner.exception(timeout)
        self.consumed = True
        return exc

    def done(self) -> bool:
        return self._inner.done()

    def running(self) -> bool:
        return self._inner.running()

    def cancelled(self) -> bool:
        return self._inner.cancelled()

    def cancel(self) -> bool:
        # A cancelled barrier was observed by whoever cancelled it.
        cancelled = self._inner.cancel()
        if cancelled:
            self.consumed = True
        return cancelled

    def add_done_callback(self, fn: Callable[[Any], None]) -> None:
        self._inner.add_done_callback(lambda _inner: fn(self))


class _SpeculativeCommitFuture:
    """Verdict future for one slot of the depth-N speculative window.

    The barrier RPC rides the manager's commit pool so the whole window's
    votes overlap on the wire (the single-thread quorum executor would
    serialize them — the depth-1 path keeps it for its FIFO ordering
    guarantees). The step/commit ACCOUNTING that ``should_commit`` applies
    inline is deferred to the first ``result()`` delivery: the pipelined
    optimizer resolves records oldest-first, so accounting applies in
    window order on the consuming thread. ``discard()`` consumes the
    verdict WITHOUT accounting — a rollback unwound this slot, so quorum-
    wide the step never happened (every survivor discards the same
    suffix, keeping fleet accounting in lockstep)."""

    __slots__ = (
        "_manager", "_inner", "claimed_step", "local_vote",
        "_participants", "_lock", "_settled",
    )

    def __init__(
        self,
        manager: "Manager",
        inner: concurrent.futures.Future,
        claimed_step: int,
        local_vote: bool,
        participants: int,
    ) -> None:
        self._manager = manager
        self._inner = inner
        self.claimed_step = claimed_step
        self.local_vote = local_vote
        self._participants = participants
        self._lock = threading.Lock()
        self._settled = False

    def result(self, timeout: Optional[float] = None) -> bool:
        verdict = bool(self._inner.result(timeout))
        with self._lock:
            settle = not self._settled
            self._settled = True
        if settle:
            # May raise (max_retries escalation) — after marking settled,
            # so a re-read returns the verdict instead of double-counting.
            self._manager._speculative_commit_resolved(
                self.claimed_step, verdict, self._participants
            )
        return verdict

    def done(self) -> bool:
        return self._inner.done()

    def discard(self) -> None:
        """Consumes the barrier verdict with NO step accounting (and no
        exception): the window unwound past this slot. Best-effort
        bounded wait — an unreachable barrier here is already a poisoned
        step through the normal error funnels."""
        with self._lock:
            self._settled = True
        try:
            self._inner.result(self._manager._timeout)
        except Exception:  # noqa: BLE001 — the slot is unwound either way
            pass


class Manager:
    """Fault tolerance manager for one rank of one replica group.

    Args:
        pg: the replica-axis process group (reconfigured on quorum change).
        min_replica_size: minimum replicas for a step to commit.
        store: rendezvous store client for this replica group (local-rank
            coordination + advertised to peers for PG rendezvous).
        store_addr: the group store's "host:port" advertised to other groups.
        load_state_dict/state_dict: legacy single-key state registration;
            prefer :meth:`register_state_dict_fn`.
        use_async_quorum: overlap quorum with the forward pass; the joining
            replica skips participation for one step instead of blocking all.
        replica_id: stable prefix for this group's identity; a uuid suffix is
            appended per process lifetime.
        group_rank/group_world_size: this process's coordinates inside the
            replica group (host index / hosts per group).
        commit_pipeline_depth: 0 (default) resolves every step's commit
            before the next dispatch; N >= 1 opts into the pipelined-commit
            schedule with an N-step bounded speculative window (the
            phantom-commit envelope grows with N — see
            optim.Optimizer.make_step_fn); the string ``"auto"`` picks the
            depth adaptively per quorum era from the measured control-plane
            RTT vs step time (capped by
            ``$TPUFT_COMMIT_PIPELINE_ADAPTIVE``, default
            ``DEFAULT_ADAPTIVE_MAX_DEPTH``).
            ``$TPUFT_COMMIT_PIPELINE_DEPTH`` overrides (int or ``auto``);
            the legacy ``$TPUFT_COMMIT_PIPELINE`` is honored when the new
            var is unset.
        heal_max_attempts: consecutive failed heal attempts tolerated
            before :class:`HealExhaustedError` escalates out of the quorum
            future (``$TPUFT_HEAL_MAX_ATTEMPTS`` overrides). Each failed
            attempt funnels into :meth:`report_error` (the step does not
            commit, the joiner re-enters the next quorum still joining);
            a DEAD donor leaves the pool via heartbeat expiry, so the next
            assignment naturally excludes it, and the transport's resume
            cache re-fetches only the chunks the failed attempt did not
            verify.
    """

    def __init__(
        self,
        pg: ProcessGroup,
        min_replica_size: int,
        store: StoreClient,
        store_addr: str,
        load_state_dict: Optional[Callable[[T], None]] = None,
        state_dict: Optional[Callable[[], T]] = None,
        use_async_quorum: bool = True,
        timeout: float = 60.0,
        quorum_timeout: float = 60.0,
        connect_timeout: float = 10.0,
        group_rank: Optional[int] = None,
        group_world_size: Optional[int] = None,
        world_size_mode: WorldSizeMode = WorldSizeMode.DYNAMIC,
        lighthouse_addr: Optional[str] = None,
        replica_id: Optional[str] = None,
        manager_bind: str = "[::]:0",
        hostname: str = "",
        heartbeat_interval: float = 0.1,
        checkpoint_transport: Optional[CheckpointTransport] = None,
        init_sync: bool = True,
        max_retries: Optional[int] = None,
        quorum_retries: int = 0,
        commit_pipeline_depth: Any = 0,
        heal_max_attempts: int = 5,
        health_monitor: Optional[Any] = None,
    ) -> None:
        self._pg = pg
        self._min_replica_size = min_replica_size
        self._timeout = _env_timeout(TIMEOUT_SEC_ENV, timeout)
        self._quorum_timeout = _env_timeout(QUORUM_TIMEOUT_SEC_ENV, quorum_timeout)
        self._connect_timeout = _env_timeout(CONNECT_TIMEOUT_SEC_ENV, connect_timeout)
        self._quorum_retries = int(
            os.environ.get(QUORUM_RETRIES_ENV, str(quorum_retries))
        )
        # Pipelined commit (opt-in): up to depth-N steps' device syncs +
        # commit votes may resolve while younger steps are already
        # dispatched — optim.make_step_fn reads this depth and runs its
        # pipelined schedule over an N-step bounded speculative window
        # (rollback snapshots become a ring, the phantom-commit envelope
        # grows to at most N steps; see optim.py). "auto" picks the depth
        # per quorum era from the measured control-plane RTT vs step time;
        # TPUFT_STRICT_COMMIT=1 overrides any depth back to 0.
        raw_depth: Any = os.environ.get(COMMIT_PIPELINE_DEPTH_ENV)
        if raw_depth is None:
            raw_depth = os.environ.get(COMMIT_PIPELINE_ENV)
        if raw_depth is None:
            raw_depth = commit_pipeline_depth
        self._commit_pipeline_adaptive = (
            isinstance(raw_depth, str) and raw_depth.strip().lower() == "auto"
        )
        try:
            self._adaptive_max_depth = max(
                1,
                int(
                    os.environ.get(
                        COMMIT_PIPELINE_ADAPTIVE_ENV,
                        str(DEFAULT_ADAPTIVE_MAX_DEPTH),
                    )
                ),
            )
        except ValueError:
            self._adaptive_max_depth = DEFAULT_ADAPTIVE_MAX_DEPTH
        if self._commit_pipeline_adaptive:
            self._commit_pipeline_depth = 1  # deepens as evidence arrives
        else:
            try:
                self._commit_pipeline_depth = int(raw_depth)
            except (TypeError, ValueError):
                raise ValueError(
                    "commit_pipeline_depth must be an int >= 0 (0 = off, "
                    "N = an N-step speculative window) or 'auto'; got "
                    f"{raw_depth!r}"
                ) from None
            if self._commit_pipeline_depth < 0:
                raise ValueError(
                    "commit_pipeline_depth must be an int >= 0 (0 = off, "
                    "N = an N-step speculative window) or 'auto'; got "
                    f"{self._commit_pipeline_depth}"
                )
        # Adaptive-controller observations (EWMAs over the pipelined loop's
        # reports; see observe_pipeline_step / _adapt_pipeline_depth).
        self._pipeline_interval_ewma: Optional[float] = None
        self._pipeline_stall_ewma: Optional[float] = None
        self._barrier_rtt_ewma: Optional[float] = None
        self._pipeline_last_obs: Optional[float] = None
        self._pipeline_obs_count = 0
        # Trial bookkeeping: a deepen is an experiment — (old depth, old
        # per-step interval) to judge it against; _adapt_hold freezes the
        # controller after a deepen that did not pay, until the next era.
        self._adapt_trial_from: Optional[tuple] = None
        self._adapt_hold = False
        # Speculative-vote pool (depth >= 2 / adaptive): the barrier RPCs
        # for the window's steps must overlap ON THE WIRE, which the
        # single-thread quorum executor cannot do. Lazily created.
        self._commit_pool: Optional[concurrent.futures.ThreadPoolExecutor] = None
        self._commit_pool_lock = threading.Lock()
        self._use_async_quorum = use_async_quorum
        self._replica_world_size_mode = world_size_mode
        self._init_sync = init_sync
        self._max_retries = max_retries

        self._group_rank: int = (
            group_rank if group_rank is not None else int(os.environ.get("GROUP_RANK", "0"))
        )
        self._group_world_size: int = (
            group_world_size
            if group_world_size is not None
            else int(os.environ.get("GROUP_WORLD_SIZE", "1"))
        )

        # Gray-failure health plane (torchft_tpu/health.py): explicit
        # monitor injection (drills/bench) or env-gated auto-attach
        # ($TPUFT_HEALTH=1). The quarantine gate runs NOW — before the
        # ManagerServer below starts heartbeating — so a replica whose
        # previous incarnation self-ejected must pass its accelerator
        # self-probe (with exponential backoff; crash-loop parking)
        # before it re-enters anyone's quorum view. Rejoin then rides
        # the normal heal path (delta rejoin makes the comeback cheap).
        self._health: Optional[health_plane.HealthMonitor] = health_monitor
        if self._health is None and health_plane.enabled():
            self._health = health_plane.HealthMonitor(
                replica_id=(replica_id or "replica"),
                group_rank=self._group_rank,
                min_replica_size=min_replica_size,
            )
        if self._health is not None:
            self._health.bind(min_replica_size=min_replica_size)
            self._health.serve_quarantine_if_pending()

        self._store = store
        # The default heal transport speaks the heal wire class: its
        # stages encode with $TPUFT_HEAL_CODEC (default fp32 = bit-for-bit
        # the pre-codec format) and a joiner decodes after CRC/digest
        # verification — decode failures funnel into report_error through
        # the same HealIntegrityError path as any corrupt donor.
        self._checkpoint_transport: CheckpointTransport = (
            checkpoint_transport
            if checkpoint_transport is not None
            else HTTPTransport(timeout=self._timeout, wire="heal")
        )
        # Serving-plane failures (e.g. a heal-serve sidecar crash,
        # TPUFT_HEAL_SERVE_MODE=child) funnel into report_error: the step
        # does not commit and the supervisor-visible error log carries the
        # crash — the train loop itself never observes it.
        self._checkpoint_transport.register_error_callback(self.report_error)

        # State-dict function registry under a readers-writer lock: readers
        # are checkpoint serves, the writer is the optimizer step
        # (reference: manager.py:229, :341-366).
        self._state_dict_lock = RWLock()
        self._load_state_dict_fns: Dict[str, Callable[[Any], None]] = {}
        self._user_state_dicts: Dict[str, Callable[[], Any]] = {}
        if load_state_dict is not None and state_dict is not None:
            self.register_state_dict_fn("default", load_state_dict, state_dict)

        # Step/commit accounting.
        self._step = 0
        self._batches_committed = 0
        self._commit_failures = 0

        # Versioned weight history (torchft_tpu/history.py): the ring of
        # committed state refs the optimizer promotes into at commit
        # resolution. Sized by the commit window, window + 1 at every
        # depth: the versions the rollback ring already held, so a
        # deep-window donor can serve quorum.max_step EXACTLY after a
        # drain advanced its live step past it (the PR-9 "fail cleanly
        # and retry" round becomes an immediate serve). At depth 0 that
        # is ONE version, the live committed state by reference: a
        # strict step holds committed N and speculative N + 1, and
        # nothing reads older (a strict donor's live step never passes
        # quorum.max_step, so the donor path never consults the ring).
        # The window is the one this manager was BUILT with:
        # TPUFT_STRICT_COMMIT=1 over a depth >= 1 manager keeps that
        # manager's ring (the override is read per make_step_fn, and the
        # window may be re-entered). TPUFT_HISTORY_MAX_VERSIONS /
        # TPUFT_HISTORY_BYTES override.
        window = (
            self._adaptive_max_depth
            if self._commit_pipeline_adaptive
            else self._commit_pipeline_depth
        )
        self._history = WeightHistory(
            max_versions=window + 1, journal=tracing.current()
        )

        # Per-step error/heal state.
        self._errored: Optional[ExceptionWithTraceback] = None
        self._shutdown_hooks: List[Callable[[], None]] = []
        self._quorum_change_hooks: List[Callable[[], None]] = []
        self._heal_parts_filters: List[Callable[[], Any]] = []
        # Serving plane (torchft_tpu/serving): commit-tail publish hooks
        # (cheap due-marks) + the attached publisher the step boundary
        # publishes through — see register_publish_hook/_maybe_publish.
        self._publish_hooks: List[Callable[[int, int], None]] = []
        self._publisher: Optional[Any] = None
        self._publisher_state_fn: Optional[Callable[[], Any]] = None
        self._healing = False
        self._pending_state_dict: Optional[Dict[str, Any]] = None
        self._pending_commit_future: Optional[_TrackedCommitFuture] = None

        # Heal failover accounting (spans quorum rounds; reset on a heal
        # that lands): consecutive failed attempts, the donor that failed
        # last (for the failover counter), and per-donor one-shot
        # fail-fast skips (addr -> skip_pending).
        self._heal_max_attempts = max(
            1, int(os.environ.get(HEAL_MAX_ATTEMPTS_ENV, str(heal_max_attempts)))
        )
        self._heal_attempts = 0
        self._heal_last_failed_donor: Optional[str] = None
        self._heal_failed_donors: Dict[str, bool] = {}
        # Advisory per-donor identity map for the CURRENT heal attempt
        # (donor url -> {"replica_id", "region"}); rebuilt by
        # _resolve_stripe_donors each attempt.
        self._heal_donor_info: Dict[str, Dict[str, Any]] = {}

        # Quorum state.
        self._quorum_id = -1
        self._quorum_future: Optional[concurrent.futures.Future] = None
        self._participating_replica_rank: Optional[int] = None
        self._participating_replica_world_size: int = 0
        self._executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="tpuft_quorum"
        )

        # Rank 0 embeds the native ManagerServer; other local ranks discover
        # its address through the group store (reference: manager.py:293-325).
        self._manager: Optional[ManagerServer] = None
        hostname = hostname or socket.gethostname()
        if self._group_rank == 0:
            lighthouse = lighthouse_addr or os.environ.get(LIGHTHOUSE_ENV)
            if lighthouse is None:
                raise ValueError(
                    f"rank 0 requires lighthouse_addr or ${LIGHTHOUSE_ENV}"
                )
            bind = manager_bind
            port_env = os.environ.get(MANAGER_PORT_ENV)
            if port_env is not None and bind == "[::]:0":
                bind = f"[::]:{port_env}"
            replica_id = (replica_id or "") + ":" + str(uuid.uuid4())
            self._manager = ManagerServer(
                replica_id=replica_id,
                lighthouse_addr=lighthouse,
                address=hostname,
                bind=bind,
                store_addr=store_addr,
                world_size=self._group_world_size,
                heartbeat_interval=heartbeat_interval,
                connect_timeout=self._connect_timeout,
                quorum_retries=self._quorum_retries,
            )
            self._store.set("manager_addr", self._manager.address().encode())
            self._store.set("replica_id", replica_id.encode())

        addr = self._store.get("manager_addr", timeout=self._connect_timeout)
        assert addr is not None
        replica_id_bytes = self._store.get("replica_id", timeout=self._connect_timeout)
        assert replica_id_bytes is not None
        self._replica_id = replica_id_bytes.decode()
        # WAN topology: register who this process is with the emulated-link
        # shim (a no-op without a configured topology) so wire seams can
        # resolve the local region from the replica-id map.
        netem.set_local_replica_id(self._replica_id)
        self._client = ManagerClient(addr.decode(), connect_timeout=self._connect_timeout)

        self._logger = _ManagerLogger(self, self._replica_id, self._group_rank)

        # Fleet metrics: every phase counter/histogram below is labeled with
        # the STABLE replica id (the user prefix, without the per-process
        # uuid suffix) so counters accumulate across supervised restarts of
        # the same replica group — the operator-facing identity.
        self._metric_labels = {
            "replica_id": self._replica_id.split(":", 1)[0] or "replica",
            "group_rank": str(self._group_rank),
        }
        self._metrics_push_interval = metrics.push_interval_sec()
        self._metrics_last_push = 0.0
        metrics.maybe_start_http_server()
        metrics.set_gauge(
            "tpuft_pipeline_depth",
            self._commit_pipeline_depth,
            **self._metric_labels,
        )

        # Trace plane: this manager's journal is whatever journal is
        # current on the CONSTRUCTING thread (threads-as-replicas drills
        # install one per replica thread; real processes get the process
        # default), captured here so events recorded from the quorum
        # thread still land in this replica's journal. Identity uses the
        # stable replica id — restarts of the same group continue one
        # timeline, exactly like the metric labels.
        self._trace = tracing.current()
        self._trace.configure(
            job_id=os.environ.get("JOB_ID", "unknown"),
            replica_id=self._metric_labels["replica_id"],
            group_rank=self._group_rank,
        )
        self._trace.set_step(self._step, self._quorum_id)
        tracing.install_compile_listener()
        self._trace_clock = tracing.StoreClockSampler(
            self._trace,
            owner_key=f"{self._metric_labels['replica_id']}/{self._group_rank}",
            claim=self._group_rank == 0,
        )
        # Goodput ledger: a fold over this replica's trace ring, closed on
        # the metrics-push cadence; its payload rides the metrics snapshot
        # so fleet_status/goodput_report can account fleet wall-clock
        # without journal access. SLO burn-rate alerting (TPUFT_SLO_*)
        # lives inside the ledger — alerting only, never actuation.
        self._goodput = goodput_plane.GoodputLedger(
            journal=self._trace, labels=self._metric_labels
        )

        # Health plane wiring that needs the full identity: the monitor
        # journals into this replica's timeline and funnels wedge-path
        # errors through report_error like every other comm-layer error.
        if self._health is not None:
            self._health.bind(trace=self._trace, report_error=self.report_error)
            self.register_shutdown_hook(self._health.stop)

    # ------------------------------------------------------------------
    # state dict registry
    # ------------------------------------------------------------------

    def register_state_dict_fn(
        self,
        key: str,
        load_state_dict: Callable[[T], None],
        state_dict: Callable[[], T],
    ) -> None:
        assert key not in self._load_state_dict_fns, f"duplicate state dict key {key}"
        self._load_state_dict_fns[key] = cast(Callable[[Any], None], load_state_dict)
        self._user_state_dicts[key] = state_dict

    def disallow_state_dict_read(self, timeout: Optional[float] = None) -> None:
        """Takes the state-dict write lock: blocks checkpoint serves while the
        optimizer mutates registered state (reference: allow/disallow pair
        used by LocalSGD/DiLoCo step hooks, local_sgd.py:112-128)."""
        effective = self._timeout if timeout is None else timeout
        if not self._state_dict_lock.w_acquire(effective):
            raise TimeoutError("state dict write lock not acquired")

    def allow_state_dict_read(self) -> None:
        self._state_dict_lock.w_release()

    @property
    def commit_pipeline_depth(self) -> int:
        """How many uncommitted steps the train loop may keep in flight
        (0 = resolve every commit before the next dispatch). In adaptive
        mode this is the CURRENT depth — the controller moves it between 1
        and the adaptive ceiling as the measured RTT/step ratio changes;
        the pipelined step_fn re-reads it every call."""
        return self._commit_pipeline_depth

    @property
    def commit_pipeline_adaptive(self) -> bool:
        return self._commit_pipeline_adaptive

    # ------------------------------------------------------------------
    # adaptive depth controller
    # ------------------------------------------------------------------

    _ADAPT_EVERY_OBS = 4  # re-evaluate cadence, in pipelined-step reports
    _EWMA_ALPHA = 0.3

    def _ewma(self, prev: Optional[float], value: float) -> float:
        if prev is None:
            return value
        return prev + self._EWMA_ALPHA * (value - prev)

    def observe_pipeline_step(self, stall_s: float) -> None:
        """Per-resolution report from the pipelined step loop: ``stall_s``
        is how long the train thread sat blocked on this step's verdict +
        device bound (the serialized latency the window failed to hide).
        Feeds the adaptive controller's EWMAs; every few reports the
        controller runs one trial-and-judge round:

        - measurable stall remaining -> DEEPEN one slot as a trial;
        - at the next round, keep the deepen only if the per-step wall
          actually improved (>= 5%) — stall that deepening cannot remove
          (a compute-throughput backlog looks exactly like an unhidden
          round trip from the train thread) reverts the trial and holds
          the controller until the next quorum era.

        Shrinking below a kept depth happens only at era boundaries
        (:meth:`_adapt_pipeline_depth`), so a noisy fast step cannot
        oscillate the window against a slow link."""
        now = time.monotonic()
        if self._pipeline_last_obs is not None:
            self._pipeline_interval_ewma = self._ewma(
                self._pipeline_interval_ewma, now - self._pipeline_last_obs
            )
        self._pipeline_last_obs = now
        self._pipeline_stall_ewma = self._ewma(
            self._pipeline_stall_ewma, max(stall_s, 0.0)
        )
        self._pipeline_obs_count += 1
        if not self._commit_pipeline_adaptive:
            return
        if self._pipeline_obs_count % self._ADAPT_EVERY_OBS:
            return
        interval = self._pipeline_interval_ewma or 0.0
        stall = self._pipeline_stall_ewma or 0.0
        if interval <= 0.0:
            return
        if self._adapt_trial_from is not None:
            prev_depth, prev_interval = self._adapt_trial_from
            self._adapt_trial_from = None
            if interval >= 0.95 * prev_interval:
                # The deepen did not pay: revert and hold this era.
                self._adapt_hold = True
                self._set_pipeline_depth(prev_depth)
                return
        if self._adapt_hold:
            return
        if (
            stall > 0.15 * interval
            and self._commit_pipeline_depth < self._adaptive_max_depth
        ):
            self._adapt_trial_from = (self._commit_pipeline_depth, interval)
            self._set_pipeline_depth(self._commit_pipeline_depth + 1)

    def _adapt_pipeline_depth(self) -> None:
        """Quorum-era re-evaluation (called on a quorum_id change, after
        the drain hooks emptied the window): clear any hold/trial and
        re-derive the depth from the measured control-plane RTT vs step
        time — ``ceil(barrier_rtt / step_compute)`` where step_compute is
        the inter-step interval minus the observed stall (what the loop
        spends NOT waiting on verdicts). This is where the window can
        SHRINK; a link that degrades mid-era deepens it through
        :meth:`observe_pipeline_step`'s trial rounds instead of stalling
        the fleet."""
        if not self._commit_pipeline_adaptive:
            return
        self._adapt_trial_from = None
        self._adapt_hold = False
        rtt = self._barrier_rtt_ewma
        interval = self._pipeline_interval_ewma
        if rtt is None or interval is None:
            return  # no evidence yet: keep the current depth
        compute = max(interval - (self._pipeline_stall_ewma or 0.0), 1e-4)
        ideal = int(math.ceil(rtt / compute))
        self._set_pipeline_depth(max(1, min(ideal, self._adaptive_max_depth)))

    def _set_pipeline_depth(self, depth: int) -> None:
        if depth == self._commit_pipeline_depth:
            return
        self._logger.info(
            f"adaptive commit pipeline: depth {self._commit_pipeline_depth} "
            f"-> {depth} (barrier_rtt={self._barrier_rtt_ewma}, "
            f"interval={self._pipeline_interval_ewma}, "
            f"stall={self._pipeline_stall_ewma})"
        )
        self._commit_pipeline_depth = depth
        # Re-measure under the new depth: stall/interval evidence gathered
        # at the old depth would keep re-triggering the deepen rule after
        # the window already absorbed the latency (observed as runaway
        # deepening at RTT 0). The barrier-RTT EWMA stays — the wire's
        # round trip is depth-independent.
        self._pipeline_stall_ewma = None
        self._pipeline_interval_ewma = None
        self._pipeline_last_obs = None
        metrics.set_gauge("tpuft_pipeline_depth", depth, **self._metric_labels)
        self._trace.record(
            "pipeline_depth", step=self._step, quorum_id=self._quorum_id,
            depth=depth,
        )

    @property
    def history(self) -> WeightHistory:
        """The step-labeled ring of committed state refs (history.py):
        state owners (the optimizer) promote each committed step here at
        commit RESOLUTION — never from a live speculative window — and
        the donor staging path consults it so a joiner asking for
        ``quorum.max_step`` is served that exact committed step even
        when this donor's window drained past it."""
        return self._history

    def _history_state_dict(self, step: int) -> Optional[Dict[str, Any]]:
        """The exact manager-shaped state dict for committed ``step``
        from the history ring, or None when it cannot be served exactly
        (evicted, a registered key never promoted — e.g. DiLoCo's
        fragments, which don't promote yet — or accounting missing).
        None means the caller stages its drained step instead: the
        fallback fetches more, it never mislabels. Read under the
        state-dict read lock: a ring of one version holds the live
        state, which its owner replaces (and deletes) under the writer,
        and answers with device copies made here."""
        if not self._user_state_dicts:
            return None
        with self._state_dict_lock.r_lock(timeout=self._timeout):
            return self._history.state_dict_at(step, set(self._user_state_dicts))

    def register_quorum_change_hook(self, hook: Callable[[], None]) -> None:
        """Runs ``hook`` on the quorum thread whenever the quorum id
        changes, BEFORE the process group reconfigures (and therefore
        before any donor checkpoint send for the new quorum).

        This is the pipelined-commit drain point: a membership change must
        not reconfigure the comm layer — or stage a donor send — while an
        uncommitted speculative step is still in flight, so the pipelined
        optimizer registers a full pipeline resolution here. Hook errors
        funnel into :meth:`report_error` (the step will not commit) rather
        than aborting the reconfigure."""
        self._quorum_change_hooks.append(hook)

    def _run_quorum_drain_hooks(self) -> None:
        """Runs the registered quorum-change (speculative-window drain)
        hooks on the calling (quorum) thread. Idempotent by contract —
        every registered hook resolves records in place — so it runs on a
        quorum-id change AND again before any donor send, making "no
        ``pg.configure`` / ``send_checkpoint`` inside an undrained window"
        structural (tpuft_check rule R7 pins the ordering lexically).
        Hook errors funnel into :meth:`report_error` (the step will not
        commit) rather than aborting the reconfigure or the serve."""
        schedules.point("manager.quorum_drain_hooks")
        for hook in self._quorum_change_hooks:
            try:
                hook()
            except Exception as e:  # noqa: BLE001
                self._logger.exception(f"quorum-change drain hook failed: {e}")
                self.report_error(e)

    def register_publish_hook(self, hook: Callable[[int, int], None]) -> None:
        """Runs ``hook(committed_step, quorum_id)`` after every committed
        step's accounting (both the inline ``should_commit`` tail and the
        speculative window's deferred resolution). Hooks must be CHEAP —
        they run on the commit-resolution path — and must not sample
        state: a depth-N pipeline's live state contains younger
        speculative steps at resolution time. The serving plane's
        publisher registers a due-mark here; the actual state capture
        happens at the next step boundary (:meth:`_maybe_publish`), after
        a full window drain. Hook errors are logged and dropped — the
        serving plane must never poison a commit."""
        self._publish_hooks.append(hook)

    def attach_publisher(
        self, publisher: Any, state_fn: Optional[Callable[[], Any]] = None
    ) -> None:
        """Attaches a ``serving.WeightPublisher``: commits mark it due via
        :meth:`register_publish_hook`, and the step boundary publishes
        through :meth:`_maybe_publish`. ``state_fn`` samples the state to
        publish (e.g. ``lambda: opt.params``); default is the registered
        user state dicts. The publisher's serving-sidecar failures funnel
        into :meth:`report_error` like the heal transport's."""
        self._publisher = publisher
        self._publisher_state_fn = state_fn
        publisher.register_error_callback(self.report_error)
        self.register_publish_hook(publisher.note_commit)
        self.register_shutdown_hook(lambda: publisher.shutdown(wait=False))

    def _run_publish_hooks(self, step: int, quorum_id: int) -> None:
        for hook in self._publish_hooks:
            try:
                hook(step, quorum_id)
            except Exception:  # noqa: BLE001 — serving must never wound a commit
                self._logger.exception("publish hook failed (ignored)")

    def _maybe_publish(self) -> None:
        """The publication site, run on the train thread at the step
        boundary (:meth:`start_quorum`) when the attached publisher has a
        version due. The speculative window is drained FIRST — identical
        discipline to donor sends, pinned lexically by analyzer rule R7 —
        so published bytes are always committed-only; the state sample
        rides the state-dict read lock like a checkpoint serve. Failures
        are counted and logged (serving lags; training is unaffected)."""
        publisher = self._publisher
        if publisher is None:
            return
        if publisher.due():
            schedules.point("manager.maybe_publish")
            try:
                # Publication must never sample speculative-window state:
                # resolve the full window before touching params (R7).
                self._run_quorum_drain_hooks()
                with self._state_dict_lock.r_lock(timeout=self._timeout):
                    if self._publisher_state_fn is not None:
                        state = self._publisher_state_fn()
                    else:
                        state = {
                            key: fn() for key, fn in self._user_state_dicts.items()
                        }
                with metrics.timer(
                    "tpuft_publish_seconds", **self._metric_labels
                ), self._trace.span(
                    "publish", step=self._step, quorum_id=self._quorum_id
                ):
                    publisher.publish(
                        step=self._step, quorum_id=self._quorum_id, state=state
                    )
            except Exception as e:  # noqa: BLE001 — publication is best-effort
                metrics.inc("tpuft_publish_failures_total", **self._metric_labels)
                self._logger.exception(
                    f"publish failed (readers lag one cadence; training "
                    f"unaffected): {e}"
                )
        # Progressive delivery: one rollout-verdict evidence window per
        # STEP BOUNDARY, not per publication — a canary wave must keep
        # accumulating evidence between publishes or a slow cadence would
        # starve the verdict loop (serving/rollout.py RolloutDirector).
        # on_commit never raises — verdicts are advisory to the step loop.
        director = getattr(publisher, "rollout_director", None)
        if director is not None:
            director.on_commit(self._step, self._quorum_id)

    def register_heal_parts_filter(self, fn: Callable[[], Any]) -> None:
        """Registers a callable returning the set of heal-part names
        (``checkpointing.transport.HEAL_PART_PREFIX`` keys) this replica
        does NOT need a donor to stream — it reconstructs them through a
        cheaper plane instead (the ZeRO optimizer re-balances its shard
        states from survivors over the PG). The union of all filters is
        passed to ``recv_checkpoint(skip_parts=...)`` on every heal;
        filter errors are ignored (skipping is an optimization — the
        fallback is simply fetching everything)."""
        self._heal_parts_filters.append(fn)

    def _heal_skip_parts(self) -> Optional[set]:
        skip: set = set()
        for fn in self._heal_parts_filters:
            try:
                skip |= set(fn() or ())
            except Exception:  # noqa: BLE001 — skip is best-effort
                self._logger.exception("heal parts filter failed (ignored)")
        return skip or None

    def register_shutdown_hook(self, hook: Callable[[], None]) -> None:
        """Runs ``hook`` during :meth:`shutdown` (before the executor stops).

        Lets higher layers tie per-manager resources (e.g. ddp's cached fp8
        wire worker) to the manager's explicit lifecycle instead of garbage
        collection — a shut-down manager held by a fixture list must not
        leak threads. Hooks run at most once; errors are swallowed so one
        failing hook cannot block teardown."""
        self._shutdown_hooks.append(hook)

    def shutdown(self, wait: bool = True) -> None:
        hooks, self._shutdown_hooks = self._shutdown_hooks, []
        for hook in hooks:
            try:
                hook()
            except Exception:
                pass
        self._checkpoint_transport.shutdown(wait=wait)
        if self._manager is not None:
            self._manager.shutdown()
        self._executor.shutdown(wait=wait)
        with self._commit_pool_lock:
            if self._commit_pool is not None:
                self._commit_pool.shutdown(wait=wait)
                self._commit_pool = None
        self._client.close()

    # ------------------------------------------------------------------
    # allreduce
    # ------------------------------------------------------------------

    def allreduce(
        self,
        tensor: Any,
        should_quantize: bool = False,
        reduce_op: ReduceOp = ReduceOp.AVG,
    ) -> Work:
        """Fault-tolerant allreduce (reference: manager.py:385-467).

        Stages ``tensor`` to host, averages it across participating replica
        groups, and returns a :class:`Work` resolving to the result (numpy).
        On error the work resolves to the *input* tensor and the error is
        tracked via :meth:`errored` — the step will not commit.

        AVG runs as SUM + divide by ``num_participants()`` so the math is
        world-size independent; non-participating replicas contribute zeros.
        """
        if self.errored():
            return _DummyWork(tensor)

        with tracing.phase("allreduce", step=self._step):
            return self._allreduce_impl(tensor, should_quantize, reduce_op)

    def _allreduce_impl(
        self, tensor: Any, should_quantize: bool, reduce_op: ReduceOp
    ) -> Work:
        self.wait_quorum()
        num_participants = self.num_participants()

        array = np.asarray(tensor)
        if not self.is_participating():
            array = np.zeros_like(array)

        pg_reduce_op = reduce_op
        if reduce_op == ReduceOp.AVG:
            # kind "V" covers ml_dtypes custom floats (bfloat16, fp8).
            if array.dtype.kind not in ("f", "V"):
                raise ValueError("average reduce op requires floating point tensors")
            pg_reduce_op = ReduceOp.SUM

        try:
            if should_quantize:
                from torchft_tpu.parallel.collectives import allreduce_quantized

                work = allreduce_quantized([array], pg_reduce_op, self._pg)
            else:
                work = self._pg.allreduce([array], pg_reduce_op)

            def callback(result: List[np.ndarray]) -> np.ndarray:
                out = result[0]
                if reduce_op == ReduceOp.AVG:
                    out = (out / num_participants).astype(out.dtype)
                return out

            return self.wrap_work(work.then(callback), default=array)
        except Exception as e:  # noqa: BLE001
            self._logger.exception(f"allreduce failed; poisoning this step (commit will be skipped): {e}")
            self.report_error(e)
            return _DummyWork(tensor)

    def allreduce_pytree(self, pytree: Any, should_quantize: bool = False) -> Work:
        """Averages every array leaf of ``pytree`` across replicas; resolves
        to a pytree of the same structure (numpy leaves).

        Leaves are **bucketed**: same-dtype leaves concatenate into one flat
        buffer per dtype so the wire carries one collective per bucket
        instead of one per parameter (DDP's frozen-bucket role; flatten
        order is deterministic across replicas for identical models)."""
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(pytree)
        # Same contract as the scalar `allreduce` AVG path (see
        # _allreduce_impl): averaging integer leaves would silently
        # floor-divide. Validate BEFORE every early return (errored /
        # lone-replica) so the programming error surfaces deterministically
        # at any quorum size instead of only once a second replica joins.
        for leaf in leaves:
            if np.dtype(getattr(leaf, "dtype", type(leaf))).kind not in ("f", "V"):
                raise ValueError(
                    "allreduce_pytree averages leaves and requires floating "
                    f"point dtypes; got {np.dtype(getattr(leaf, 'dtype', type(leaf)))}. "
                    "Cast the leaf to float or exclude it from the synced pytree."
                )
        if self.errored():
            return _DummyWork(pytree)
        with tracing.phase("allreduce_pytree", step=self._step):
            return self._allreduce_pytree_impl(
                pytree, leaves, treedef, should_quantize
            )

    def _allreduce_pytree_impl(
        self, pytree: Any, leaves: List[Any], treedef: Any, should_quantize: bool
    ) -> Work:
        import jax

        self.wait_quorum()
        num_participants = self.num_participants()
        if self.is_lone_replica():
            # Identity: SUM over one participant / 1. Resolve to host
            # copies of the leaves (the documented numpy contract)
            # without touching the wire.
            return _DummyWork(
                jax.tree_util.tree_unflatten(
                    treedef, [np.asarray(leaf) for leaf in leaves]
                )
            )
        # The wire's stages, each a phase of its own (tpuft::wire::*,
        # tpuft_wire_stage_seconds{stage}); ids as this step's.
        trace, labels, step = self._trace, self._metric_labels, self._step
        with tracing.phase("wire_stage", trace, labels, step=step):
            # Launch every device→host copy before completing any: the
            # per-leaf np.asarray then drains transfers that are already in
            # flight instead of serializing them.
            prefetch_to_host(leaves)
            arrays = [np.asarray(leaf) for leaf in leaves]
        if not self.is_participating():
            arrays = [np.zeros_like(a) for a in arrays]

        # Bucket same-dtype leaves (stable order). The quantized path stays
        # per-leaf: concatenation would let one fp8 block's max-abs scale
        # span parameter boundaries and crush small-magnitude leaves to 0.
        with tracing.phase("wire_bucket", trace, labels, step=step):
            if should_quantize:
                buckets: Dict[Any, List[int]] = {
                    index: [index] for index in range(len(arrays))
                }
                flat_buffers = [a.reshape(-1) for a in arrays]
            else:
                buckets = {}
                for index, array in enumerate(arrays):
                    buckets.setdefault(array.dtype, []).append(index)
                flat_buffers = [
                    np.concatenate([arrays[i].reshape(-1) for i in members])
                    if len(members) > 1
                    else arrays[members[0]].reshape(-1)
                    for members in buckets.values()
                ]
        try:
            # The ring crosses threads: it starts here and ends where the
            # PG's worker resolves the future, so it is recorded there with
            # this start stamp (the waiting thread annotates its own wait).
            ring_start = trace._mono()
            if should_quantize:
                from torchft_tpu.parallel.collectives import allreduce_quantized

                work = allreduce_quantized(flat_buffers, ReduceOp.SUM, self._pg)
            else:
                work = self._pg.allreduce(flat_buffers, ReduceOp.SUM)

            def callback(result: List[np.ndarray]) -> Any:
                tracing.record_phase("wire_ring", ring_start, trace, labels, step=step)
                with tracing.phase("wire_average", trace, labels, step=step):
                    averaged: List[Any] = [None] * len(arrays)
                    for flat, members in zip(result, buckets.values()):
                        # Float-only by the precondition above.
                        flat = (flat / num_participants).astype(flat.dtype)
                        offset = 0
                        for i in members:
                            size = arrays[i].size
                            # Copy: leaves must not alias one shared bucket
                            # buffer (or the caller's input via an echo PG).
                            averaged[i] = (
                                flat[offset : offset + size]
                                .reshape(arrays[i].shape)
                                .copy()
                            )
                            offset += size
                    return jax.tree_util.tree_unflatten(treedef, averaged)

            return self.wrap_work(work.then(callback), default=pytree)
        except Exception as e:  # noqa: BLE001
            self._logger.exception(f"allreduce failed; poisoning this step (commit will be skipped): {e}")
            self.report_error(e)
            return _DummyWork(pytree)

    def allreduce_prequantized(self, payload: Any, scales: Any) -> Work:
        """Averages device-prequantized data (fp8 payload + f32 block scales,
        ops/quantization.py layout) across participating replicas with the
        same semantics as :meth:`allreduce_pytree`: non-participants zero
        their contribution (by zeroing scales — free), errors resolve the
        work to None and poison the step. Resolves to (payload, scales) of
        the average for device-side dequantization."""
        from torchft_tpu.parallel.collectives import allreduce_quantized_wire

        if self.errored():
            return _DummyWork(None)
        with tracing.phase("allreduce_prequantized", step=self._step):
            self.wait_quorum()
            num_participants = self.num_participants()
        if self.is_lone_replica():
            # Averaging over one participant is the identity: skip the
            # device→host→wire→device round trip entirely (the payload stays
            # on device; callers feed it straight back to the dequant jit).
            return self.wrap_work(_DummyWork((payload, scales)), default=None)
        if not self.is_participating():
            scales = scales * 0
        try:
            work = allreduce_quantized_wire(payload, scales, ReduceOp.SUM, self._pg)
            return self.wrap_work(
                work.then(lambda ps: (ps[0], ps[1] / max(num_participants, 1))),
                default=None,
            )
        except Exception as e:  # noqa: BLE001
            self._logger.exception(f"allreduce failed; poisoning this step (commit will be skipped): {e}")
            self.report_error(e)
            return _DummyWork(None)

    # ------------------------------------------------------------------
    # error tracking
    # ------------------------------------------------------------------

    def report_error(self, e: Exception) -> None:
        """Records an error for this step: the step will not commit and the
        comm layer is reconfigured on the next quorum."""
        self._errored = ExceptionWithTraceback(e)
        metrics.inc("tpuft_errors_total", **self._metric_labels)
        self._trace.record(
            "report_error",
            step=self._step,
            quorum_id=self._quorum_id,
            error=str(e),
            error_type=type(e).__name__,
        )
        errors_logger.info(
            "error",
            extra={
                "job_id": os.environ.get("JOB_ID", "unknown"),
                "replica_id": self._replica_id,
                "rank": self._group_rank,
                "quorum_id": self._quorum_id,
                "step": self._step,
                "error": str(e),
            },
        )
        from torchft_tpu.utils import flight_recorder

        flight_recorder.dump_on_failure(
            "manager",
            f"report_error step={self._step} quorum={self._quorum_id}: {e}",
        )

    def errored(self) -> Optional[ExceptionWithTraceback]:
        return self._errored

    def wrap_work(self, work: Work, default: Any, timeout: Optional[float] = None) -> Work:
        """Bounds ``work`` with a deadline and swallows its errors into
        :meth:`report_error`, resolving to ``default`` instead (reference
        ``wrap_future``, manager.py:491-532)."""
        from torchft_tpu.futures import future_timeout

        timed = Work(future_timeout(work._future, timeout or self._timeout))

        def handler(e: Exception) -> None:
            self._logger.exception(f"future raised; remaining callbacks skipped: {e}")
            self.report_error(e)

        return timed.with_error_handler(handler, default)

    # Alias matching the reference name.
    wrap_future = wrap_work

    # ------------------------------------------------------------------
    # quorum
    # ------------------------------------------------------------------

    def start_quorum(
        self,
        allow_heal: bool = True,
        shrink_only: bool = False,
        timeout: Optional[float] = None,
    ) -> None:
        """Starts a (possibly async) quorum and readies the manager for a new
        step (reference: manager.py:534-589). Call before the forward pass."""
        schedules.point("manager.start_quorum")
        with tracing.phase("start_quorum", self._trace, step=self._step):
            self._begin_quorum(allow_heal, shrink_only, timeout)
        if not self._use_async_quorum:
            self.wait_quorum()
            if self._healing:
                # Eagerly apply the pending state dict so the forward pass
                # runs against recovered parameters.
                self._apply_pending_state_dict()
                self._healing = False

    def _begin_quorum(
        self, allow_heal: bool, shrink_only: bool, timeout: Optional[float]
    ) -> None:
        """The synchronous part of :meth:`start_quorum`: the drain of what
        the previous step left pending, the health gate, the publication
        and the hand-over to the quorum thread."""
        if self._quorum_future is not None:
            self._quorum_future.result()

        # Enforce the should_commit_async ordering contract: the commit
        # barrier reads (and may heal through) the per-step error/heal flags,
        # so an unresolved commit future queued behind this quorum would vote
        # with wiped flags and silently drop a pending heal. Drain it here so
        # the misordering is impossible rather than merely documented.
        self._drain_pending_commit("start_quorum")

        # Gray-failure self-ejection: a latched degraded verdict (or a
        # wedge-watchdog trip) leaves the fleet HERE, at the step
        # boundary, with the previous commit fully resolved — the same
        # supervisor-escalation family as a quorum timeout or
        # HealExhaustedError. Survivors observe an ordinary membership
        # change (window drain -> pg.configure -> proceed) and this
        # replica rejoins through the quarantine gate + normal heal path.
        if self._health is not None:
            eject_reason = self._health.should_eject()
            if eject_reason is not None:
                err = DegradedReplicaError(eject_reason)
                self.report_error(err)
                self._health.note_ejected(eject_reason)
                raise err

        self._errored = None
        self._healing = False

        # Serving plane: a due publication runs here, on the train thread,
        # with no quorum task in flight — the drain inside can resolve the
        # window's votes without racing the quorum executor, and any error
        # it reports sticks to THIS step's freshly wiped flags.
        self._maybe_publish()

        self._quorum_future = self._executor.submit(
            self._async_quorum,
            allow_heal=allow_heal,
            shrink_only=shrink_only,
            quorum_timeout=timeout or self._quorum_timeout,
        )

    def _drain_pending_commit(self, caller: str) -> None:
        """Resolves any should_commit_async future the caller never
        observed, BEFORE the per-step error/heal flags are wiped (or a new
        barrier queued behind it): an unresolved commit queued behind a new
        quorum would vote with wiped flags and silently drop a pending
        heal, and a stored barrier exception (e.g. the max_retries
        RuntimeError, the supervisor-restart signal) must propagate rather
        than be silently dropped. A future the caller already resolved and
        handled is NOT replayed on a later, healthy step."""
        pending_commit = self._pending_commit_future
        self._pending_commit_future = None
        if pending_commit is not None and not pending_commit.consumed:
            if not pending_commit.done():
                self._logger.warn(
                    f"{caller} called with an unresolved should_commit_async "
                    "future; draining it so the commit votes with its own "
                    "step's error/heal flags instead of the wiped ones"
                )
            pending_commit.result()

    def wait_quorum(self) -> None:
        """Blocks until the quorum completes; the PG is healthy after."""
        future = self._quorum_future
        assert future is not None, "must call start_quorum before wait_quorum"
        if future.done():
            # An accessor's look at a quorum that is already there (every
            # ``num_participants()`` comes through here): no wait, so no span.
            future.result()
            return
        with tracing.phase("wait_quorum", self._trace, step=self._step):
            future.result()

    def _async_quorum(
        self, allow_heal: bool, shrink_only: bool, quorum_timeout: float
    ) -> None:
        try:
            with tracing.phase(
                "quorum", self._trace, self._metric_labels, step=self._step
            ):
                quorum = self._client._quorum(
                    group_rank=self._group_rank,
                    step=self._step,
                    checkpoint_metadata=self._checkpoint_transport.metadata(),
                    shrink_only=shrink_only,
                    init_sync=self._init_sync,
                    commit_failures=self._commit_failures,
                    timeout=quorum_timeout,
                )
        except Exception as e:
            # A quorum that never resolves is supervisor-restart territory
            # (the exception escalates out of the quorum future): stamp the
            # shared incident id so every process that timed out on the
            # same quorum dumps a correlatable journal + flight-recorder
            # ring under $TPUFT_FLIGHT_RECORDER.
            kind = (
                "quorum_timeout"
                if isinstance(e, TimeoutError) or "timed out" in str(e).lower()
                else "quorum_error"
            )
            tracing.open_incident(
                kind, self._step, self._quorum_id,
                journal=self._trace, reason=str(e),
            )
            raise

        # Participation bookkeeping: async quorum means a healing replica
        # sits out this step (max-step cohort participates); sync quorum
        # means everyone participates post-heal (reference: manager.py:
        # 636-657).
        if self._use_async_quorum or not allow_heal:
            self._participating_replica_rank = quorum.max_rank
            self._participating_replica_world_size = quorum.max_world_size
        else:
            self._participating_replica_rank = quorum.replica_rank
            self._participating_replica_world_size = quorum.replica_world_size

        if self._replica_world_size_mode == WorldSizeMode.FIXED_WITH_SPARES:
            self._participating_replica_world_size = min(
                self._participating_replica_world_size, self._min_replica_size
            )
            if (
                self._participating_replica_rank is not None
                and self._participating_replica_rank >= self._min_replica_size
            ):
                self._participating_replica_rank = None

        metrics.set_gauge(
            "tpuft_participants",
            self._participating_replica_world_size,
            **self._metric_labels,
        )
        # Storm visibility: how many members of this quorum are behind
        # max_step (i.e. joining/healing) as THIS replica observed it.
        # Pushed with the metrics snapshot, so fleet_status's JOINERS
        # column shows every replica's view — drift between views is
        # itself a debugging signal (a member seeing stale quorums).
        joining = 0
        if quorum.quorum is not None and quorum.max_step > 0:
            joining = sum(
                1
                for member in quorum.quorum.participants
                if member.step < quorum.max_step
            )
        metrics.set_gauge(
            "tpuft_heal_storm_joiners", joining, **self._metric_labels
        )
        if self._health is not None:
            # Peer discovery for the health board: participant ids + the
            # quorum's shared rendezvous store. Best-effort inside.
            self._health.on_quorum(quorum)
        self._trace.record(
            "quorum_ready",
            step=self._step,
            quorum_id=quorum.quorum_id,
            participants=self._participating_replica_world_size,
            heal=bool(quorum.heal),
            joining=joining,
        )

        if quorum.quorum_id != self._quorum_id:
            metrics.inc("tpuft_quorum_changes_total", **self._metric_labels)
            self._trace.record(
                "quorum_change",
                step=self._step,
                quorum_id=quorum.quorum_id,
                old_quorum_id=self._quorum_id,
                participants=self._participating_replica_world_size,
            )
            quorums_logger.info(
                "quorum",
                extra={
                    "job_id": os.environ.get("JOB_ID", "unknown"),
                    "replica_id": self._replica_id,
                    "rank": self._group_rank,
                    "quorum_id": quorum.quorum_id,
                    "step": quorum.max_step,
                },
            )
            store_prefixed_addr = (
                f"{quorum.store_address}/tpuft/{quorum.quorum_id}/{self._group_rank}"
            )
            self._logger.info(
                f"reconfiguring for quorum_id={quorum.quorum_id} {store_prefixed_addr=}"
            )
            # Membership changed: drain anything the pipelined-commit mode
            # still has in flight BEFORE reconfiguring the wire or serving
            # a donor checkpoint — the new quorum era (and any joiner
            # healing from this replica) must observe committed state only.
            # With a depth-N window this resolves the FULL window; the
            # committed step may advance past quorum.max_step here, and
            # the donor send below then serves max_step EXACTLY from the
            # history ring (resolved slots promote instead of dropping —
            # torchft_tpu/history.py). Only a ring miss falls back to
            # staging the drained step honestly labeled, which the joiner
            # rejects cleanly and retries — never mislabeled bytes.
            self._run_quorum_drain_hooks()
            # Era boundary: the adaptive controller re-derives its depth
            # from the measured barrier RTT vs step time (the only point
            # the window may SHRINK — see _adapt_pipeline_depth).
            self._adapt_pipeline_depth()
            try:
                with tracing.phase(
                    "pg_configure", self._trace, self._metric_labels,
                    step=self._step, quorum_id=quorum.quorum_id,
                ):
                    self._pg.configure(
                        store_prefixed_addr,
                        self._replica_id,
                        quorum.replica_rank,
                        quorum.replica_world_size,
                    )
                metrics.inc("tpuft_pg_configure_total", **self._metric_labels)
                self._quorum_id = quorum.quorum_id
                self._trace.set_step(self._step, self._quorum_id)
            except Exception as e:  # noqa: BLE001
                self._logger.exception(f"got exception in pg configure: {e}")
                self.report_error(e)
                return

        if allow_heal:
            # Striped heals fetch from EVERY max-step member, not only the
            # assigned donor: when a heal is in flight anywhere in the
            # quorum, each member whose state matches max_step co-stages
            # the same committed bytes so joiners can partition the fetch
            # across the whole donor set. The digest is donor-independent
            # (bitwise-identical committed state), which is what makes the
            # co-staged copies interchangeable.
            stripe_costage = (
                heal_stripe_enabled()
                and not quorum.recover_dst_replica_ranks
                and quorum.max_step > 0
                and self._step == quorum.max_step
                and not quorum.heal
                and quorum.quorum is not None
                and any(
                    member.step < quorum.max_step
                    for member in quorum.quorum.participants
                )
            )
            if quorum.recover_dst_replica_ranks or stripe_costage:
                # A donor send must NEVER sample speculative state, even
                # when the quorum id did not move (e.g. a repeated heal
                # round inside one era): drain the full window here too —
                # idempotent, the membership-change path above already ran
                # the hooks when the id changed. In child serve mode the
                # sidecar's restaged snapshot therefore can never contain
                # uncommitted state either.
                self._run_quorum_drain_hooks()
                serve_step = quorum.max_step
                serve_state_dict: Optional[Dict[str, Any]] = None
                if self._step > serve_step:
                    # Draining a depth-N window advanced our committed
                    # step past the quorum's (pre-drain-reported)
                    # max_step. The history ring holds the last K
                    # committed steps exactly (optim promotes each slot
                    # at resolution), so serve the joiner the step it
                    # asked for — the committed bytes AT max_step,
                    # honestly labeled. Only a ring miss (evicted /
                    # never promoted) falls back to staging the drained
                    # step, which the joiner rejects cleanly and retries
                    # next round — never mislabeled bytes either way.
                    # Step 0 is the init_sync mosaic (per-rank state,
                    # never history-served).
                    if serve_step > 0:
                        serve_state_dict = self._history_state_dict(serve_step)
                    if serve_state_dict is not None:
                        metrics.inc(
                            "tpuft_history_exact_serves_total",
                            **self._metric_labels,
                        )
                        self._trace.record(
                            "history_exact_serve",
                            step=serve_step,
                            quorum_id=quorum.quorum_id,
                            drained_step=self._step,
                        )
                        self._logger.info(
                            f"donor serving step {serve_step} exactly from "
                            f"the history ring (drained step {self._step})"
                        )
                    else:
                        metrics.inc(
                            "tpuft_history_misses_total",
                            **self._metric_labels,
                        )
                        self._logger.info(
                            f"donor staging drained step {self._step} "
                            f"(quorum max_step={serve_step}): history ring "
                            "cannot serve the exact step"
                        )
                        serve_step = self._step
                try:
                    if stripe_costage:
                        self._logger.info(
                            "a peer is healing; co-staging our checkpoint "
                            "for the striped donor set"
                        )
                        metrics.inc(
                            "tpuft_heal_stripe_costages_total",
                            **self._metric_labels,
                        )
                    else:
                        self._logger.info(
                            f"peers need recovery from us {quorum.recover_dst_replica_ranks}"
                        )
                        metrics.inc(
                            "tpuft_heals_total",
                            role="donor",
                            **self._metric_labels,
                        )
                    with trace_span(
                        "tpuft::manager::_checkpoint_transport::send_checkpoint",
                        quorum_id=quorum.quorum_id,
                        step=serve_step,
                    ), metrics.timer(
                        "tpuft_heal_send_seconds", **self._metric_labels
                    ), tracing.phase(
                        "heal_send",
                        self._trace,
                        step=serve_step,
                        quorum_id=quorum.quorum_id,
                        dst_ranks=str(list(quorum.recover_dst_replica_ranks)),
                    ):
                        self._checkpoint_transport.send_checkpoint(
                            dst_ranks=quorum.recover_dst_replica_ranks,
                            step=serve_step,
                            state_dict=(
                                serve_state_dict
                                if serve_state_dict is not None
                                else self._manager_state_dict()
                            ),
                            timeout=self._timeout,
                            quorum_id=quorum.quorum_id,
                        )
                except Exception as e:  # noqa: BLE001
                    self._logger.exception(f"got exception in donor send: {e}")
                    self.report_error(e)

            if quorum.heal:
                self._heal_as_joiner(quorum)

    def _heal_as_joiner(self, quorum: Any) -> None:
        """One heal attempt against the quorum's donor set, with the
        failover accounting around it.

        The assigned donor stays the anchor (its /meta is fetched first,
        and the single-donor path is byte-identical to the pre-striping
        behavior), but the transfer itself stripes across every max-step
        participant the quorum advertises (:meth:`_resolve_stripe_donors`)
        and diffs against the local stale state when there is one
        (:meth:`_delta_local_state`) — donor death/stall/staleness inside
        the stripe set is handled *inside* the attempt by reassignment.
        Only when the whole attempt fails does the cross-round machinery
        here engage: the failure funnels into :meth:`report_error` (clean
        fail — the joiner re-enters the next quorum still joining and the
        transport's per-chunk resume cache keeps the verified chunks), the
        donor is marked for a one-shot fail-fast skip (a dead donor also
        leaves via heartbeat expiry, so the next assignment excludes it),
        and once ``heal_max_attempts`` consecutive attempts have failed
        :class:`HealExhaustedError` escalates out of the quorum future to
        the supervisor."""
        self._healing = True
        metrics.set_gauge("tpuft_healing", 1, **self._metric_labels)
        metrics.inc("tpuft_heals_total", role="joiner", **self._metric_labels)
        src_addr = quorum.recover_src_manager_address
        try:
            if self._heal_attempts > 0:
                metrics.inc("tpuft_heal_retries_total", **self._metric_labels)
            if self._heal_failed_donors.get(src_addr, False):
                # One-shot fail-fast: this donor failed us on the previous
                # attempt; skip the transfer (no window burned against
                # fresh evidence) so the next quorum round can rotate the
                # assignment. If it assigns the same donor again, attempt
                # it for real — it may have recovered.
                self._heal_failed_donors[src_addr] = False
                raise _DonorRecentlyFailed(
                    f"donor {src_addr} failed the previous heal attempt; "
                    "skipping one round to let the assignment rotate"
                )
            if (
                self._heal_last_failed_donor is not None
                and src_addr != self._heal_last_failed_donor
            ):
                metrics.inc(
                    "tpuft_heal_donor_failovers_total", **self._metric_labels
                )
                self._logger.info(
                    f"heal failover: donor {self._heal_last_failed_donor} "
                    f"failed, retrying from {src_addr}"
                )
            self._logger.info(
                "healing required, fetching checkpoint metadata from "
                f"{src_addr} max_step={quorum.max_step}"
            )
            primary_client = ManagerClient(
                src_addr,
                connect_timeout=self._connect_timeout,
            )
            checkpoint_metadata = primary_client._checkpoint_metadata(
                self._group_rank, timeout=self._timeout
            )
            primary_client.close()
            assert (
                quorum.recover_src_replica_rank is not None
            ), "must have a recover rank when healing"
            rotation = self._storm_rotation(quorum)
            metrics.set_gauge(
                "tpuft_heal_storm_rotation", rotation, **self._metric_labels
            )
            donor_urls = self._resolve_stripe_donors(quorum, rotation=rotation)
            # The assigned donor rides the same advisory info map (its
            # replica id comes from the quorum view by address) so the
            # transport's bandwidth EWMA and same-/cross-region byte
            # accounting cover the anchor donor too.
            q = quorum.quorum
            if q is not None:
                for member in q.participants:
                    if member.address == src_addr:
                        self._heal_donor_info[checkpoint_metadata] = {
                            "replica_id": member.replica_id,
                            "region": netem.region_of(member.replica_id),
                        }
                        break
            local_state = self._delta_local_state(quorum)
            with trace_span(
                "tpuft::manager::_checkpoint_transport::recv_checkpoint",
                quorum_id=quorum.quorum_id,
                step=quorum.max_step,
            ), metrics.timer(
                "tpuft_heal_recv_seconds", **self._metric_labels
            ), tracing.phase(
                "heal_recv",
                self._trace,
                step=quorum.max_step,
                quorum_id=quorum.quorum_id,
                donor=src_addr,
                donors=len(donor_urls) + 1,
                delta=local_state is not None,
                attempt=self._heal_attempts,
                rotation=rotation,
            ):
                self._pending_state_dict = self._checkpoint_transport.recv_checkpoint(
                    src_rank=quorum.recover_src_replica_rank,
                    metadata=checkpoint_metadata,
                    step=quorum.max_step,
                    timeout=self._timeout,
                    quorum_id=quorum.quorum_id,
                    skip_parts=self._heal_skip_parts(),
                    donors=donor_urls,
                    local_state=local_state,
                    stripe_rotation=rotation,
                    donor_info=self._heal_donor_info,
                )
            # Restore manager accounting immediately; user state is
            # applied from the main thread when safe.
            self.load_state_dict(self._pending_state_dict["tpuft"])
            self._step = quorum.max_step
            self._trace.set_step(self._step)
            self._heal_attempts = 0
            self._heal_last_failed_donor = None
            self._heal_failed_donors.clear()
        except Exception as e:  # noqa: BLE001
            if not isinstance(e, _DonorRecentlyFailed):
                self._heal_attempts += 1
                self._heal_last_failed_donor = src_addr
                self._heal_failed_donors[src_addr] = True
            self._logger.exception(f"got exception in recovery: {e}")
            self._trace.record(
                "heal_attempt_failed",
                step=quorum.max_step,
                quorum_id=quorum.quorum_id,
                donor=src_addr,
                attempt=self._heal_attempts,
                error=str(e),
            )
            self.report_error(e)
            if self._heal_attempts >= self._heal_max_attempts:
                tracing.open_incident(
                    "heal_exhausted", quorum.max_step, quorum.quorum_id,
                    journal=self._trace,
                    reason=f"{self._heal_attempts} attempts, last donor {src_addr}",
                )
                raise HealExhaustedError(
                    f"{self._heal_attempts} consecutive heal attempts failed "
                    f"(last donor {src_addr}); escalating to the supervisor "
                    f"(bound from ${HEAL_MAX_ATTEMPTS_ENV})"
                ) from e

    def _storm_rotation(self, quorum: Any) -> int:
        """This joiner's coordinated-storm offset (see
        :func:`storm_stripe_rotation`): derived purely from the quorum
        view every member already holds, so N joiners agree on who is
        joiner 0..N-1 without a single extra RPC."""
        joining: List[str] = []
        q = quorum.quorum
        if q is not None and quorum.max_step > 0:
            joining = [
                member.replica_id
                for member in q.participants
                if member.step < quorum.max_step
            ]
        return storm_stripe_rotation(
            self._replica_id, joining, self._group_rank, quorum.quorum_id
        )

    def _resolve_stripe_donors(
        self, quorum: Any, rotation: Optional[int] = None
    ) -> List[str]:
        """Extra donor addresses for a striped heal: every quorum
        participant standing at ``max_step`` holds bitwise-identical
        committed state (and co-stages it when it sees a joiner — see
        ``_async_quorum``), so its transport can serve any stripe of the
        fetch. Each candidate's manager resolves to its checkpoint
        transport address; resolution is best-effort per donor — a peer
        that cannot be resolved is simply left out of the stripe set,
        never a reason to fail the heal. The extras rotate by the storm
        offset (:meth:`_storm_rotation` — joiner ordinal + group rank +
        quorum id) so N concurrent joiners spread their donor ORDER and,
        past the stripe cap, their donor SUBSETS across the fleet
        instead of all hammering it in the same sequence.

        Striping is skipped entirely at ``max_step == 0``: the init_sync
        heal is a per-LOCAL-rank mosaic (state is intentionally NOT
        identical across replicas yet), so only the assigned donor is
        valid there.

        Under a WAN topology (``netem.topology_enabled``) the rotated
        candidate order is stably re-sorted same-region-first BEFORE the
        cap, so the stripe set saturates the cheap intra-region links and
        cross-region donors only fill remaining slots; a region with zero
        live same-region donors keeps its cross-region candidates — the
        preference can narrow where bytes come from, never whether they
        come. With no topology the sort key is uniform and the order (and
        behavior) is byte-identical to the region-blind plan."""
        self._heal_donor_info = {}
        if not heal_stripe_enabled() or quorum.max_step <= 0:
            return []
        q = quorum.quorum
        if q is None:
            return []
        candidates = [
            (member.address, member.replica_id)
            for member in q.participants
            if member.address
            and member.address != quorum.recover_src_manager_address
            and member.replica_id != self._replica_id
            and member.step >= quorum.max_step
        ]
        if not candidates:
            return []
        if rotation is None:
            rotation = self._storm_rotation(quorum)
        # Rotate BEFORE capping: joiners beyond the cap then resolve
        # different donor subsets, not just different orderings.
        rotate = rotation % len(candidates)
        candidates = candidates[rotate:] + candidates[:rotate]
        my_region = netem.local_region()
        if my_region is not None:
            # Stable: within each region class the storm rotation's
            # ordering survives, so concurrent joiners still spread.
            candidates.sort(
                key=lambda c: 0 if netem.region_of(c[1]) == my_region else 1
            )
        # The cap minus the assigned donor; the transport re-applies it
        # after deduping, this just avoids pointless resolution RPCs.
        candidates = candidates[: max(0, heal_stripe_max_donors() - 1)]
        urls: List[str] = []
        for addr, rid in candidates:
            try:
                client = ManagerClient(
                    addr, connect_timeout=self._connect_timeout
                )
                try:
                    url = client._checkpoint_metadata(
                        self._group_rank, timeout=self._timeout
                    )
                finally:
                    client.close()
                urls.append(url)
                self._heal_donor_info[url] = {
                    "replica_id": rid,
                    "region": netem.region_of(rid),
                }
            except Exception as e:  # noqa: BLE001 — best-effort per donor
                self._logger.warn(
                    f"stripe donor {addr} metadata resolution failed ({e}); "
                    "striping without it"
                )
        metrics.set_gauge(
            "tpuft_heal_stripe_donors", len(urls) + 1, **self._metric_labels
        )
        return urls

    def _delta_local_state(self, quorum: Any) -> Optional[Dict[str, Any]]:
        """The joiner's stale-but-recent state for delta rejoin, or None
        when there is nothing worth diffing: delta disabled, no real local
        progress (``step == 0`` — freshly initialized state, and the
        init_sync mosaic owns step-0 heals anyway), or no registered user
        state yet. Building it costs one host snapshot; the transport pays
        one serialize+CRC pass only after the donor's manifest proves the
        layouts comparable."""
        if not heal_delta_enabled() or self._step <= 0 or quorum.max_step <= 0:
            return None
        if not self._user_state_dicts:
            return None
        try:
            return self._manager_state_dict()
        except Exception as e:  # noqa: BLE001 — delta is an optimization
            self._logger.warn(
                f"delta-rejoin local state unavailable ({e}); full fetch"
            )
            return None

    def _apply_pending_state_dict(self) -> None:
        schedules.point("manager.apply_pending_state")
        assert self._healing, "must be in healing state"
        assert self._quorum_future is not None, "must call start_quorum first"
        self._quorum_future.result()

        if self._pending_state_dict is None:
            assert self.errored(), "checkpoint was not staged and no error occurred"
            return
        self._logger.info("applying pending state dict")
        assert self._load_state_dict_fns, "user load_state_dict is not initialized"
        pending_user = cast(Dict[str, Any], self._pending_state_dict["user"])
        # Healing rebinds registered state: take the writer so a checkpoint
        # serve staging on another thread never captures a half-applied
        # mosaic (the lock-discipline invariant R3 enforces statically —
        # the load fns themselves are suppressed at their definition sites
        # because THIS caller owns the lock).
        self.disallow_state_dict_read()
        try:
            for key, load_fn in self._load_state_dict_fns.items():
                load_fn(pending_user[key])
        finally:
            self.allow_state_dict_read()
        self._pending_state_dict = None
        metrics.set_gauge("tpuft_healing", 0, **self._metric_labels)
        self._logger.info("Loaded state dict.")

    # ------------------------------------------------------------------
    # commit
    # ------------------------------------------------------------------

    def should_commit_async(
        self, timeout: Optional[float] = None
    ) -> "_TrackedCommitFuture":
        """:meth:`should_commit` dispatched on the manager's executor so the
        barrier RPC overlaps work the caller still has to do this step —
        e.g. dispatching the speculative optimizer update (optim.py) or the
        next batch's h2d. The reference's analogue is keeping commit cost
        off the step's critical path (manager.py:790-878 design note).

        The caller SHOULD resolve the future before reading any state the
        barrier may heal (should_commit applies pending state dicts) and
        before calling start_quorum. The ordering is enforced:
        ``start_quorum`` drains any still-unresolved commit future before
        wiping the per-step error/heal flags, so a misordered caller blocks
        (and sees the barrier's exception, if any) instead of silently
        dropping a pending heal."""
        # A second async barrier with the first still unobserved would
        # silently drop the first's tracking (and any stored exception) on
        # overwrite — drain it with the same semantics start_quorum uses.
        # A span of its own: the hand-over to the executor runs on the
        # caller's thread, under the step's root (0.1 ms a step on the chip).
        with tracing.phase("commit_submit", self._trace, step=self._step):
            self._drain_pending_commit("should_commit_async")
            future = _TrackedCommitFuture(self._executor.submit(self.should_commit, timeout))
            self._pending_commit_future = future
        return future

    def should_commit(self, timeout: Optional[float] = None) -> bool:
        """All-local-rank commit barrier (reference: manager.py:790-878).

        Call after the step's math is complete (``jax.block_until_ready`` on
        the outputs) and step the optimizer only when this returns True.
        """
        # The barrier must run unlocked: it may apply a healing state dict
        # (write lock) and peer serve threads need the read lock meanwhile.
        # No-op unless the lock-order detector is enabled (TPUFT_LOCK_CHECK).
        lockcheck.check_barrier("Manager.should_commit")
        if err := self._pg.errored():
            self.report_error(err)

        if self._healing:
            self._apply_pending_state_dict()

        enough_replicas = self.num_participants() >= self._min_replica_size
        local_should_commit = enough_replicas and self._errored is None
        self._trace.record(
            "vote_send",
            step=self._step,
            quorum_id=self._quorum_id,
            vote=local_should_commit,
            enough_replicas=enough_replicas,
            errored=self._errored is not None,
        )
        barrier_t0 = time.perf_counter()
        with tracing.phase(
            "should_commit", self._trace, self._metric_labels,
            step=self._step, quorum_id=self._quorum_id,
            vote=local_should_commit,
        ):
            should_commit = self._client.should_commit(
                self._group_rank,
                self._step,
                local_should_commit,
                timeout=timeout or self._timeout,
            )
        # The barrier releases every local rank together, so the rank that
        # entered LAST waited LEAST — fleet_status derives its STRAGGLER/
        # LAG column from this gauge across the pushed snapshots, and
        # fleet_trace uses the barrier-release instant as its fine clock
        # anchor.
        metrics.set_gauge(
            "tpuft_trace_barrier_wait_seconds",
            time.perf_counter() - barrier_t0,
            **self._metric_labels,
        )
        self._logger.info(
            f"should_commit={should_commit} enough_replicas={enough_replicas}, "
            f"errored={self._errored}"
        )
        commits_logger.info(
            "commit",
            extra={
                "job_id": os.environ.get("JOB_ID", "unknown"),
                "replica_id": self._replica_id,
                "rank": self._group_rank,
                "quorum_id": self._quorum_id,
                "step": self._step,
                "commit_result": should_commit,
            },
        )

        self._checkpoint_transport.disallow_checkpoint()

        if should_commit:
            self._trace.record(
                "commit", step=self._step, quorum_id=self._quorum_id
            )
            self._step += 1
            self._batches_committed += self.num_participants()
            self._commit_failures = 0
            # History-ring accounting for this committed step (cheap
            # ints, never a state sample): the state half arrives from
            # the optimizer's promotion at adoption.
            self._history.note_accounting(self._step, self._batches_committed)
            metrics.inc("tpuft_commits_total", **self._metric_labels)
            metrics.set_gauge(
                "tpuft_last_commit_time", time.time(), **self._metric_labels
            )
            self._run_publish_hooks(self._step, self._quorum_id)
            # A committed step closes any open incident window: later dumps
            # get fresh ids instead of riding a resolved incident.
            tracing.clear_incident(self._trace)
        else:
            self._commit_failures += 1
            metrics.inc("tpuft_commit_failures_total", **self._metric_labels)
            self._trace.record(
                "commit_failed",
                step=self._step,
                quorum_id=self._quorum_id,
                consecutive_failures=self._commit_failures,
            )
        self._trace.set_step(self._step, self._quorum_id)
        metrics.set_gauge("tpuft_step", self._step, **self._metric_labels)
        metrics.set_gauge(
            "tpuft_batches_committed", self._batches_committed, **self._metric_labels
        )
        self._push_metrics()
        if self._health is not None:
            # One health-scoring window per commit resolution (cheap,
            # never raises): watchdog beat, rollup ingest, board
            # push/pull, verdict latching. Actuation waits for the next
            # start_quorum — the step boundary.
            self._health.on_step(
                self._step,
                committed=should_commit,
                participants=self._participating_replica_world_size,
            )
        if not should_commit:
            if self._max_retries is not None and self._commit_failures > self._max_retries:
                msg = (
                    f"should_commit failed {self._commit_failures} times consecutively, "
                    f"exceeding max_retries={self._max_retries}"
                )
                self._logger.exception(msg)
                raise RuntimeError(msg)
        return should_commit

    # ------------------------------------------------------------------
    # speculative commits (the depth-N pipelined window)
    # ------------------------------------------------------------------

    def speculative_commit_async(
        self, claimed_step: int, timeout: Optional[float] = None
    ) -> _SpeculativeCommitFuture:
        """Commit-barrier vote for the speculative step ``claimed_step``
        (committed step + window offset) — the depth>=2 / adaptive vote
        path of the pipelined commit schedule.

        Split-phase ``should_commit``: the LOCAL phase (pg error read,
        pending-heal apply, vote computation) runs here on the caller
        thread, so the vote reflects exactly this step's error/heal flags
        before the next ``start_quorum`` wipes them — the property
        ``_drain_pending_commit`` enforces by blocking on the depth<=1
        path. The barrier RPC rides the commit pool so every window
        slot's vote overlaps on the wire, and the step/batch accounting
        defers to the first ``result()`` delivery (the pipelined
        optimizer resolves oldest-first, keeping accounting in step
        order; see :class:`_SpeculativeCommitFuture`).
        ``should_commit_async`` remains the depth<=1 path: its
        quorum-executor FIFO ordering is what the depth-1 tests pin."""
        lockcheck.check_barrier("Manager.speculative_commit_async")
        if err := self._pg.errored():
            self.report_error(err)
        if self._healing:
            self._apply_pending_state_dict()
        participants = self.num_participants()
        enough_replicas = participants >= self._min_replica_size
        local_should_commit = enough_replicas and self._errored is None
        self._trace.record(
            "vote_send",
            step=claimed_step,
            quorum_id=self._quorum_id,
            vote=local_should_commit,
            enough_replicas=enough_replicas,
            errored=self._errored is not None,
            speculative=True,
        )
        inner = self._commit_executor().submit(
            self._speculative_barrier, claimed_step, local_should_commit, timeout
        )
        return _SpeculativeCommitFuture(
            self, inner, claimed_step, local_should_commit, participants
        )

    def _commit_executor(self) -> concurrent.futures.ThreadPoolExecutor:
        with self._commit_pool_lock:
            if self._commit_pool is None:
                depth_bound = (
                    self._adaptive_max_depth
                    if self._commit_pipeline_adaptive
                    else self._commit_pipeline_depth
                )
                self._commit_pool = concurrent.futures.ThreadPoolExecutor(
                    max_workers=max(2, min(int(depth_bound), 16)),
                    thread_name_prefix="tpuft_commit",
                )
            return self._commit_pool

    def _speculative_barrier(
        self, step: int, vote: bool, timeout: Optional[float]
    ) -> bool:
        """The barrier RPC leg of one speculative vote (commit-pool
        thread). Also the adaptive controller's RTT sensor: measured here
        the barrier round trip is UNHIDDEN, unlike the stall the train
        thread observes once the window covers it."""
        barrier_t0 = time.perf_counter()
        try:
            with tracing.phase(
                "speculative_commit", self._trace, self._metric_labels,
                step=step, quorum_id=self._quorum_id, vote=vote,
            ):
                return self._client.should_commit(
                    self._group_rank, step, vote, timeout=timeout or self._timeout
                )
        finally:
            elapsed = time.perf_counter() - barrier_t0
            self._barrier_rtt_ewma = self._ewma(self._barrier_rtt_ewma, elapsed)
            metrics.set_gauge(
                "tpuft_trace_barrier_wait_seconds", elapsed, **self._metric_labels
            )

    def _speculative_commit_resolved(
        self, step: int, should_commit: bool, participants: int
    ) -> None:
        """Deferred accounting tail of one speculative vote (mirrors
        :meth:`should_commit`'s inline tail), applied in window order on
        the consuming thread. ``participants`` was captured at vote
        launch — re-reading it here could block on the CURRENT quorum
        future from the quorum thread itself (the drain hook runs inside
        ``_async_quorum``)."""
        self._logger.info(
            f"speculative should_commit={should_commit} step={step} "
            f"errored={self._errored}"
        )
        commits_logger.info(
            "commit",
            extra={
                "job_id": os.environ.get("JOB_ID", "unknown"),
                "replica_id": self._replica_id,
                "rank": self._group_rank,
                "quorum_id": self._quorum_id,
                "step": step,
                "commit_result": should_commit,
            },
        )
        self._checkpoint_transport.disallow_checkpoint()
        if should_commit:
            self._trace.record("commit", step=step, quorum_id=self._quorum_id)
            if step != self._step:
                # Resolution is oldest-first by construction; a mismatch
                # means the owner broke window order — keep accounting
                # monotone and loud rather than silently double-counting.
                self._logger.warn(
                    f"speculative commit for step {step} resolved at "
                    f"committed step {self._step} (window order violated?)"
                )
            self._step = max(self._step, step + 1)
            self._batches_committed += participants
            self._commit_failures = 0
            self._history.note_accounting(self._step, self._batches_committed)
            metrics.inc("tpuft_commits_total", **self._metric_labels)
            metrics.set_gauge(
                "tpuft_last_commit_time", time.time(), **self._metric_labels
            )
            self._run_publish_hooks(self._step, self._quorum_id)
            tracing.clear_incident(self._trace)
        else:
            self._commit_failures += 1
            metrics.inc("tpuft_commit_failures_total", **self._metric_labels)
            self._trace.record(
                "commit_failed",
                step=step,
                quorum_id=self._quorum_id,
                consecutive_failures=self._commit_failures,
            )
        self._trace.set_step(self._step, self._quorum_id)
        metrics.set_gauge("tpuft_step", self._step, **self._metric_labels)
        metrics.set_gauge(
            "tpuft_batches_committed", self._batches_committed, **self._metric_labels
        )
        self._push_metrics()
        if self._health is not None:
            # Same per-resolution health window as the inline tail;
            # participants were captured at vote launch (re-reading here
            # could block on the current quorum future).
            self._health.on_step(
                self._step, committed=should_commit, participants=participants
            )
        if not should_commit:
            if self._max_retries is not None and self._commit_failures > self._max_retries:
                msg = (
                    f"should_commit failed {self._commit_failures} times consecutively, "
                    f"exceeding max_retries={self._max_retries}"
                )
                self._logger.exception(msg)
                raise RuntimeError(msg)

    # ------------------------------------------------------------------
    # metrics push (the fleet-table feed)
    # ------------------------------------------------------------------

    def _push_metrics(self, force: bool = False) -> None:
        """Publishes this process's metrics snapshot into the group store
        under ``metrics/<replica_id>/<group_rank>`` (rate-limited by
        ``$TPUFT_METRICS_PUSH_SEC``). The replica id key is the FULL id
        (uuid included) — exactly what the lighthouse status reports for
        this group — so ``scripts/fleet_status.py`` can join lighthouse
        members to their snapshots without a key-listing RPC the store
        does not have. Best-effort: a push failure never poisons a step."""
        interval = self._metrics_push_interval
        if interval <= 0:
            return
        now = time.monotonic()
        if not force and now - self._metrics_last_push < interval:
            return
        self._metrics_last_push = now
        try:
            payload = json.dumps(
                {
                    "ts": time.time(),
                    "replica_id": self._replica_id,
                    "group_rank": self._group_rank,
                    "step": self._step,
                    "batches_committed": self._batches_committed,
                    "healing": self._healing,
                    # WAN topology: this replica's region (None without a
                    # configured topology) — feeds fleet_status's REGION
                    # column; a string, so it rides the snapshot top level
                    # rather than the numeric metrics registry.
                    "region": netem.local_region(),
                    "metrics": metrics.snapshot(),
                    # Goodput accounting: closing a due ledger window here
                    # also scores the SLO — both ride this push cadence.
                    "goodput": self._goodput.collect(
                        step=self._step, quorum_id=self._quorum_id
                    ),
                }
            ).encode()
            self._store.set(
                f"metrics/{self._replica_id}/{self._group_rank}", payload
            )
        except Exception as e:  # noqa: BLE001 — observability must not wound
            self._logger.warn(f"metrics push failed (ignored): {e}")
        self._push_trace()

    def _push_trace(self) -> None:
        """Publishes this process's journal segment (events since the last
        push) plus its per-step phase rollup into the group store under
        ``trace/<replica_id>/<group_rank>``, and runs one clock-beacon
        sampling round — both riding the metrics-push cadence. The rollup
        feeds fleet_status's STRAGGLER/LAG column; the segments (and the
        fuller ``/trace.json`` surface) feed scripts/fleet_trace.py.
        Best-effort: a push failure never poisons a step."""
        try:
            segment = self._trace.drain_segment()
            payload = json.dumps(
                {
                    "ts": time.time(),
                    "replica_id": self._replica_id,
                    "group_rank": self._group_rank,
                    "job_id": self._trace.job_id,
                    "wall": time.time(),
                    "mono": time.monotonic(),
                    "clock_offset_s": self._trace.clock_offset_s,
                    "events": segment,
                    "phases": self._trace.phase_rollup(),
                }
            ).encode()
            self._store.set(
                f"{tracing.STORE_PREFIX}/{self._replica_id}/{self._group_rank}",
                payload,
            )
            self._trace_clock.tick(self._store)
        except Exception as e:  # noqa: BLE001 — observability must not wound
            self._logger.warn(f"trace push failed (ignored): {e}")

    # ------------------------------------------------------------------
    # state dict / accounting
    # ------------------------------------------------------------------

    def load_state_dict(self, state_dict: Dict[str, int]) -> None:
        self._step = state_dict["step"]
        self._batches_committed = state_dict["batches_committed"]
        # A checkpoint restore rewrote the step counter: resident history
        # entries' step labels no longer describe this trajectory.
        self._history.clear()

    def _manager_state_dict(self) -> Dict[str, Any]:
        with self._state_dict_lock.r_lock(timeout=self._timeout):
            assert self._user_state_dicts, "user state_dict is not initialized"
            return {
                "user": {key: fn() for key, fn in self._user_state_dicts.items()},
                "tpuft": self.state_dict(),
            }

    def state_dict(self) -> Dict[str, int]:
        """Manager accounting for user checkpoints: persist alongside model
        state and restore via :meth:`load_state_dict`."""
        return {"step": self._step, "batches_committed": self._batches_committed}

    def current_step(self) -> int:
        return self._step

    def batches_committed(self) -> int:
        return self._batches_committed

    def participating_rank(self) -> Optional[int]:
        if self._quorum_future is None:
            return None
        self.wait_quorum()
        return self._participating_replica_rank

    def num_participants(self) -> int:
        if self._quorum_future is None:
            return 0
        self.wait_quorum()
        assert self._participating_replica_world_size >= 0, "internal error"
        return self._participating_replica_world_size

    def is_lone_replica(self) -> bool:
        """True when this replica is ALONE on the wire for the current
        quorum: sole participant AND a process-group world of one. Then
        every averaging collective is an exact identity (SUM over one,
        divided by one) and may skip the stage/wire round trip.

        Both conditions matter: a healing joiner is a PG member without
        being a participant, and if the survivor skipped the wire while the
        joiner entered the collective, the joiner would average with nobody
        and replica states would diverge (caught by the kill-recovery
        bitwise-equality integ tests)."""
        return (
            self.num_participants() == 1
            and self.is_participating()
            and self._pg.size() <= 1
        )

    def is_participating(self) -> bool:
        if self._participating_replica_rank is None:
            return False
        if self._healing:
            assert self._use_async_quorum
            return False
        return True


class _ManagerLogger:
    def __init__(self, manager: Manager, replica_id: str, group_rank: int) -> None:
        self._logger = logging.getLogger("torchft_tpu.manager")
        self._replica_id = replica_id
        self._group_rank = group_rank
        self._manager = manager

    def _prefix(self) -> str:
        return f"[{self._replica_id}/{self._group_rank} - step {self._manager.current_step()}]"

    def info(self, msg: str) -> None:
        self._logger.info(f"{self._prefix()} {msg}")

    def warn(self, msg: str) -> None:
        self._logger.warning(f"{self._prefix()} {msg}")

    def exception(self, msg: str) -> None:
        self._logger.exception(f"{self._prefix()} {msg}")
