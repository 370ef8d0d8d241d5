"""Fleet trace plane: per-process step-event journals that merge into one
causally ordered cross-replica timeline.

The other observability surfaces are *per-process*: metrics count
(metrics.py), telemetry narrates single records (telemetry.py), the flight
recorder rings raw PG events (utils/flight_recorder.py), chrome spans show
one process's overlap (utils/profiling.py). None of them answers "who
stalled step N's commit barrier?" across a fleet with unsynchronized wall
clocks. This module adds the missing layer:

- :class:`TraceJournal` — a bounded ring of structured span/instant events,
  one journal per process (threads-as-replicas tests get one per replica
  thread via :func:`use_journal`). Every FT phase records here: quorum
  begin/end, ``pg.configure``, update dispatch, device sync, vote
  send/resolve, commit/rollback, heal chunk progress, serve-child
  lifecycle, ZeRO re-balance. Each event carries the full causal tuple
  ``(job_id, replica_id, group_rank, step, quorum_id, seq, t_mono,
  t_wall)`` — ``(step, quorum_id, seq)`` is the hybrid logical clock that
  keeps merged timelines causally ordered even when wall clocks drift.
- clock alignment — :class:`StoreClockSampler` samples a coarse wall-clock
  offset against a store-mediated beacon key (``trace/clockref``), riding
  the metrics-push cadence; precision is bounded by that cadence, so it
  catches *gross* skew (unsynced hosts seconds/minutes apart). Fine
  alignment happens at merge time from barrier-simultaneity anchors
  (``scripts/fleet_trace.py``): every participant's commit-barrier release
  is quorum-wide simultaneous within RPC fanout skew.
- fleet collection — each Manager pushes journal segments to its group
  store at ``trace/<replica_id>/<group_rank>`` (same cadence as the
  metrics push) and every metrics HTTP surface serves the full ring as
  ``GET /trace.json``.
- incident auto-capture — :func:`open_incident` stamps a *deterministic*
  incident id (pure function of kind/step/quorum_id, so every process
  observing the same quorum-wide event derives the same id with zero
  coordination) and dumps journal + flight-recorder ring under
  ``$TPUFT_FLIGHT_RECORDER``. Triggers: rollback, quorum timeout,
  ``HealExhaustedError``.

Recording is always on (a dict build + deque append per event — the
per-event cost is pinned by a unit test); ``TPUFT_TRACE=0`` disables it.
The ring holds ``TPUFT_TRACE_SIZE`` events (default 8192).
``TPUFT_TRACE_CLOCK=0`` disables the store beacon sampling.

Journal recording NEVER takes the state-dict lock — recording sites are
plain deque appends, safe inside any phase including the commit barrier
(the R3 lock-discipline fixtures pin the pattern).

Step-path phases are recorded through ONE primitive, :func:`phase`: a
context manager that reads the clock once at entry and once at exit and
feeds the three sinks the :data:`PHASES` table names for it — the
histogram (always), this journal (when enabled) and a
``jax.profiler.TraceAnnotation`` (a no-op unless a profiler session is
running). :func:`start_capture` / :func:`stop_capture` start and stop such
a session from inside the process that holds the chip and hand back what
the journal and the metrics registry recorded meanwhile, with two clock
anchors that lay journal instants onto the profiler's timeline.
docs/observability.md has the span tree.
"""

from __future__ import annotations

import glob
import itertools
import json
import logging
import os
import threading
import time
import warnings
from contextlib import contextmanager
from typing import (
    Any, Callable, Deque, Dict, Generator, List, NamedTuple, Optional,
)

import collections

from torchft_tpu import metrics

__all__ = [
    "TraceJournal",
    "StoreClockSampler",
    "ENV_TRACE",
    "ENV_SIZE",
    "ENV_CLOCK",
    "CLOCK_REF_KEY",
    "STORE_PREFIX",
    "default",
    "current",
    "use_journal",
    "configure",
    "set_step",
    "record",
    "span",
    "incident_id",
    "open_incident",
    "active_incident",
    "clear_incident",
    "trace_json_payload",
    "PHASES",
    "phase",
    "record_phase",
    "annotation",
    "start_capture",
    "stop_capture",
    "install_compile_listener",
]

ENV_TRACE = "TPUFT_TRACE"
ENV_SIZE = "TPUFT_TRACE_SIZE"
ENV_CLOCK = "TPUFT_TRACE_CLOCK"

# Well-known store keys: the beacon every process samples against, and the
# per-process segment keys fleet_status/fleet_trace read.
CLOCK_REF_KEY = "trace/clockref"
STORE_PREFIX = "trace"


def _enabled_from_env() -> bool:
    return os.environ.get(ENV_TRACE, "1") != "0"


def _ring_size() -> int:
    try:
        return max(64, int(os.environ.get(ENV_SIZE, "8192")))
    except ValueError:
        return 8192  # malformed env must not break package import


def _jsonable(value: Any) -> Any:
    if isinstance(value, (str, int, float, bool, type(None))):
        return value
    try:
        return repr(value)
    except Exception:  # pathological __repr__
        return f"<unreprable {type(value).__name__}>"


class TraceJournal:
    """Bounded per-process journal of structured FT-phase events.

    Thread-safe the cheap way: deque appends are atomic, ``seq`` comes from
    ``itertools.count`` (atomic in CPython), and identity/step fields are
    plain attribute reads — races on them only mislabel an event's step by
    one, which the merge's hybrid logical clock tolerates. ``wall``/``mono``
    are injectable so tests can skew clocks per journal and prove the
    alignment machinery recovers them.
    """

    def __init__(
        self,
        maxlen: Optional[int] = None,
        wall: Callable[[], float] = time.time,
        mono: Callable[[], float] = time.monotonic,
        enabled: Optional[bool] = None,
    ) -> None:
        self._ring: Deque[Dict[str, Any]] = collections.deque(
            maxlen=maxlen or _ring_size()
        )
        self._seq = itertools.count()
        self._last_seq = -1  # last seq handed out (for drop accounting)
        self._last_drained = -1
        self._wall = wall
        self._mono = mono
        self._enabled = _enabled_from_env() if enabled is None else enabled
        # Identity (stamped onto events at export, not per append).
        self.job_id = "unknown"
        self.replica_id = "proc"
        self.group_rank = 0
        # Hybrid-logical-clock context, maintained by the Manager.
        self.step = 0
        self.quorum_id = -1
        # Last coarse store-sampled clock offset (seconds, my_wall - ref_wall).
        self.clock_offset_s: Optional[float] = None
        self.active_incident: Optional[str] = None

    # -- configuration ------------------------------------------------------

    def configure(
        self,
        job_id: Optional[str] = None,
        replica_id: Optional[str] = None,
        group_rank: Optional[int] = None,
    ) -> None:
        if job_id is not None:
            self.job_id = str(job_id)
        if replica_id is not None:
            self.replica_id = str(replica_id)
        if group_rank is not None:
            self.group_rank = int(group_rank)

    def set_step(
        self, step: Optional[int] = None, quorum_id: Optional[int] = None
    ) -> None:
        if step is not None:
            self.step = int(step)
        if quorum_id is not None:
            self.quorum_id = int(quorum_id)

    @property
    def enabled(self) -> bool:
        return self._enabled

    def set_enabled(self, enabled: bool) -> None:
        """Switches recording from inside the process (``TPUFT_TRACE`` only
        decides the value a journal starts with)."""
        self._enabled = bool(enabled)

    # -- recording ----------------------------------------------------------

    def record(
        self,
        name: str,
        ph: str = "i",
        cat: str = "ft",
        dur: Optional[float] = None,
        step: Optional[int] = None,
        quorum_id: Optional[int] = None,
        t_wall: Optional[float] = None,
        t_mono: Optional[float] = None,
        **args: Any,
    ) -> None:
        """Appends one event. ``ph`` is chrome-trace-flavored: ``"X"`` a
        complete span (``dur`` seconds; stamps default to *start* = now -
        dur), ``"i"`` an instant. Never raises — recording sites include
        failure paths that must stay clean."""
        if not self._enabled:
            return
        try:
            seq = next(self._seq)
            self._last_seq = seq
            back = dur or 0.0
            event: Dict[str, Any] = {
                "seq": seq,
                "name": name,
                "ph": ph,
                "cat": cat,
                "t_wall": self._wall() - back if t_wall is None else t_wall,
                "t_mono": self._mono() - back if t_mono is None else t_mono,
                "thread": threading.current_thread().name,
                "step": self.step if step is None else step,
                "quorum_id": self.quorum_id if quorum_id is None else quorum_id,
            }
            if dur is not None:
                event["dur"] = dur
            if args:
                event["args"] = {k: _jsonable(v) for k, v in args.items()}
            self._ring.append(event)
        except Exception:  # noqa: BLE001 — observability must not wound
            pass

    @contextmanager
    def span(
        self,
        name: str,
        cat: str = "ft",
        step: Optional[int] = None,
        quorum_id: Optional[int] = None,
        **args: Any,
    ) -> Generator[None, None, None]:
        """Times the with-body into one ``"X"`` event (recorded at exit
        with the *start* timestamps, so merged timelines sort by entry)."""
        if not self._enabled:
            yield
            return
        start_wall = self._wall()
        start_mono = self._mono()
        try:
            yield
        finally:
            self.record(
                name,
                ph="X",
                cat=cat,
                dur=self._mono() - start_mono,
                step=step,
                quorum_id=quorum_id,
                t_wall=start_wall,
                t_mono=start_mono,
                **args,
            )

    # -- export -------------------------------------------------------------

    def _identity(self) -> Dict[str, Any]:
        return {
            "job_id": self.job_id,
            "replica_id": self.replica_id,
            "group_rank": self.group_rank,
        }

    def _copy_ring(self) -> List[Dict[str, Any]]:
        # Deque appends are atomic but iteration can race a concurrent
        # append; retry then fall back to an index walk (flight-recorder
        # pattern — a slightly short sample is fine for observability).
        for _ in range(4):
            try:
                return list(self._ring)
            except RuntimeError:
                continue
        out: List[Dict[str, Any]] = []
        for i in range(len(self._ring)):
            try:
                out.append(self._ring[i])
            except IndexError:
                break
        return out

    def snapshot(self) -> List[Dict[str, Any]]:
        """Identity-stamped copies of every ring event (oldest first)."""
        ident = self._identity()
        return [{**ident, **event} for event in self._copy_ring()]

    def dropped(self) -> int:
        """Events overwritten by the ring bound so far."""
        maxlen = self._ring.maxlen or 0
        return max(0, (self._last_seq + 1) - maxlen) if maxlen else 0

    def drain_segment(self) -> List[Dict[str, Any]]:
        """Events recorded since the last drain (identity-stamped) — the
        incremental store-push payload. Counts exported events and any that
        fell off the ring un-exported into the ``tpuft_trace_*`` metrics."""
        ring = self._copy_ring()
        previous = self._last_drained
        segment = [e for e in ring if e["seq"] > previous]
        if segment:
            self._last_drained = segment[-1]["seq"]
        # Events that fell off the ring BEFORE any drain exported them
        # (ring-bound drops of already-drained events are benign — they
        # were pushed).
        oldest = ring[0]["seq"] if ring else self._last_seq + 1
        missed = max(0, oldest - (previous + 1))
        if missed > 0:
            metrics.inc("tpuft_trace_dropped_total", missed)
        if segment:
            metrics.inc("tpuft_trace_events_total", len(segment))
        ident = self._identity()
        return [{**ident, **event} for event in segment]

    def phase_rollup(self, max_steps: int = 4) -> List[Dict[str, Any]]:
        """Per-step phase durations from the ring (latest ``max_steps``
        steps): ``{"step", "quorum_id", "phases": {span: seconds},
        "committed": bool|None}``. Durations are *local monotonic* — clock-
        free, so fleet_status can compare them across replicas directly
        (the straggler entered the commit barrier last and therefore
        waited in it least)."""
        by_step: Dict[int, Dict[str, Any]] = {}
        for event in self._copy_ring():
            step = event.get("step")
            if step is None:
                continue
            name = event.get("name")
            slot = by_step.setdefault(
                step,
                {"step": step, "quorum_id": event.get("quorum_id"),
                 "phases": {}, "committed": None},
            )
            if event.get("ph") == "X" and name in ROLLUP_SPANS:
                slot["phases"][name] = round(
                    slot["phases"].get(name, 0.0) + float(event.get("dur", 0.0)), 6
                )
                slot["quorum_id"] = event.get("quorum_id", slot["quorum_id"])
            elif name == "commit":
                slot["committed"] = True
            elif name == "commit_failed":
                slot["committed"] = False
        steps = sorted(by_step)[-max_steps:]
        return [by_step[s] for s in steps]

    # -- dump ---------------------------------------------------------------

    def dump(self, path: Optional[str] = None, reason: str = "") -> Optional[str]:
        """Writes the ring as JSON lines (header first). With no ``path``,
        uses ``$TPUFT_FLIGHT_RECORDER/tpuft_trace_<replica>_<rank>_<pid>_
        <ns>[_<incident>].jsonl`` — or returns None when the env is unset.
        Atomic (tmp + replace): a chaos kill mid-dump must never leave a
        truncated JSONL at the final name."""
        if path is None:
            directory = os.environ.get("TPUFT_FLIGHT_RECORDER", "")
            if not directory:
                return None
            os.makedirs(directory, exist_ok=True)
            suffix = f"_{self.active_incident}" if self.active_incident else ""
            path = os.path.join(
                directory,
                f"tpuft_trace_{sanitize(self.replica_id)}_{self.group_rank}"
                f"_{os.getpid()}_{time.time_ns()}{suffix}.jsonl",
            )
        header = {
            "trace_header": True,
            **self._identity(),
            "reason": reason,
            "incident": self.active_incident,
            "wall": self._wall(),
            "mono": self._mono(),
            "clock_offset_s": self.clock_offset_s,
            "dropped": self.dropped(),
        }
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(json.dumps(header) + "\n")
            for event in self.snapshot():
                f.write(json.dumps(event) + "\n")
        os.replace(tmp, path)
        return path


def sanitize(name: str) -> str:
    """Filesystem-safe identity fragment for dump filenames."""
    return (
        "".join(c if (c.isalnum() or c in "-._") else "-" for c in str(name))
        or "proc"
    )


# ---------------------------------------------------------------------------
# process default + per-thread journals (threads-as-replicas drills)
# ---------------------------------------------------------------------------

_PROCESS = TraceJournal()
_TLS = threading.local()


def default() -> TraceJournal:
    """The process-wide journal (one real deployment process = one rank)."""
    return _PROCESS


def current() -> TraceJournal:
    """The journal for this thread: a :func:`use_journal` override when one
    is active (threads-as-replicas tests give each replica thread its own
    journal), else the process journal. A Manager captures ``current()`` at
    construction, so events it records from its quorum thread still land in
    its replica's journal."""
    return getattr(_TLS, "journal", None) or _PROCESS


@contextmanager
def use_journal(journal: TraceJournal) -> Generator[TraceJournal, None, None]:
    previous = getattr(_TLS, "journal", None)
    _TLS.journal = journal
    try:
        yield journal
    finally:
        _TLS.journal = previous


def configure(**kwargs: Any) -> None:
    current().configure(**kwargs)


def set_step(step: Optional[int] = None, quorum_id: Optional[int] = None) -> None:
    current().set_step(step, quorum_id)


def record(name: str, **kwargs: Any) -> None:
    current().record(name, **kwargs)


def span(name: str, **kwargs: Any):
    return current().span(name, **kwargs)


# ---------------------------------------------------------------------------
# phases: one recording site, three sinks
# ---------------------------------------------------------------------------


class _PhaseSpec(NamedTuple):
    """Where one phase lands: its journal event, its profiler annotation and
    its histogram (``None``: that sink has no entry for it). ``stage`` is
    the histogram's ``stage`` label where several phases share one
    histogram; ``root`` makes the annotation a ``StepTraceAnnotation``
    numbered by the ``step`` id, so the profiler's own step view works;
    ``rollup`` puts the journal event into the per-step phase rollup (the
    STRAGGLER/LAG feed of scripts/fleet_status.py and ``--explain-step``'s
    phase deltas)."""

    journal: Optional[str]
    annotation: Optional[str]
    histogram: Optional[str] = None
    stage: Optional[str] = None
    root: bool = False
    rollup: bool = False


def _outer(stage: str) -> _PhaseSpec:
    return _PhaseSpec(
        f"sync_{stage}", f"tpuft::local_sgd::{stage}",
        histogram="tpuft_outer_sync_seconds", stage=stage,
    )


def _wire(stage: str, journal: Optional[str] = None) -> _PhaseSpec:
    return _PhaseSpec(
        journal or f"wire_{stage}", f"tpuft::wire::{stage}",
        histogram="tpuft_wire_stage_seconds", stage=stage,
    )


# Every step-path phase, by the key its recording site passes to
# :func:`phase`. The names in all three sinks are read elsewhere, letter for
# letter: the journal's by goodput.fold_events, the health scorer's rollup
# and scripts/fleet_trace.py; the annotations' by the benchmark's trace
# reduction (idle gaps by host span); the histograms' by METRICS.md (rule
# R8 reads the ``histogram=`` entries here as emission sites).
PHASES: Dict[str, _PhaseSpec] = {
    # control plane (manager.py)
    "quorum": _PhaseSpec(
        "quorum", "tpuft::manager::_client::_quorum",
        histogram="tpuft_quorum_seconds", rollup=True,
    ),
    "start_quorum": _PhaseSpec("start_quorum", "tpuft::manager::start_quorum"),
    # The caller's wait for the quorum thread: with it every child of a
    # step's root is in the journal, so the root's self time is computable
    # from the events alone. No reader of ``quorum`` reads this name.
    "wait_quorum": _PhaseSpec("wait_quorum", "tpuft::manager::wait_quorum"),
    "pg_configure": _PhaseSpec(
        "pg_configure", "tpuft::manager::_pg::configure",
        histogram="tpuft_pg_configure_seconds", rollup=True,
    ),
    "should_commit": _PhaseSpec(
        "commit_barrier", "tpuft::manager::should_commit",
        histogram="tpuft_commit_barrier_seconds", rollup=True,
    ),
    "speculative_commit": _PhaseSpec(
        "commit_barrier", "tpuft::manager::speculative_commit",
        histogram="tpuft_commit_barrier_seconds", rollup=True,
    ),
    # ``should_commit_async``'s hand-over of the vote to the executor, on the
    # caller's thread (the vote itself is ``should_commit``, on the executor).
    "commit_submit": _PhaseSpec("commit_submit", "tpuft::manager::commit_submit"),
    "allreduce": _PhaseSpec(None, "tpuft::manager::allreduce"),
    "allreduce_pytree": _PhaseSpec(None, "tpuft::manager::allreduce_pytree"),
    "allreduce_prequantized": _PhaseSpec(
        None, "tpuft::manager::allreduce_prequantized"
    ),
    # FT-DDP step protocol (optim.py)
    "optim_step": _PhaseSpec("step", "tpuft::optim::step", root=True),
    "device_sync": _PhaseSpec(
        "device_sync", "tpuft::optim::device_sync",
        histogram="tpuft_device_sync_seconds", rollup=True,
    ),
    "update_dispatch": _PhaseSpec(
        "update_dispatch", "tpuft::optim::update_dispatch",
        histogram="tpuft_update_dispatch_seconds", rollup=True,
    ),
    "commit_wait": _PhaseSpec("commit_wait", "tpuft::optim::commit_wait"),
    "adopt": _PhaseSpec("adopt", "tpuft::optim::adopt"),
    # ``adopt``'s children, opened where the work happens: the write-locked
    # assignment of the new state (a wait for a reader of the state dict
    # shows here), the hand-over to the history ring, and inside it the
    # ring's release of the versions that leave (history.py; only when one
    # does).
    "state_swap": _PhaseSpec("state_swap", "tpuft::optim::state_swap"),
    "history_promote": _PhaseSpec("history_promote", "tpuft::history::promote"),
    "history_evict": _PhaseSpec("history_evict", "tpuft::history::evict"),
    "pipeline_drain": _PhaseSpec(
        "pipeline_drain", "tpuft::optim::pipeline_drain", rollup=True
    ),
    # streaming DiLoCo / LocalSGD (local_sgd.py)
    "local_sgd_step": _PhaseSpec("step", "tpuft::local_sgd::step", root=True),
    "inner_dispatch": _PhaseSpec(
        "inner_dispatch", "tpuft::local_sgd::inner_dispatch"
    ),
    "prepare_sync": _PhaseSpec("prepare_sync", "tpuft::local_sgd::prepare_sync"),
    "perform_sync": _PhaseSpec("perform_sync", "tpuft::local_sgd::perform_sync"),
    "sync_quantize": _outer("quantize"),
    "sync_launch": _outer("launch"),
    "sync_wait": _outer("wait"),
    "sync_restore": _outer("restore"),
    "sync_commit": _outer("commit"),
    "sync_apply_outer": _outer("apply_outer"),
    # replica-axis wire (parallel/mesh.py, Manager.allreduce_pytree)
    "wire_stage": _wire("stage"),
    # ``wire_bucket`` is ddp.py's per-bucket wire time in the journal: the
    # concatenation into buckets is ``wire_concat`` there.
    "wire_bucket": _wire("bucket", journal="wire_concat"),
    "wire_ring": _wire("ring"),
    "wire_average": _wire("average"),
    "wire_scatter": _wire("scatter"),
    # Journal-only rows: the heal and ZeRO planes keep their annotation and
    # histogram on ``utils.profiling.trace_span`` / ``metrics.timer`` beside
    # the phase, and ddp.py / collectives.py record ``wire_bucket`` after the
    # fact from their own clock pair around ``work.wait()``. They stand here
    # so that ONE table names every span the rollup sums.
    "heal_send": _PhaseSpec("heal_send", None, rollup=True),
    "heal_recv": _PhaseSpec("heal_recv", None, rollup=True),
    "zero_rebalance": _PhaseSpec("zero_rebalance", None, rollup=True),
    "bucket_wire": _PhaseSpec("wire_bucket", None, rollup=True),
}

# Journal span names the per-step phase rollup sums.
ROLLUP_SPANS = frozenset(spec.journal for spec in PHASES.values() if spec.rollup)

# Fields of a phase that identify WHICH step, quorum or fragment it belongs
# to: they go to the journal event and, as keyword arguments, to the
# annotation, so that the spans of one step share an identifier on the
# device trace too (the profiler stores them as the event's stats; the
# event keeps its bare name). Any other field is a journal argument only.
_ID_FIELDS = ("step", "quorum_id", "fragment")

_span_logger = logging.getLogger("torchft_tpu.trace")
# Operator's knob (docs/protocol.md): log every annotated span's wall time.
_LOG_SPANS = os.environ.get("TPUFT_TRACE_LOG", "") == "1"
# The active utils.profiling.chrome_trace capture, or None; it owns the
# event list, this module only hands it finished spans.
_chrome_capture: Any = None
# (TraceAnnotation, StepTraceAnnotation) once jax.profiler is imported;
# False where it cannot be.
_annotation_types: Any = None


def _annotations() -> Any:
    global _annotation_types
    if _annotation_types is None:
        try:
            import jax.profiler

            _annotation_types = (
                jax.profiler.TraceAnnotation,
                jax.profiler.StepTraceAnnotation,
            )
        except Exception:  # noqa: BLE001 — profiling must never break training
            _annotation_types = False
    return _annotation_types


def _feed(
    spec: _PhaseSpec, journal: Optional[TraceJournal],
    labels: Optional[Dict[str, Any]], ids: Dict[str, Any],
    args: Dict[str, Any], start: float, dur: float,
) -> None:
    """One finished phase into its histogram and its journal."""
    if spec.histogram is not None:
        stage = {"stage": spec.stage} if spec.stage is not None else {}
        metrics.observe(spec.histogram, dur, **stage, **(labels or {}))
    if journal is not None and spec.journal is not None and journal.enabled:
        # step and quorum_id are the event's own fields (None: the journal's
        # current ones); any other id is an argument of the event.
        ids = dict(ids)
        journal.record(
            spec.journal, ph="X", dur=dur, t_mono=start,
            step=ids.pop("step", None), quorum_id=ids.pop("quorum_id", None),
            **ids, **args,
        )


class _Span:
    """The with-block behind :func:`phase`: one clock read at entry, one at
    exit, and the exit feeds every sink the spec names. Never swallows the
    body's exception, and a raising body still closes all three. The clock
    is ``time.monotonic`` (the journal's), which the benchmark's own
    ``SpanLog.span`` reads too."""

    __slots__ = (
        "_spec", "_journal", "_labels", "_ids", "_args", "_start",
        "_annotation", "_outer_step",
    )

    def __init__(
        self,
        spec: _PhaseSpec,
        journal: Optional[TraceJournal],
        labels: Optional[Dict[str, Any]],
        fields: Dict[str, Any],
    ) -> None:
        self._spec = spec
        self._journal = (journal or current()) if spec.journal else None
        self._labels = labels
        # An id the caller could not give (a scripted manager's step) is
        # left out; the journal then stamps its own current step.
        self._ids = {
            k: v for k in _ID_FIELDS if (v := fields.pop(k, None)) is not None
        }
        self._args = fields
        self._annotation = None

    def __enter__(self) -> "_Span":
        spec = self._spec
        # The spans that open on this thread under a root take the root's
        # step unless they name their own: the commit may advance the
        # journal's step on another thread while the step is still running.
        if spec.root:
            self._outer_step = getattr(_TLS, "step", None)
            _TLS.step = self._ids.get("step")
        elif "step" not in self._ids:
            step = getattr(_TLS, "step", None)
            if step is not None:
                self._ids["step"] = step
        if spec.annotation is not None:
            types = _annotations()
            if types:
                try:
                    if spec.root:
                        # Numbered by the step it is, which for an inner step
                        # is not the manager's (that counts committed syncs).
                        ids = dict(self._ids)
                        step = ids.pop("step", 0)
                        # ``tid``: see :func:`_runtime_under_spans`.
                        self._annotation = types[1](
                            spec.annotation,
                            step_num=self._args.get("inner_step", step),
                            tid=threading.get_native_id(), **ids,
                        )
                    else:
                        self._annotation = types[0](spec.annotation, **self._ids)
                    self._annotation.__enter__()
                except Exception:  # noqa: BLE001 — observability must not wound
                    self._annotation = None
        journal = self._journal
        self._start = journal._mono() if journal is not None else time.monotonic()
        return self

    def __exit__(self, *exc: object) -> None:
        spec, journal = self._spec, self._journal
        start = self._start
        dur = (journal._mono() if journal is not None else time.monotonic()) - start
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        if spec.root:
            _TLS.step = self._outer_step
        _feed(spec, journal, self._labels, self._ids, self._args, start, dur)
        if spec.annotation is not None:
            chrome = _chrome_capture
            if chrome is not None:
                chrome.add_span(
                    spec.annotation, start, dur, {**self._ids, **self._args}
                )
            if _LOG_SPANS:
                _span_logger.info("%s took %.3fms", spec.annotation, dur * 1000)


def phase(
    name: str,
    journal: Optional[TraceJournal] = None,
    labels: Optional[Dict[str, Any]] = None,
    **fields: Any,
) -> _Span:
    """Times the with-body as the phase ``name`` of :data:`PHASES` into the
    histogram, the journal and the profiler annotation that table gives it.

    ``journal`` defaults to this thread's :func:`current` journal (a Manager
    passes its own, so that its quorum thread's events land in its
    replica's timeline); ``labels`` are the histogram's labels; ``fields``
    named ``step``, ``quorum_id`` or ``fragment`` identify the span in the
    journal and on the annotation, any other field is a journal argument."""
    return _Span(PHASES[name], journal, labels, fields)


def record_phase(
    name: str,
    start_mono: float,
    journal: Optional[TraceJournal] = None,
    labels: Optional[Dict[str, Any]] = None,
    **fields: Any,
) -> float:
    """Records a phase that began on another thread at ``start_mono`` (the
    journal's monotonic clock) and ends now: histogram and journal, no
    annotation (that belongs to the thread that waits: :func:`annotation`).
    Returns the duration."""
    j = journal or current()
    dur = j._mono() - start_mono
    _feed(PHASES[name], j, labels, fields, {}, start_mono, dur)
    return dur


def annotation(name: str, **ids: Any) -> Any:
    """The bare profiler annotation of phase ``name``, for the thread that
    waits on work :func:`record_phase` times elsewhere."""
    return _Span(PHASES[name]._replace(journal=None, histogram=None), None, None, ids)


# ---------------------------------------------------------------------------
# capture: the profiler, started and stopped inside the running process
# ---------------------------------------------------------------------------

_capture_lock = threading.Lock()
_capture: Optional[Dict[str, Any]] = None


def _registry_totals() -> Dict[tuple, Dict[str, float]]:
    """{(name, label items): {"sum", "count"} | {"value"}} of every histogram
    and counter now."""
    snap = metrics.snapshot()
    out: Dict[tuple, Dict[str, float]] = {}
    for name, entries in snap["histograms"].items():
        for e in entries:
            out[(name, tuple(sorted(e["labels"].items())))] = {
                "sum": e["sum"], "count": e["count"],
            }
    for name, entries in snap["counters"].items():
        for e in entries:
            out[(name, tuple(sorted(e["labels"].items())))] = {"value": e["value"]}
    return out


def _capture_mark(name: str) -> int:
    """One instant annotation carrying the monotonic clock's reading: the
    same instant on the profiler's clock (the annotation's start) and on the
    journal's (``t_mono`` seconds = ``mono_ns`` / 1e9). And the OS thread's
    id, as a step's root does: see :func:`_runtime_under_spans`."""
    mono_ns = time.monotonic_ns()
    types = _annotations()
    if types:
        with types[0](name, mono_ns=mono_ns, tid=threading.get_native_id()):
            pass
    return mono_ns


def _xplane_files(log_dir: str) -> Dict[str, int]:
    """{path: mtime_ns} of the profiler's files under ``log_dir``."""
    found: Dict[str, int] = {}
    pattern = os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")
    for path in glob.glob(pattern):
        try:
            found[path] = os.stat(path).st_mtime_ns
        except OSError:
            pass
    return found


# Of the names under one span the table keeps these many, by inclusive
# seconds; the rest is summed as "other".
_RUNTIME_NAMES_KEPT = 12


class _OpenSpan(NamedTuple):
    """An open ``tpuft::`` annotation while its line is walked: its table in
    the result, its start and the runtime names seen under it so far."""

    table: Dict[str, Any]
    start_ns: int
    seen: set


class _OpenEvent(NamedTuple):
    """An open event of a line: where it ends, its name, the innermost
    ``tpuft::`` annotation around it (itself, for an annotation) and, for a
    runtime event, its name's slot in that annotation's table."""

    end_ns: int
    name: str
    owner: Optional[_OpenSpan]
    slot: Optional[Dict[str, Any]]


def _thread_id(line: Any) -> Optional[int]:
    """The OS thread id a root or a capture mark on this line carries."""
    with warnings.catch_warnings():
        # jaxlib's stats type lacks ``__module__``, which Python warns of.
        warnings.simplefilter("ignore", DeprecationWarning)
        for event in line.events:
            if event.name.startswith("tpuft::"):
                tid = dict(event.stats).get("tid")
                if tid is not None:
                    return int(tid)
    return None


def _runtime_under_spans(path: str) -> Dict[str, Any]:
    """What the runtime did under each of the program's spans, from the
    xplane a capture wrote: on every host thread line, each event that is
    neither the program's (``tpuft::``) nor the benchmark's (``chipbench/``)
    goes to the INNERMOST ``tpuft::`` annotation that contains it on that
    line. ``{annotation: {"count", "seconds", "under": {name: {"count",
    "seconds", "self_seconds", "first_at_s"}}}}``: a name is the event's cut
    at ``(``; ``seconds`` is inclusive (an event inside one of its own name
    adds to ``count`` alone, so a name never holds more than its span),
    ``self_seconds`` is less the events nested in it, ``first_at_s`` is the
    mean offset of the name's first start from its span's start. No event
    lists: a tail of 16 steps holds 11,000 waits for holds.

    One thread may have two lines: a runtime that records its own events
    (the TPU's PJRT plugin) writes them to a line of its own, named
    ``<thread name>/<OS thread id>``, beside the line that holds the
    annotations. A step's root and the capture's marks carry ``tid``, the OS
    thread id, as a stat, so such a line is laid into its thread's before
    the walk; on the CPU there is none."""
    from jax.profiler import ProfileData

    spans: Dict[str, Dict[str, Any]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        lines = [
            (line.name, [(e.start_ns, e.duration_ns, e.name) for e in line.events], line)
            for line in plane.lines
        ]
        for _, events, line in lines:
            if not any(name.startswith("tpuft::") for _, _, name in events):
                continue
            tid = _thread_id(line)
            if tid is not None:
                events = events + [
                    e for other, guest, _ in lines
                    if other.endswith(f"/{tid}") for e in guest
                    if not e[2].startswith("tpuft::")
                ]
            events.sort(key=lambda e: (e[0], -e[1]))
            stack: List[_OpenEvent] = []  # outermost first
            for start, dur, name in events:
                while stack and stack[-1].end_ns <= start:
                    stack.pop()
                seconds = dur * 1e-9
                if stack and stack[-1].slot is not None:
                    stack[-1].slot["self_seconds"] -= seconds
                if name.startswith("tpuft::"):
                    table = spans.setdefault(
                        name, {"count": 0, "seconds": 0.0, "under": {}}
                    )
                    table["count"] += 1
                    table["seconds"] += seconds
                    owner = _OpenSpan(table, start, set())
                    stack.append(_OpenEvent(start + dur, name, owner, None))
                    continue
                owner = stack[-1].owner if stack else None
                if owner is None or name.startswith("chipbench/"):
                    stack.append(_OpenEvent(start + dur, name, owner, None))
                    continue
                name = name.split("(")[0].strip()
                slot = owner.table["under"].setdefault(
                    name, {"count": 0, "seconds": 0.0, "self_seconds": 0.0,
                           "first_at_s": 0.0, "spans": 0},
                )
                slot["count"] += 1
                slot["self_seconds"] += seconds
                if not any(o.name == name and o.owner is owner for o in stack):
                    slot["seconds"] += seconds
                if name not in owner.seen:
                    owner.seen.add(name)
                    slot["spans"] += 1
                    slot["first_at_s"] += (start - owner.start_ns) * 1e-9
                stack.append(_OpenEvent(start + dur, name, owner, slot))
    for table in spans.values():
        under = table["under"]
        for slot in under.values():
            slot["first_at_s"] /= slot.pop("spans")
        ranked = sorted(under, key=lambda n: -under[n]["seconds"])
        table["under"] = {n: under[n] for n in ranked[:_RUNTIME_NAMES_KEPT]}
        rest = ranked[_RUNTIME_NAMES_KEPT:]
        if rest:
            table["under"]["other"] = {
                key: sum(under[n][key] for n in rest)
                for key in ("count", "seconds", "self_seconds")
            }
    return spans


def start_capture(
    log_dir: str,
    host_level: int = 2,
    python_level: int = 0,
    journal: Optional[TraceJournal] = None,
) -> None:
    """Starts a profiler session writing under ``log_dir`` (the xplane lands
    in ``<log_dir>/plugins/profile/<time>/*.xplane.pb``) and notes where the
    journal and the metrics registry stand. Any number of captures may
    follow one another in a process; starting one while another runs is an
    error. Only the process that holds the chip can trace it."""
    global _capture
    import jax.profiler

    j = journal or current()
    with _capture_lock:
        if _capture is not None:
            raise RuntimeError(
                f"a capture into {_capture['trace_dir']} is already running; "
                "stop_capture() it first"
            )
        files = _xplane_files(str(log_dir))
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = host_level
        options.python_tracer_level = python_level  # the spans, not every Python frame
        jax.profiler.start_trace(str(log_dir), profiler_options=options)
        _capture = {
            "trace_dir": str(log_dir),
            "files": files,
            "journal": j,
            "seq": j._last_seq,
            "totals": _registry_totals(),
            "begin_mono_ns": _capture_mark("tpuft::capture_begin"),
        }


def stop_capture() -> Dict[str, Any]:
    """Stops the running capture and returns plain data: ``trace_dir``;
    ``events``, the journal's own events recorded during the capture;
    ``counters``, the growth of every histogram (``sum``, ``count``) and
    counter (``value``) over it, ``{name: [{"labels": {...}, ...}]}`` with
    what did not grow left out; ``clock``, the monotonic clock at the
    ``tpuft::capture_begin`` / ``tpuft::capture_end`` annotations;
    ``dropped``, events of the capture the ring had already overwritten; and
    ``runtime``, what the runtime did under each of the program's spans
    (:func:`_runtime_under_spans` of the xplane this capture wrote, on the
    profiler's clock, which is the device ops'). It is read here, after the
    capture has stopped; a trace that cannot be read gives ``{}`` and a
    journal instant ``capture_runtime_unread``, never an exception."""
    global _capture
    import jax.profiler

    with _capture_lock:
        if _capture is None:
            raise RuntimeError("no capture is running")
        cap, _capture = _capture, None
        end_mono_ns = _capture_mark("tpuft::capture_end")
        jax.profiler.stop_trace()
    j: TraceJournal = cap["journal"]
    events = [e for e in j.snapshot() if e["seq"] > cap["seq"]]
    before = cap["totals"]
    counters: Dict[str, List[Dict[str, Any]]] = {}
    for (name, labels), now in _registry_totals().items():
        was = before.get((name, labels), {})
        growth = {k: v - was.get(k, 0.0) for k, v in now.items()}
        if any(growth.values()):
            counters.setdefault(name, []).append({"labels": dict(labels), **growth})
    dropped = max(0, (j._last_seq - cap["seq"]) - len(events))
    runtime: Dict[str, Any] = {}
    try:
        was = cap["files"]
        wrote = {
            path: mtime for path, mtime in _xplane_files(cap["trace_dir"]).items()
            if was.get(path) != mtime
        }
        if not wrote:
            raise FileNotFoundError(f"no new *.xplane.pb under {cap['trace_dir']}")
        runtime = _runtime_under_spans(max(wrote, key=wrote.__getitem__))
    except Exception as e:  # noqa: BLE001 — observability must not wound
        j.record("capture_runtime_unread", cat="capture", error=repr(e))
    return {
        "trace_dir": cap["trace_dir"],
        "events": events,
        "counters": counters,
        "clock": {"begin_mono_ns": cap["begin_mono_ns"], "end_mono_ns": end_mono_ns},
        "dropped": dropped,
        "runtime": runtime,
    }


_compile_listener_installed = False


def install_compile_listener() -> None:
    """Once a process: every backend compilation becomes a journal event
    ``compile`` (its seconds, at the compiling thread's current step), so
    that "which step recompiled" has an answer in the program's own record."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return
    try:
        import jax.monitoring

        def on_duration(event: str, seconds: float, **_: Any) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                current().record("compile", cat="compile", seconds=seconds)

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        _compile_listener_installed = True
    except Exception:  # noqa: BLE001 — observability must not wound
        pass


# ---------------------------------------------------------------------------
# incidents
# ---------------------------------------------------------------------------


def incident_id(kind: str, step: int, quorum_id: int) -> str:
    """Deterministic incident id: every process observing the same
    quorum-wide event (a rollback at step N under quorum Q, a heal
    exhaustion) derives the SAME id with no coordination, so offline dumps
    from N hosts correlate by filename alone."""
    return f"inc-{kind}-q{quorum_id}-s{step}"


def open_incident(
    kind: str,
    step: int,
    quorum_id: int,
    journal: Optional[TraceJournal] = None,
    reason: str = "",
) -> str:
    """Stamps an incident: records the event, marks the id active (flight-
    recorder dumps reuse it in their filenames until the next commit
    clears it), dumps this journal AND the flight-recorder ring under
    ``$TPUFT_FLIGHT_RECORDER`` (no-ops when unset). Never raises."""
    j = journal or current()
    iid = incident_id(kind, step, quorum_id)
    try:
        j.record(
            "incident", cat="incident", step=step, quorum_id=quorum_id,
            kind=kind, incident=iid, reason=reason,
        )
        j.active_incident = iid
        metrics.inc("tpuft_trace_incidents_total", kind=kind)
        j.dump(reason=f"{kind}: {reason}")
        from torchft_tpu.utils import flight_recorder

        # Bind the journal as this thread's current while the flight
        # recorder dumps: incidents often open on the quorum thread, and
        # the FR filename reads identity + incident from tracing.current().
        with use_journal(j):
            flight_recorder.dump_on_failure("tracing", f"incident {iid}: {reason}")
    except Exception:  # noqa: BLE001 — incident capture must never wound
        pass
    return iid


def active_incident(journal: Optional[TraceJournal] = None) -> Optional[str]:
    return (journal or current()).active_incident


def clear_incident(journal: Optional[TraceJournal] = None) -> None:
    (journal or current()).active_incident = None


# ---------------------------------------------------------------------------
# /trace.json payload (served by every metrics HTTP surface)
# ---------------------------------------------------------------------------


def trace_json_payload(journal: Optional[TraceJournal] = None) -> Dict[str, Any]:
    j = journal or default()
    return {
        "ts": time.time(),
        "job_id": j.job_id,
        "replica_id": j.replica_id,
        "group_rank": j.group_rank,
        "step": j.step,
        "quorum_id": j.quorum_id,
        "clock": {
            "wall": j._wall(),
            "mono": j._mono(),
            "offset_s": j.clock_offset_s,
        },
        "incident": j.active_incident,
        "dropped": j.dropped(),
        "events": j.snapshot(),
        "phases": j.phase_rollup(),
    }


# ---------------------------------------------------------------------------
# store-mediated coarse clock sampling
# ---------------------------------------------------------------------------


class StoreClockSampler:
    """Coarse wall-clock offset sampling through a shared KV store.

    Protocol (pure get/set — the store has no server clock or listing):
    rank-0 managers race to own the ``trace/clockref`` beacon; ownership
    converges to the smallest owner key (each claimer only overwrites when
    it sorts at-or-below the current owner), with stale takeover when the
    beacon's counter stops advancing (dead owner). Everyone else samples:
    a beacon whose counter ADVANCED since our previous read was written
    inside our (prev_read, now] window, so
    ``offset = now - window/2 - beacon.wall`` with error ± window/2 —
    bounded by the push cadence. That catches gross skew (hosts seconds or
    minutes apart); fine alignment comes from barrier anchors at merge
    time (scripts/fleet_trace.py). Best-effort everywhere: a dead store
    never wounds a step.
    """

    STALE_TAKEOVER_READS = 3

    def __init__(
        self,
        journal: TraceJournal,
        owner_key: str,
        claim: bool = False,
        key: str = CLOCK_REF_KEY,
    ) -> None:
        self._journal = journal
        self._owner_key = str(owner_key)
        self._claim = claim
        self._key = key
        self._n = 0
        self._last_seen_n: Optional[int] = None
        self._stale_reads = 0
        self._last_read_wall: Optional[float] = None
        self._enabled = os.environ.get(ENV_CLOCK, "1") != "0"
        self.last_offset_s: Optional[float] = None

    def tick(self, store: Any) -> None:
        """One sampling round (call at the metrics-push cadence)."""
        if not self._enabled:
            return
        try:
            self._tick(store)
        except Exception:  # noqa: BLE001 — observability must not wound
            pass

    def _tick(self, store: Any) -> None:
        now = self._journal._wall()
        raw = store.get(self._key, timeout=2.0, wait=False)
        beacon = json.loads(raw.decode()) if raw else None

        should_claim = False
        if self._claim:
            if beacon is None:
                should_claim = True
            else:
                owner = str(beacon.get("owner", ""))
                if owner == self._owner_key or self._owner_key < owner:
                    should_claim = True
                elif beacon.get("n") == self._last_seen_n:
                    self._stale_reads += 1
                    if self._stale_reads >= self.STALE_TAKEOVER_READS:
                        should_claim = True  # owner stopped heartbeating
                else:
                    self._stale_reads = 0

        if beacon is not None and str(beacon.get("owner")) != self._owner_key:
            n = beacon.get("n")
            if n != self._last_seen_n and self._last_read_wall is not None:
                # The write landed between our previous read and this one
                # (in OUR clock): midpoint estimate, error ± window/2.
                window = max(0.0, now - self._last_read_wall)
                offset = (now - window / 2.0) - float(beacon.get("wall", now))
                self.last_offset_s = offset
                self._journal.clock_offset_s = offset
                self._journal.record(
                    "clock_sample",
                    cat="clock",
                    ref_owner=str(beacon.get("owner")),
                    ref_n=n,
                    ref_wall=float(beacon.get("wall", now)),
                    window_s=round(window, 6),
                    offset_s=offset,
                )
                metrics.set_gauge("tpuft_trace_clock_offset_ms", offset * 1e3)
            self._last_seen_n = n
        elif beacon is not None:
            # We are the owner: our frame IS the beacon frame.
            self.last_offset_s = 0.0
            self._journal.clock_offset_s = 0.0
        self._last_read_wall = now

        if should_claim:
            self._n += 1
            store.set(
                self._key,
                json.dumps(
                    {"owner": self._owner_key, "n": self._n,
                     "wall": self._journal._wall()}
                ).encode(),
            )
