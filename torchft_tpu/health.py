"""Gray-failure ejection plane: slow-is-the-new-dead straggler verdicts.

The quorum model is binary — a replica is heartbeating or it is dead —
but the worst production failures are gray: a replica whose device
wedges mid-run, whose NIC drips, or whose host is oversubscribed keeps
heartbeating and voting while dragging every commit barrier to its
speed. The fleet already *measures* the signal — per-phase histograms, the
trace plane's per-step phase rollup, fleet_status's STRAGGLER column —
this module closes the loop from evidence to safe actuation:

- :class:`HealthScorer` — per-replica EWMAs of the existing phase
  evidence (device_sync / update_dispatch / wire_bucket), compared
  fleet-relatively against peer snapshots pushed through the quorum's
  shared store (the same plumbing the metrics push rides). A verdict
  requires ``TPUFT_HEALTH_CONSECUTIVE`` consecutive windows beyond a
  multiplicative threshold vs the fleet median AND an absolute gap
  floor — hysteresis: a transient blip must never eject.
- **Self-ejection** — a replica judging itself degraded funnels a
  :class:`DegradedReplicaError` into ``Manager.report_error`` and then
  raises it out of ``start_quorum`` at the step boundary: the same
  supervisor-escalation family as quorum timeouts and
  ``HealExhaustedError``. The survivors see an ordinary membership
  change (window drain → pg.configure → proceed) and the ejected
  replica rejoins via the normal heal path once its self-probe passes
  (delta rejoin makes the comeback cheap).
- :class:`StepWatchdog` — the fully-wedged case: device sync never
  completes but the control thread keeps heartbeating. A step-progress
  deadline scaled from the replica's OWN step-interval EWMA trips the
  same probe→eject path from a watchdog thread (the train thread is
  stuck, so escalation defaults to SIGTERM — the supervisor restarts
  the process and the quarantine gate takes over).
- :class:`QuarantineGate` — re-probe with exponential backoff
  (``TPUFT_QUARANTINE_BASE_SEC``, capped), and ``M`` ejections inside a
  sliding window parks the replica until a long cooldown — a
  crash-looping gray host cannot flap the fleet. State persists across
  supervised restarts (keyed by the STABLE replica id).
- **Peer accusations stay advisory**: barrier-wait asymmetry (the rank
  that waited least entered last) is published to the metrics plane and
  surfaced in fleet_status / ``fleet_trace --explain-step``, but a peer
  NEVER initiates a kill — a partition cannot brain-split the fleet
  into mutual ejections. Only self-verdicts actuate.

Chaos seams (:func:`injected_stall`): the punisher arms
``slow_replica`` / ``wedge_device`` (site ``device_sync``) and
``drip_wire`` (site ``wire``) through the fault file
(utils/faultinject.py). One arm = one replica affected: the consuming
replica installs a PERSISTENT per-replica stall/wedge keyed by its
trace-journal identity (threads-as-replicas drills give each replica
thread its own journal), cleared by ejection — exactly like a process
restart clears real module state.

Safety invariants:

- Ejection below ``min_replica_size`` is REFUSED and counted
  (``tpuft_health_ejections_refused_total``) — a degraded fleet keeps
  training slowly rather than deadlocking the quorum.
- Everything store/metrics-side is best-effort: a dead board or a
  failed push can never wound a step. Only the explicit ejection raise
  leaves the step boundary.

docs/resilience.md rows; docs/observability.md walkthrough;
drills in tests/test_health.py; benchmarks/straggler_bench.py.
"""

from __future__ import annotations

import json
import logging
import os
import signal
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from torchft_tpu import metrics, tracing
from torchft_tpu.utils import faultinject

logger = logging.getLogger(__name__)

__all__ = [
    "DegradedReplicaError",
    "HealthScorer",
    "StepWatchdog",
    "QuarantineGate",
    "HealthMonitor",
    "enabled",
    "injected_stall",
    "install_injected",
    "clear_injected",
    "SELF_PHASES",
]

# -- env knobs (doctor.KNOWN_ENV mirrors every name here) -------------------
ENV_HEALTH = "TPUFT_HEALTH"
ENV_THRESHOLD = "TPUFT_HEALTH_THRESHOLD"
ENV_CONSECUTIVE = "TPUFT_HEALTH_CONSECUTIVE"
ENV_MIN_PEERS = "TPUFT_HEALTH_MIN_PEERS"
ENV_EWMA_ALPHA = "TPUFT_HEALTH_EWMA_ALPHA"
ENV_PEER_TTL = "TPUFT_HEALTH_PEER_TTL_SEC"
ENV_PUSH_SEC = "TPUFT_HEALTH_PUSH_SEC"
ENV_MIN_GAP = "TPUFT_HEALTH_MIN_GAP_SEC"
ENV_WEDGE_SCALE = "TPUFT_HEALTH_WEDGE_SCALE"
ENV_WEDGE_FLOOR = "TPUFT_HEALTH_WEDGE_FLOOR_SEC"
ENV_WEDGE_ACTION = "TPUFT_HEALTH_WEDGE_ACTION"  # term | flag
ENV_SLOW_MS = "TPUFT_HEALTH_SLOW_MS"
ENV_PROBE = "TPUFT_HEALTH_PROBE"
ENV_PROBE_TIMEOUT = "TPUFT_HEALTH_PROBE_TIMEOUT_SEC"
ENV_QUARANTINE_BASE = "TPUFT_QUARANTINE_BASE_SEC"
ENV_QUARANTINE_CAP = "TPUFT_QUARANTINE_CAP_SEC"
ENV_QUARANTINE_MAX_EJECTS = "TPUFT_QUARANTINE_MAX_EJECTS"
ENV_QUARANTINE_WINDOW = "TPUFT_QUARANTINE_WINDOW_SEC"
ENV_QUARANTINE_PARK = "TPUFT_QUARANTINE_PARK_SEC"
ENV_QUARANTINE_DIR = "TPUFT_QUARANTINE_DIR"

# Phases a replica scores ITSELF on (own work being slow = I am the
# straggler). The commit-barrier wait is the INVERSE signal — the rank
# that waited least entered last — and feeds peer accusations only.
SELF_PHASES = ("device_sync", "update_dispatch", "wire_bucket")
BARRIER_PHASE = "commit_barrier"

# tpuft_health_state gauge values (fleet_status's HEALTH column decodes).
STATE_HEALTHY = 0
STATE_SUSPECT = 1
STATE_DEGRADED = 2
STATE_QUARANTINED = 3
STATE_PARKED = 4
STATE_NAMES = {
    STATE_HEALTHY: "ok",
    STATE_SUSPECT: "suspect",
    STATE_DEGRADED: "degraded",
    STATE_QUARANTINED: "quar",
    STATE_PARKED: "parked",
}

# Well-known shared-store key prefix for pushed health snapshots (the
# quorum's rendezvous store, which every member can already reach).
BOARD_PREFIX = "health"


class DegradedReplicaError(RuntimeError):
    """Raised out of ``Manager.start_quorum`` at the step boundary when
    this replica's health verdict (or the wedge watchdog) judged it
    degraded: slow-is-the-new-dead. Same escalation family as a quorum
    timeout or :class:`~torchft_tpu.manager.HealExhaustedError` — the
    supervisor restarts the process, the quarantine gate re-probes the
    accelerator with exponential backoff, and the replica rejoins
    through the normal heal path (delta rejoin) once the probe passes."""


def _env_float(env: str, default: float) -> float:
    try:
        return float(os.environ.get(env, "") or default)
    except ValueError:
        return default


def _env_int(env: str, default: int) -> int:
    try:
        return int(os.environ.get(env, "") or default)
    except ValueError:
        return default


def enabled() -> bool:
    """Master switch: the Manager auto-attaches a monitor iff set."""
    return os.environ.get(ENV_HEALTH, "0") not in ("", "0")


# ---------------------------------------------------------------------------
# chaos seams: punisher-armed persistent gray faults
# ---------------------------------------------------------------------------

# Per-replica injected gray state, keyed by the trace journal identity of
# the consuming thread (threads-as-replicas drills give each replica its
# own journal; a real process has exactly one). Module-global on purpose:
# real gray failures are per-PROCESS, and ejection/restart clears them.
_INJECTED_LOCK = threading.Lock()
_INJECTED: Dict[str, Dict[str, Any]] = {}

# Fault modes -> the sites their installed stall applies to.
_INJECT_MODES = {
    "slow_replica": ("device_sync",),
    "wedge_device": ("device_sync",),
    "drip_wire": ("wire",),
}


def _replica_key() -> str:
    return tracing.current().replica_id


def install_injected(
    mode: str, replica_id: Optional[str] = None, stall_s: Optional[float] = None
) -> None:
    """Installs a persistent gray fault for ``replica_id`` (default: the
    calling thread's journal identity). ``slow_replica``/``drip_wire``
    stall every matching phase by ``stall_s`` (default
    ``$TPUFT_HEALTH_SLOW_MS``); ``wedge_device`` blocks the device sync
    until :func:`clear_injected` — the fully-wedged mode the step
    watchdog exists for."""
    if mode not in _INJECT_MODES:
        raise ValueError(f"unknown injected gray mode {mode!r}")
    key = replica_id if replica_id is not None else _replica_key()
    state: Dict[str, Any] = {"mode": mode, "sites": set(_INJECT_MODES[mode])}
    if mode == "wedge_device":
        state["released"] = threading.Event()
    else:
        state["stall_s"] = (
            stall_s
            if stall_s is not None
            else _env_float(ENV_SLOW_MS, 250.0) / 1000.0
        )
    with _INJECTED_LOCK:
        _INJECTED[key] = state
    metrics.inc("tpuft_health_injected_faults_total", mode=mode)
    tracing.record("health_fault_injected", mode=mode, replica=key)
    logger.warning("health chaos: installed %s for replica %s", mode, key)


def clear_injected(replica_id: Optional[str] = None) -> None:
    """Clears injected gray faults (one replica, or all when None) —
    what a process restart does for free; the thread drills and the
    ejection path call it explicitly. Releases any wedge waiter."""
    with _INJECTED_LOCK:
        keys = [replica_id] if replica_id is not None else list(_INJECTED)
        for key in keys:
            state = _INJECTED.pop(key, None)
            if state is not None and state.get("released") is not None:
                state["released"].set()


def injected_stall(site: str) -> None:
    """The gray-fault chokepoint, called from the device-sync and wire
    seams (optim._sync_device, ddp's bucket wait). Production cost when
    unarmed: one env lookup + one dict get. A punisher arm at this site
    is consumed exactly once (faultinject semantics) and INSTALLS the
    persistent per-replica fault; every later call applies it."""
    if os.environ.get(faultinject.ENV_FAULT_FILE):
        mode = faultinject.consume(site)
        if mode in _INJECT_MODES:
            install_injected(mode)
    state = _INJECTED.get(_replica_key())
    if not state or site not in state["sites"]:
        return
    released = state.get("released")
    if released is not None:
        # Wedge: the device never answers. Blocks until ejection/restart
        # clears the fault (clear_injected sets the event) — meanwhile
        # the control threads keep heartbeating, which is the point.
        released.wait()
        return
    stall = float(state.get("stall_s", 0.0))
    if stall > 0.0:
        time.sleep(stall)


# ---------------------------------------------------------------------------
# scorer
# ---------------------------------------------------------------------------


def _median(values: List[float]) -> float:
    ordered = sorted(values)
    n = len(ordered)
    mid = n // 2
    if n % 2:
        return ordered[mid]
    return 0.5 * (ordered[mid - 1] + ordered[mid])


class HealthScorer:
    """Pure verdict logic: own per-phase EWMAs vs fleet-relative peer
    snapshots, with hysteresis. No I/O, no threads — the monitor owns
    plumbing, the bench and unit tests drive this directly.

    A window is "slow" when ANY self phase satisfies BOTH bounds against
    the fleet median of fresh peers: ``own > threshold * median`` (the
    multiplicative bound — fleet-relative, so a uniformly slow fleet
    never accuses anyone) and ``own - median > min_gap_s`` (the absolute
    floor — 3x a microsecond-scale phase is noise, not a verdict).
    ``consecutive`` slow windows latch the degraded verdict; one healthy
    window resets the streak — transient blips never eject."""

    def __init__(
        self,
        replica_id: str,
        threshold: Optional[float] = None,
        consecutive: Optional[int] = None,
        min_peers: Optional[int] = None,
        alpha: Optional[float] = None,
        peer_ttl_s: Optional[float] = None,
        min_gap_s: Optional[float] = None,
        wall: Callable[[], float] = time.time,
    ) -> None:
        self.replica_id = replica_id
        self.threshold = max(
            1.01, threshold if threshold is not None else _env_float(ENV_THRESHOLD, 3.0)
        )
        self.consecutive = max(
            1,
            consecutive
            if consecutive is not None
            else _env_int(ENV_CONSECUTIVE, 3),
        )
        self.min_peers = max(
            1, min_peers if min_peers is not None else _env_int(ENV_MIN_PEERS, 2)
        )
        self.alpha = min(
            1.0, max(0.01, alpha if alpha is not None else _env_float(ENV_EWMA_ALPHA, 0.25))
        )
        self.peer_ttl_s = (
            peer_ttl_s if peer_ttl_s is not None else _env_float(ENV_PEER_TTL, 60.0)
        )
        self.min_gap_s = (
            min_gap_s if min_gap_s is not None else _env_float(ENV_MIN_GAP, 0.05)
        )
        self._wall = wall
        self.ewma: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self._peers: Dict[str, Tuple[float, Dict[str, float]]] = {}
        self.streak = 0
        self._rollup_seen_step = -1

    # -- own evidence -------------------------------------------------------

    def observe(self, phase: str, seconds: float) -> None:
        prev = self.ewma.get(phase)
        value = max(float(seconds), 0.0)
        self.ewma[phase] = (
            value if prev is None else prev + self.alpha * (value - prev)
        )
        self.counts[phase] = self.counts.get(phase, 0) + 1

    def ingest_rollup(self, rollup: List[Dict[str, Any]]) -> None:
        """Feeds the trace plane's per-step phase rollup
        (TraceJournal.phase_rollup) — the EXISTING per-phase evidence —
        into the EWMAs, each step at most once."""
        for entry in rollup:
            step = entry.get("step")
            if step is None or step <= self._rollup_seen_step:
                continue
            phases = entry.get("phases") or {}
            for phase in SELF_PHASES + (BARRIER_PHASE,):
                if phase in phases:
                    self.observe(phase, float(phases[phase]))
            self._rollup_seen_step = step

    # -- peer snapshots -----------------------------------------------------

    def note_peer(
        self, replica_id: str, phases: Dict[str, float], ts: Optional[float] = None
    ) -> None:
        if replica_id == self.replica_id:
            return
        self._peers[replica_id] = (
            self._wall() if ts is None else float(ts),
            {k: float(v) for k, v in phases.items()},
        )

    def fresh_peers(self) -> Dict[str, Dict[str, float]]:
        now = self._wall()
        return {
            rid: phases
            for rid, (ts, phases) in self._peers.items()
            if now - ts <= self.peer_ttl_s
        }

    def snapshot(self) -> Dict[str, Any]:
        """The pushed board payload — what peers score us against."""
        return {
            "ts": self._wall(),
            "replica_id": self.replica_id,
            "phases": {k: round(v, 6) for k, v in self.ewma.items()},
            "streak": self.streak,
        }

    # -- verdict ------------------------------------------------------------

    def evaluate(self) -> Dict[str, Any]:
        """One scoring window. Returns the verdict dict; hysteresis state
        (the streak) advances only on judgeable windows."""
        peers = self.fresh_peers()
        verdict: Dict[str, Any] = {
            "judgeable": False,
            "slow": False,
            "degraded": False,
            "streak": self.streak,
            "ratios": {},
            "peers": len(peers),
        }
        if len(peers) < self.min_peers:
            return verdict
        slow = False
        for phase in SELF_PHASES:
            own = self.ewma.get(phase)
            if own is None or self.counts.get(phase, 0) < 2:
                continue
            fleet = [p[phase] for p in peers.values() if phase in p]
            if len(fleet) < self.min_peers:
                continue
            med = _median(fleet)
            ratio = own / max(med, 1e-9)
            verdict["ratios"][phase] = round(ratio, 3)
            verdict["judgeable"] = True
            if ratio > self.threshold and (own - med) > self.min_gap_s:
                slow = True
        if not verdict["judgeable"]:
            return verdict
        self.streak = self.streak + 1 if slow else 0
        verdict.update(
            slow=slow, streak=self.streak, degraded=self.streak >= self.consecutive
        )
        return verdict

    def accuse(self) -> Optional[Tuple[str, float]]:
        """ADVISORY straggler attribution from barrier-wait asymmetry:
        the commit barrier releases everyone together, so the member
        with the SMALLEST barrier wait entered last and held the fleet
        up. Returns ``(accused_replica_id, gap_seconds)`` when the
        asymmetry clears both the multiplicative and absolute bounds, or
        None. Never actuates — accusations are published for operators
        (fleet_status / explain-step), not for peers to act on."""
        waits: Dict[str, float] = {}
        own = self.ewma.get(BARRIER_PHASE)
        if own is not None and self.counts.get(BARRIER_PHASE, 0) >= 2:
            waits[self.replica_id] = own
        for rid, phases in self.fresh_peers().items():
            if BARRIER_PHASE in phases:
                waits[rid] = phases[BARRIER_PHASE]
        if len(waits) < max(self.min_peers + 1, 2):
            return None
        slowest = min(waits, key=lambda r: waits[r])  # least wait = entered last
        longest = max(waits.values())
        gap = longest - waits[slowest]
        if longest > self.threshold * max(waits[slowest], 1e-9) and gap > self.min_gap_s:
            return slowest, gap
        return None


# ---------------------------------------------------------------------------
# step-progress watchdog (the fully-wedged case)
# ---------------------------------------------------------------------------


class StepWatchdog:
    """Fires ``on_wedge(elapsed_s, deadline_s)`` once when no step
    progress (:meth:`beat`) lands within a deadline scaled from the
    replica's OWN step-interval EWMA — ``max(scale * interval_ewma,
    floor)``, the floor alone before any interval evidence exists. The
    whole point is the case the scorer cannot see: a device sync that
    never completes parks the train thread forever while heartbeats
    keep the replica in the quorum. Re-arms on the next beat."""

    def __init__(
        self,
        on_wedge: Callable[[float, float], None],
        scale: Optional[float] = None,
        floor_s: Optional[float] = None,
        mono: Callable[[], float] = time.monotonic,
        alpha: float = 0.25,
    ) -> None:
        self._on_wedge = on_wedge
        self.scale = max(
            1.5, scale if scale is not None else _env_float(ENV_WEDGE_SCALE, 10.0)
        )
        self.floor_s = max(
            0.05,
            floor_s if floor_s is not None else _env_float(ENV_WEDGE_FLOOR, 30.0),
        )
        self._mono = mono
        self._alpha = alpha
        self._lock = threading.Lock()
        self._last_beat: Optional[float] = None
        self.interval_ewma: Optional[float] = None
        self._fired = False
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def deadline_s(self) -> float:
        with self._lock:
            if self.interval_ewma is None:
                return self.floor_s
            return max(self.scale * self.interval_ewma, self.floor_s)

    def beat(self) -> None:
        now = self._mono()
        with self._lock:
            if self._last_beat is not None:
                dt = now - self._last_beat
                self.interval_ewma = (
                    dt
                    if self.interval_ewma is None
                    else self.interval_ewma + self._alpha * (dt - self.interval_ewma)
                )
            self._last_beat = now
            self._fired = False
        if self._thread is None:
            self.start()

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="tpuft-health-watchdog"
        )
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.is_set():
            deadline = self.deadline_s()
            self._stop.wait(min(max(deadline / 4.0, 0.05), 1.0))
            with self._lock:
                last = self._last_beat
                fired = self._fired
            if last is None or fired:
                continue
            elapsed = self._mono() - last
            if elapsed > deadline:
                with self._lock:
                    self._fired = True
                try:
                    self._on_wedge(elapsed, deadline)
                except Exception:  # noqa: BLE001 — the watchdog must survive
                    logger.exception("wedge callback failed")

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None


# ---------------------------------------------------------------------------
# quarantine gate
# ---------------------------------------------------------------------------


def _default_probe() -> bool:
    """The self-check a quarantined replica must pass before rejoining: a
    full compile→execute→fetch round trip on the default device, bounded
    by ``TPUFT_HEALTH_PROBE_TIMEOUT_SEC``. In-process on a helper thread:
    the chip belongs to one process at a time, and by the time the gate
    is served the trainer may already hold it, so a probe in a child
    could only ever fail there. A device that never answers fails the
    probe at the deadline (the thread is a daemon and is left behind).
    ``TPUFT_HEALTH_PROBE=0`` skips it (drills / CPU-only fleets)."""
    if os.environ.get(ENV_PROBE, "1") == "0":
        return True
    from torchft_tpu.utils.platform import device_round_trip

    answer: List[bool] = []
    thread = threading.Thread(
        target=lambda: answer.append(device_round_trip()),
        daemon=True,
        name="tpuft-health-probe",
    )
    thread.start()
    thread.join(_env_float(ENV_PROBE_TIMEOUT, 120.0))
    return answer == [True]


class QuarantineGate:
    """Ejection bookkeeping + the startup re-admission gate.

    Every ejection is recorded (persisted under
    ``$TPUFT_QUARANTINE_DIR`` — default the flight-recorder dir — so
    supervised restarts of the same replica see it). :meth:`serve`
    re-probes with exponential backoff (``base * 2^attempt``, capped)
    until the probe passes; ``max_ejects`` ejections inside the sliding
    ``window_s`` parks the replica for ``park_s`` first — the
    crash-loop fence. All waiting is injectable for tests."""

    def __init__(
        self,
        replica_id: str,
        base_s: Optional[float] = None,
        cap_s: Optional[float] = None,
        max_ejects: Optional[int] = None,
        window_s: Optional[float] = None,
        park_s: Optional[float] = None,
        state_dir: Optional[str] = None,
        probe: Optional[Callable[[], bool]] = None,
        sleep: Callable[[float], None] = time.sleep,
        wall: Callable[[], float] = time.time,
    ) -> None:
        self.replica_id = replica_id
        self.base_s = max(
            0.01, base_s if base_s is not None else _env_float(ENV_QUARANTINE_BASE, 1.0)
        )
        self.cap_s = max(
            self.base_s,
            cap_s if cap_s is not None else _env_float(ENV_QUARANTINE_CAP, 60.0),
        )
        self.max_ejects = max(
            1,
            max_ejects
            if max_ejects is not None
            else _env_int(ENV_QUARANTINE_MAX_EJECTS, 3),
        )
        self.window_s = (
            window_s if window_s is not None else _env_float(ENV_QUARANTINE_WINDOW, 900.0)
        )
        self.park_s = (
            park_s if park_s is not None else _env_float(ENV_QUARANTINE_PARK, 1800.0)
        )
        self._probe = probe if probe is not None else _default_probe
        self._sleep = sleep
        self._wall = wall
        if state_dir is None:
            state_dir = os.environ.get(ENV_QUARANTINE_DIR) or os.environ.get(
                "TPUFT_FLIGHT_RECORDER"
            )
        self._state_path: Optional[str] = None
        if state_dir:
            try:
                os.makedirs(state_dir, exist_ok=True)
                self._state_path = os.path.join(
                    state_dir, f"quarantine_{tracing.sanitize(replica_id)}.json"
                )
            except OSError:
                self._state_path = None
        self.ejections: List[float] = []
        self.last_reason = ""
        self._load()

    # -- persistence --------------------------------------------------------

    def _load(self) -> None:
        if not self._state_path:
            return
        try:
            with open(self._state_path, "r") as f:
                data = json.load(f)
            self.ejections = [float(t) for t in data.get("ejections", [])]
            self.last_reason = str(data.get("last_reason", ""))
        except (OSError, ValueError):
            pass

    def _save(self) -> None:
        if not self._state_path:
            return
        try:
            tmp = f"{self._state_path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(
                    {"ejections": self.ejections, "last_reason": self.last_reason}, f
                )
            os.replace(tmp, self._state_path)
        except OSError:
            pass

    # -- accounting ---------------------------------------------------------

    def _recent(self) -> List[float]:
        now = self._wall()
        return [t for t in self.ejections if now - t <= self.window_s]

    def record_ejection(self, reason: str) -> None:
        self.ejections = self._recent() + [self._wall()]
        self.last_reason = reason
        self._save()

    def pending(self) -> bool:
        """True when a recent ejection is on file — the restarted
        process must serve quarantine before rejoining the fleet."""
        return bool(self._recent())

    def parked_until(self) -> float:
        """Nonzero wall time when the crash-loop fence is up: the
        sliding window holds ``max_ejects`` ejections, so re-admission
        waits out the long cooldown from the LAST ejection."""
        recent = self._recent()
        if len(recent) >= self.max_ejects:
            return max(recent) + self.park_s
        return 0.0

    # -- the gate -----------------------------------------------------------

    def serve(
        self, trace: Optional["tracing.TraceJournal"] = None, max_attempts: int = 64
    ) -> Dict[str, Any]:
        """Blocks until re-admission: park cooldown (if the crash-loop
        fence is up), then probe with exponential backoff until it
        passes. Returns the served record; counts
        ``tpuft_health_quarantine_seconds_total`` / ``_probes_total`` /
        ``_parked_total``. ``max_attempts`` bounds a probe that can
        never pass (the capped backoff keeps waiting cheap; past the
        bound we admit and let the verdict plane re-eject — an operator
        signal, not an infinite coma)."""
        journal = trace or tracing.current()
        waited = 0.0
        parked = False
        park_until = self.parked_until()
        if park_until > 0:
            parked = True
            metrics.inc("tpuft_health_parked_total")
            remaining = max(park_until - self._wall(), 0.0)
            journal.record(
                "health_quarantine", phase="parked", wait_s=round(remaining, 3),
                ejections=len(self._recent()),
            )
            logger.warning(
                "replica %s crash-loop parked: %d ejections in %.0fs window; "
                "cooling down %.1fs",
                self.replica_id, len(self._recent()), self.window_s, remaining,
            )
            self._sleep(remaining)
            waited += remaining
        attempts = 0
        while True:
            delay = min(self.base_s * (2.0 ** attempts), self.cap_s)
            self._sleep(delay)
            waited += delay
            ok = False
            try:
                ok = bool(self._probe())
            except Exception:  # noqa: BLE001 — a probe crash is a fail
                logger.exception("quarantine probe raised (counted as fail)")
            metrics.inc(
                "tpuft_health_probes_total", result="pass" if ok else "fail"
            )
            attempts += 1
            journal.record(
                "health_quarantine", phase="probe", attempt=attempts,
                result="pass" if ok else "fail", backoff_s=round(delay, 3),
            )
            if ok or attempts >= max_attempts:
                break
        metrics.inc("tpuft_health_quarantine_seconds_total", waited)
        record = {
            "attempts": attempts,
            "waited_s": round(waited, 3),
            "parked": parked,
        }
        # A real span (start backdated by the wait), not an instant: the
        # goodput ledger folds it into the `degraded` bucket; fleet_trace
        # keeps reading the same args off the served record.
        journal.record(
            "health_quarantine", ph="X", dur=waited, phase="served", **record
        )
        return record


# ---------------------------------------------------------------------------
# the monitor (glue: manager-side AND bench-side host)
# ---------------------------------------------------------------------------


class HealthMonitor:
    """One replica's verdict loop: scorer + watchdog + quarantine gate +
    the board plumbing, driven from the step boundary.

    The Manager calls :meth:`on_quorum` (peer set + shared board),
    :meth:`on_step` (cheap, never raises) after every commit
    resolution, and :meth:`should_eject` at the next ``start_quorum`` —
    the ONLY place the plane leaves the step boundary. The straggler
    bench drives the same object with a dict board and injected clocks.
    """

    def __init__(
        self,
        replica_id: str,
        group_rank: int = 0,
        min_replica_size: int = 1,
        scorer: Optional[HealthScorer] = None,
        gate: Optional[QuarantineGate] = None,
        watchdog: Optional[StepWatchdog] = None,
        board: Optional[Any] = None,
        trace: Optional["tracing.TraceJournal"] = None,
        push_interval_s: Optional[float] = None,
        wedge_action: Optional[Callable[[], None]] = None,
        wall: Callable[[], float] = time.time,
    ) -> None:
        self.replica_id = replica_id
        self.group_rank = int(group_rank)
        self.min_replica_size = int(min_replica_size)
        self.scorer = scorer or HealthScorer(replica_id, wall=wall)
        self.gate = gate or QuarantineGate(replica_id, wall=wall)
        self._watchdog = watchdog
        if self._watchdog is None:
            self._watchdog = StepWatchdog(self._on_wedge)
        else:
            self._watchdog._on_wedge = self._on_wedge
        self._board = board
        # An explicitly injected board (bench/tests) is pinned: quorum
        # discovery must not silently swap it for a store client.
        self._board_pinned = board is not None
        self._board_addr: Optional[str] = None
        self._peer_ids: List[str] = []
        self._participants = 0
        self._trace = trace
        self._wall = wall
        self._push_interval = (
            push_interval_s
            if push_interval_s is not None
            else _env_float(ENV_PUSH_SEC, 2.0)
        )
        self._last_push = 0.0
        self._wedge_action = wedge_action
        self._report_error: Optional[Callable[[Exception], None]] = None
        self._lock = threading.Lock()
        self._eject_reason: Optional[str] = None
        self._ejection_recorded = False
        self._refusal_counted = False
        self._accused: Optional[str] = None
        self.state = STATE_HEALTHY
        self._labels = {
            "replica_id": replica_id,
            "group_rank": str(self.group_rank),
        }
        self._set_state(STATE_HEALTHY)

    # -- wiring -------------------------------------------------------------

    def bind(
        self,
        trace: Optional["tracing.TraceJournal"] = None,
        report_error: Optional[Callable[[Exception], None]] = None,
        min_replica_size: Optional[int] = None,
    ) -> None:
        if trace is not None:
            self._trace = trace
        if report_error is not None:
            self._report_error = report_error
        if min_replica_size is not None:
            self.min_replica_size = int(min_replica_size)

    def _journal(self) -> "tracing.TraceJournal":
        return self._trace or tracing.current()

    def _set_state(self, state: int) -> None:
        self.state = state
        metrics.set_gauge("tpuft_health_state", state, **self._labels)

    # -- quorum-side plumbing ------------------------------------------------

    def on_quorum(self, quorum: Any) -> None:
        """Peer discovery off the quorum view the manager already holds:
        participant stable ids + the quorum's shared rendezvous store as
        the snapshot board. Best-effort everywhere."""
        try:
            q = getattr(quorum, "quorum", None)
            if q is not None:
                self._peer_ids = sorted(
                    {
                        str(member.replica_id).split(":", 1)[0]
                        for member in q.participants
                    }
                    - {self.replica_id}
                )
            addr = getattr(quorum, "store_address", "") or ""
            if addr and addr != self._board_addr and not self._board_pinned:
                from torchft_tpu.parallel.store import create_store_client

                board = create_store_client(addr, connect_timeout=2.0)
                old = self._board
                self._board, self._board_addr = board, addr
                if old is not None and hasattr(old, "close"):
                    try:
                        old.close()
                    except Exception:  # noqa: BLE001
                        pass
        except Exception:  # noqa: BLE001 — discovery is best-effort
            logger.debug("health peer discovery failed", exc_info=True)

    def set_peers(self, peer_ids: List[str], board: Any) -> None:
        """Direct wiring for the bench / tests (no quorum object)."""
        self._peer_ids = [p for p in peer_ids if p != self.replica_id]
        self._board = board
        self._board_pinned = True

    def _push_snapshot(self) -> None:
        if self._board is None:
            return
        try:
            snap = self.scorer.snapshot()
            snap["state"] = self.state
            if self._accused:
                snap["accused"] = self._accused
            self._board.set(
                f"{BOARD_PREFIX}/{self.replica_id}", json.dumps(snap).encode()
            )
        except Exception:  # noqa: BLE001 — the board must not wound a step
            logger.debug("health snapshot push failed", exc_info=True)

    def _pull_peers(self) -> None:
        if self._board is None:
            return
        for rid in self._peer_ids:
            try:
                raw = self._board.get(
                    f"{BOARD_PREFIX}/{rid}", timeout=1.0, wait=False
                )
                if raw is None:
                    continue
                snap = json.loads(
                    raw.decode() if isinstance(raw, (bytes, bytearray)) else raw
                )
                self.scorer.note_peer(
                    rid, snap.get("phases") or {}, ts=snap.get("ts")
                )
            except Exception:  # noqa: BLE001
                continue
        metrics.set_gauge(
            "tpuft_health_peer_snapshots",
            len(self.scorer.fresh_peers()),
            **self._labels,
        )

    # -- the step-boundary loop ---------------------------------------------

    def on_step(
        self, step: int, committed: bool = True, participants: Optional[int] = None
    ) -> None:
        """The per-step hook (commit-resolution tail). Cheap and
        exception-free by contract: watchdog beat, rollup ingest, board
        push/pull (rate-limited), one scoring window, verdict latching.
        Actuation (the raise) happens later, at ``start_quorum``."""
        try:
            self._on_step(step, committed, participants)
        except Exception:  # noqa: BLE001 — observability must not wound
            logger.exception("health on_step failed (ignored)")

    def _on_step(
        self, step: int, committed: bool, participants: Optional[int]
    ) -> None:
        assert self._watchdog is not None
        self._watchdog.beat()
        if participants is not None:
            self._participants = int(participants)
        journal = self._journal()
        self.scorer.ingest_rollup(journal.phase_rollup())
        now = self._wall()
        push_due = now - self._last_push >= self._push_interval
        if push_due:
            self._last_push = now
            self._pull_peers()
        verdict = self.scorer.evaluate()
        for phase, ratio in verdict["ratios"].items():
            metrics.set_gauge(
                "tpuft_health_phase_ratio", ratio, phase=phase, **self._labels
            )
        self._update_accusation()
        latched = False
        with self._lock:
            latched = self._eject_reason is not None
        if not latched:
            if verdict["degraded"]:
                self._latch_degraded(step, verdict)
            elif self.state in (STATE_HEALTHY, STATE_SUSPECT, STATE_DEGRADED):
                if verdict["streak"] > 0:
                    self._set_state(STATE_SUSPECT)
                else:
                    self._set_state(STATE_HEALTHY)
                    self._refusal_counted = False
        if push_due:
            # Pushed AFTER the window so peers (and fleet_status) see the
            # freshest EWMAs/state/accusation, not last window's.
            self._push_snapshot()

    def _update_accusation(self) -> None:
        accusation = self.scorer.accuse()
        accused = accusation[0] if accusation else None
        if accused == self.replica_id:
            accused = None  # self-blame rides the verdict plane instead
        if accused != self._accused:
            if self._accused is not None:
                metrics.set_gauge(
                    "tpuft_health_accuse", 0, accused=self._accused, **self._labels
                )
            if accused is not None:
                metrics.set_gauge(
                    "tpuft_health_accuse", 1, accused=accused, **self._labels
                )
                metrics.inc("tpuft_health_accusations_total", **self._labels)
                self._journal().record(
                    "health_accuse",
                    accused=accused,
                    gap_s=round(accusation[1], 4) if accusation else 0.0,
                )
            self._accused = accused

    def _latch_degraded(self, step: int, verdict: Dict[str, Any]) -> None:
        """A degraded verdict: eject unless that would drop the quorum
        below min_replica_size — then refuse (counted once per latch)
        and keep training degraded; re-checked every window so a later
        join unlocks the ejection."""
        if self._participants and self._participants - 1 < self.min_replica_size:
            self._set_state(STATE_DEGRADED)
            if not self._refusal_counted:
                self._refusal_counted = True
                metrics.inc(
                    "tpuft_health_ejections_refused_total", **self._labels
                )
                self._journal().record(
                    "health_ejection_refused",
                    participants=self._participants,
                    min_replica=self.min_replica_size,
                    ratios=json.dumps(verdict["ratios"]),
                )
                logger.warning(
                    "degraded verdict for %s REFUSED: ejecting would drop "
                    "participants %d below min_replica_size %d; training "
                    "continues degraded",
                    self.replica_id, self._participants, self.min_replica_size,
                )
            return
        metrics.inc("tpuft_health_verdicts_total", **self._labels)
        self._set_state(STATE_DEGRADED)
        reason = (
            f"self-verdict: phases {verdict['ratios']} beyond "
            f"{self.scorer.threshold}x the fleet median for "
            f"{verdict['streak']} consecutive windows"
        )
        self._journal().record(
            "health_verdict",
            step=step,
            streak=verdict["streak"],
            ratios=json.dumps(verdict["ratios"]),
            peers=verdict["peers"],
        )
        with self._lock:
            self._eject_reason = reason

    # -- wedge path ----------------------------------------------------------

    def _on_wedge(self, elapsed: float, deadline: float) -> None:
        """Watchdog thread: the train thread is presumed stuck, so this
        path must complete the accounting itself (record, report, dump)
        and then escalate. Default escalation is SIGTERM to our own
        process (``TPUFT_HEALTH_WEDGE_ACTION=term``) — the supervisor
        restarts us and the quarantine gate re-probes; ``flag`` only
        latches the ejection for the next step boundary (thread drills,
        and fleets whose wedges are known to resolve)."""
        reason = (
            f"step-progress watchdog: no step in {elapsed:.1f}s "
            f"(deadline {deadline:.1f}s from the replica's own cadence)"
        )
        metrics.inc("tpuft_health_wedge_trips_total", **self._labels)
        journal = self._journal()
        journal.record(
            "health_wedge", elapsed_s=round(elapsed, 3),
            deadline_s=round(deadline, 3),
        )
        tracing.open_incident(
            "health_wedge", journal.step, journal.quorum_id,
            journal=journal, reason=reason,
        )
        self.gate.record_ejection(reason)
        metrics.inc("tpuft_health_ejections_total", **self._labels)
        self._set_state(STATE_QUARANTINED)
        with self._lock:
            self._eject_reason = reason
            # The accounting above already happened; the (possibly
            # unreachable) train thread's note_ejected must not repeat it.
            self._ejection_recorded = True
        if self._report_error is not None:
            try:
                self._report_error(DegradedReplicaError(reason))
            except Exception:  # noqa: BLE001
                pass
        # Injected wedges clear like a process restart would; a REAL
        # wedge needs the hard escalation below to unpark the replica.
        clear_injected(self.replica_id)
        action = self._wedge_action
        if action is not None:
            try:
                action()
            except Exception:  # noqa: BLE001
                logger.exception("wedge escalation callback failed")
            return
        if os.environ.get(ENV_WEDGE_ACTION, "term") == "term":
            logger.error(
                "wedged replica %s: SIGTERM to self for supervisor restart "
                "(%s)", self.replica_id, reason,
            )
            os.kill(os.getpid(), signal.SIGTERM)

    # -- actuation (manager's start_quorum) -----------------------------------

    def should_eject(self) -> Optional[str]:
        with self._lock:
            return self._eject_reason

    def note_ejected(self, reason: str) -> None:
        """Called by the manager right before the DegradedReplicaError
        raise: persist the ejection for the restarted process's gate,
        count it, stamp the incident, and clear this replica's injected
        gray faults (the thread-drill analogue of the process dying).
        Idempotent with the wedge path's own accounting."""
        with self._lock:
            already = self._ejection_recorded
            self._ejection_recorded = False
        if not already:
            self.gate.record_ejection(reason)
            metrics.inc("tpuft_health_ejections_total", **self._labels)
        journal = self._journal()
        journal.record("health_ejection", reason=reason)
        tracing.open_incident(
            "health_ejection", journal.step, journal.quorum_id,
            journal=journal, reason=reason,
        )
        self._set_state(STATE_QUARANTINED)
        clear_injected(self.replica_id)

    def serve_quarantine_if_pending(self) -> Optional[Dict[str, Any]]:
        """The startup gate (Manager construction / bench rejoin): a
        replica with a recent ejection on file proves itself healthy —
        probe with backoff, park if crash-looping — before it may rejoin
        the fleet. Returns the served record, or None when clean."""
        if not self.gate.pending():
            return None
        self._set_state(
            STATE_PARKED if self.gate.parked_until() > 0 else STATE_QUARANTINED
        )
        record = self.gate.serve(trace=self._journal())
        self._set_state(STATE_HEALTHY)
        with self._lock:
            self._eject_reason = None
            self._ejection_recorded = False
        # Re-admission scores fresh, like the restarted process it
        # models: evidence gathered while degraded/wedged (e.g. the
        # blocked sync's huge sample) must not re-verdict a healthy
        # comeback.
        self.scorer.ewma.clear()
        self.scorer.counts.clear()
        self.scorer.streak = 0
        self._refusal_counted = False
        self._journal().record("health_rejoin", **record)
        return record

    def stop(self) -> None:
        if self._watchdog is not None:
            self._watchdog.stop()
