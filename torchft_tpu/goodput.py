"""Fleet goodput ledger: per-second badput attribution over the trace ring.

The observability planes before this one answer *what happened* (tracing's
causal timeline, metrics' cumulative counters, telemetry's event records).
None answers the question a production fleet is judged by: what fraction of
paid wall-clock became committed training progress, and which subsystem ate
the rest? This module adds that currency:

- :func:`fold_events` — a conservation-exact fold over trace-ring events
  that partitions a ``[t0, t1]`` monotonic window into exactly one of the
  :data:`BUCKETS` per elementary segment, so the buckets sum to the
  wall-clock width by construction. It is a *fold over the existing ring*
  (tracing.py already tags every FT phase), never new hot-path
  instrumentation; the per-event cost is pinned <= 5 us by a unit test.
- :class:`GoodputLedger` — closes windows on the metrics-push cadence,
  retains them in a byte-budgeted :class:`metrics.WindowedSeries` ring so
  rates are queryable live, counts ``tpuft_goodput_*``, and builds the
  ``goodput`` payload each Manager pushes through the quorum store
  (feeding fleet_status's GOODPUT column and
  ``scripts/goodput_report.py``).
- :class:`SloEvaluator` — declarative burn-rate alerting
  (``TPUFT_SLO_GOODPUT=0.95`` style) with the health plane's K-consecutive
  -windows hysteresis: a window "burns" when badput spends the error
  budget faster than ``TPUFT_SLO_BURN_RATE``; K consecutive burning
  windows latch exactly ONE breach (telemetry record on the ``tpuft_slo``
  logger + ``slo_breach`` trace event + incident auto-dump), re-armed
  only by a healthy window. Alerting, never actuation — the health plane
  (health.py) owns ejection; this plane only pages.
- :func:`merge_windows` — merges per-replica pushed payloads into one
  fleet goodput number + per-cause and per-region badput breakdowns.

Attribution model: mapped trace SPANS claim their interval (overlaps
resolve by fixed priority — a heal stripe inside a quorum wait is heal
time), and the time *between* spans is ambient: attributed to the next
outcome instant at-or-after the segment (``commit`` -> committed compute;
``commit_failed``/``rollback``/``speculation_discarded`` -> rollback
recompute), or ``idle`` when no outcome follows in the window — so a dead
replica's post-death window honestly reads idle, and device dispatch /
wire time leading into a commit counts as the committed compute it was.
A joiner's ``heal_recv`` start additionally fences the lookahead
(:data:`BOUNDARY_SPANS`): dead time before a heal reads idle even when
the healed replica commits later in the same window.

Docs: docs/observability.md section 0; METRICS.md rows; reference framing
per PAPERS.md availability accounting (goodput, not step counts).
"""

from __future__ import annotations

import math
import os
from bisect import bisect_left
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from torchft_tpu import metrics, telemetry, tracing

__all__ = [
    "BUCKETS",
    "SPAN_BUCKETS",
    "OUTCOME_BUCKETS",
    "BOUNDARY_SPANS",
    "ENV_WINDOW_SEC",
    "ENV_WINDOWS",
    "ENV_BYTES",
    "ENV_SLO_GOODPUT",
    "ENV_SLO_WINDOWS",
    "ENV_SLO_BURN_RATE",
    "fold_events",
    "top_badput",
    "GoodputLedger",
    "SloEvaluator",
    "merge_windows",
]

ENV_WINDOW_SEC = "TPUFT_GOODPUT_WINDOW_SEC"
ENV_WINDOWS = "TPUFT_GOODPUT_WINDOWS"
ENV_BYTES = "TPUFT_GOODPUT_BYTES"
ENV_SLO_GOODPUT = "TPUFT_SLO_GOODPUT"
ENV_SLO_WINDOWS = "TPUFT_SLO_WINDOWS"
ENV_SLO_BURN_RATE = "TPUFT_SLO_BURN_RATE"

# Every second of every replica's wall-clock lands in exactly one of these.
BUCKETS: Tuple[str, ...] = (
    "committed_compute",
    "commit_wait",
    "quorum_wait",
    "drain",
    "heal_donor",
    "heal_joiner",
    "rollback_recompute",
    "degraded",
    "idle",
)

# Span name -> bucket, priority-ordered (first listed wins an overlap): a
# heal stripe served while parked in a quorum wait is heal time, a drain
# inside a quorum round is drain time. Spans NOT listed here (device_sync,
# update_dispatch, wire_bucket, ...) stay ambient on purpose — dispatch and
# wire time leading into a commit IS the committed compute being paid for.
SPAN_BUCKETS: Tuple[Tuple[str, str], ...] = (
    ("heal_recv", "heal_joiner"),
    ("heal_send", "heal_donor"),
    ("pipeline_drain", "drain"),
    ("zero_rebalance", "drain"),
    ("health_quarantine", "degraded"),
    ("quorum", "quorum_wait"),
    ("pg_configure", "quorum_wait"),
    ("commit_barrier", "commit_wait"),
)

# Outcome instants that classify the ambient time leading up to them.
OUTCOME_BUCKETS: Dict[str, str] = {
    "commit": "committed_compute",
    "commit_failed": "rollback_recompute",
    "rollback": "rollback_recompute",
    "speculation_discarded": "rollback_recompute",
}

# Spans whose START is an attribution boundary: ambient time leading into a
# joiner's heal was LOST time (the process died/restarted/desynced — that
# is why it is healing), so it reads idle even when a post-heal commit
# follows in the same window. Donor-side heal_send is deliberately NOT a
# boundary: the donor's preceding ambient time was compute toward its own
# commit.
BOUNDARY_SPANS: Tuple[str, ...] = ("heal_recv",)

_RANK_BUCKET: Tuple[str, ...] = tuple(
    bucket for _, bucket in SPAN_BUCKETS
)
_SPAN_RANK: Dict[str, int] = {
    name: rank for rank, (name, _) in enumerate(SPAN_BUCKETS)
}
_N_RANKS = len(SPAN_BUCKETS)
_QUARANTINE_RANK = _SPAN_RANK["health_quarantine"]


def _env_float(name: str, default: float, floor: Optional[float] = None) -> float:
    try:
        value = float(os.environ.get(name, "") or default)
    except ValueError:
        value = default
    if floor is not None and value < floor:
        value = default
    return value


def _env_int(name: str, default: int, floor: Optional[int] = None) -> int:
    try:
        value = int(os.environ.get(name, "") or default)
    except ValueError:
        value = default
    if floor is not None and value < floor:
        value = default
    return value


def fold_events(
    events: Iterable[Dict[str, Any]], t0: float, t1: float
) -> Dict[str, float]:
    """Attributes the monotonic window ``[t0, t1]`` to :data:`BUCKETS`.

    Conservation-exact by construction: the window is cut at every mapped
    span edge and outcome instant, and each elementary segment is assigned
    exactly one bucket (highest-priority covering span, else the ambient
    rule above), so ``sum(result.values()) == t1 - t0`` to float epsilon.
    Events outside the window are ignored; spans straddling an edge are
    clipped. Tolerates ring drops (lost spans degrade to ambient time,
    never to a non-conserving total) and legacy quarantine ``served``
    instants that carry ``waited_s`` instead of a real span.
    """
    out = dict.fromkeys(BUCKETS, 0.0)
    if t1 <= t0:
        return out
    span_rank = _SPAN_RANK
    outcome_bucket = OUTCOME_BUCKETS
    marks: List[Tuple[float, int, int]] = []
    outcomes: List[Tuple[float, str]] = []
    for e in events:
        tm = e.get("t_mono")
        if tm is None:
            continue
        name = e.get("name")
        if e.get("ph") == "X":
            rank = span_rank.get(name)
            if rank is None:
                continue
            start = tm
            end = tm + float(e.get("dur") or 0.0)
        else:
            bucket = outcome_bucket.get(name)
            if bucket is not None:
                if t0 <= tm <= t1:
                    outcomes.append((tm, bucket))
                continue
            if name != "health_quarantine":
                continue
            args = e.get("args") or {}
            if args.get("phase") != "served":
                continue
            # Legacy journals recorded the quarantine serve as an instant
            # carrying waited_s; newer ones record the real span (which
            # takes the ph == "X" branch above).
            try:
                waited = float(args.get("waited_s") or 0.0)
            except (TypeError, ValueError):
                continue
            start = tm - waited
            end = tm
            rank = _QUARANTINE_RANK
        if end <= t0 or start >= t1:
            continue
        if start < t0:
            start = t0
        if end > t1:
            end = t1
        if end <= start:
            continue
        marks.append((start, 1, rank))
        marks.append((end, -1, rank))
        if name in BOUNDARY_SPANS:
            # The heal start fences the ambient lookahead: whatever the
            # replica was doing before it needed a heal, it did not commit.
            outcomes.append((start, "idle"))

    cut_set = {t0, t1}
    for t, _, _ in marks:
        cut_set.add(t)
    for t, _ in outcomes:
        cut_set.add(t)
    cuts = sorted(cut_set)
    marks.sort()
    outcomes.sort()
    otimes = [t for t, _ in outcomes]
    n_outcomes = len(otimes)
    counts = [0] * _N_RANKS
    mi = 0
    n_marks = len(marks)
    rank_bucket = _RANK_BUCKET
    for i in range(len(cuts) - 1):
        a = cuts[i]
        b = cuts[i + 1]
        while mi < n_marks and marks[mi][0] <= a:
            mark = marks[mi]
            counts[mark[2]] += mark[1]
            mi += 1
        bucket = None
        for rank in range(_N_RANKS):
            if counts[rank] > 0:
                bucket = rank_bucket[rank]
                break
        if bucket is None:
            # Ambient: the next outcome at-or-after this segment's end
            # names what the time was spent becoming; none -> idle.
            j = bisect_left(otimes, b)
            bucket = outcomes[j][1] if j < n_outcomes else "idle"
        out[bucket] += b - a
    return out


def top_badput(
    seconds: Dict[str, float], n: int = 2
) -> List[Tuple[str, float]]:
    """The ``n`` largest non-goodput buckets, largest first (zeros omitted)."""
    items = [
        (bucket, value)
        for bucket, value in seconds.items()
        if bucket != "committed_compute" and value > 0
    ]
    items.sort(key=lambda kv: (-kv[1], kv[0]))
    return items[:n]


class SloEvaluator:
    """Windowed goodput SLO with burn-rate hysteresis (health.py style).

    One :meth:`observe` per closed ledger window. ``burn_rate = badput /
    (1 - target)`` — the classic multi-window burn-rate framing: 1.0 means
    spending the error budget exactly at the sustained-violation rate,
    ``TPUFT_SLO_BURN_RATE`` scales the trip point. K consecutive burning
    windows (``TPUFT_SLO_WINDOWS``) latch exactly one breach — telemetry
    record on :data:`telemetry.slo_logger`, an ``slo_breach`` trace event,
    ``tpuft_slo_breaches_total``, and an incident auto-dump
    (:func:`tracing.open_incident`, kind ``slo_goodput``) — then stay
    latched until a healthy window re-arms, so a sustained burn pages once
    and a single-window blip never pages at all. Alerting only: nothing
    here ejects, raises past the step boundary, or touches actuation.
    """

    def __init__(
        self,
        target: float,
        windows: int = 3,
        burn_threshold: float = 1.0,
        labels: Optional[Dict[str, str]] = None,
    ) -> None:
        if not 0.0 < float(target) <= 1.0:
            raise ValueError(f"SLO goodput target must be in (0, 1]: {target}")
        self.target = float(target)
        self.windows = max(1, int(windows))
        self.burn_threshold = float(burn_threshold)
        self.streak = 0
        self.latched = False
        self.breaches = 0
        self.last_burn_rate: float = 0.0
        self._labels = dict(labels or {})

    @classmethod
    def from_env(
        cls, labels: Optional[Dict[str, str]] = None
    ) -> Optional["SloEvaluator"]:
        """Builds the evaluator from ``TPUFT_SLO_*``; None when the SLO is
        unset or unparsable (doctor names the offender — a bad env must
        degrade to no-alerting, never break training)."""
        raw = os.environ.get(ENV_SLO_GOODPUT, "")
        if not raw:
            return None
        try:
            target = float(raw)
        except ValueError:
            return None
        if not 0.0 < target <= 1.0:
            return None
        return cls(
            target,
            windows=_env_int(ENV_SLO_WINDOWS, 3, floor=1),
            burn_threshold=_env_float(ENV_SLO_BURN_RATE, 1.0, floor=1e-9),
            labels=labels,
        )

    def observe(
        self,
        goodput: float,
        step: int = 0,
        quorum_id: int = -1,
        journal: Optional["tracing.TraceJournal"] = None,
    ) -> bool:
        """Scores one closed window; True when THIS window latches a breach."""
        budget = 1.0 - self.target
        badput = max(0.0, 1.0 - float(goodput))
        if budget <= 0.0:
            burn = math.inf if badput > 0 else 0.0
        else:
            burn = badput / budget
        self.last_burn_rate = burn
        metrics.set_gauge(
            "tpuft_slo_burn_rate",
            burn if math.isfinite(burn) else 1e9,
            slo="goodput",
            **self._labels,
        )
        burning = burn > self.burn_threshold
        if not burning:
            # A healthy window resets the streak AND re-arms the latch —
            # the next sustained burn pages again, a blip still cannot.
            self.streak = 0
            self.latched = False
            metrics.set_gauge(
                "tpuft_slo_burn_streak", 0, slo="goodput", **self._labels
            )
            return False
        self.streak += 1
        metrics.set_gauge(
            "tpuft_slo_burn_streak", self.streak, slo="goodput", **self._labels
        )
        if self.streak < self.windows or self.latched:
            return False
        self.latched = True
        self.breaches += 1
        self._fire(float(goodput), burn, step, quorum_id, journal)
        return True

    def _fire(
        self,
        goodput: float,
        burn: float,
        step: int,
        quorum_id: int,
        journal: Optional["tracing.TraceJournal"],
    ) -> None:
        j = journal or tracing.current()
        burn_out = round(burn, 4) if math.isfinite(burn) else "inf"
        metrics.inc("tpuft_slo_breaches_total", slo="goodput", **self._labels)
        try:
            telemetry.slo_logger.info(
                "slo_breach",
                extra={
                    "job_id": j.job_id,
                    "replica_id": j.replica_id,
                    "rank": j.group_rank,
                    "quorum_id": quorum_id,
                    "step": step,
                    "slo": "goodput",
                    "slo_target": self.target,
                    "burn_rate": burn_out,
                    "goodput": round(goodput, 6),
                    "windows": self.streak,
                },
            )
        except Exception:  # noqa: BLE001 — exporter failures never escape
            pass
        j.record(
            "slo_breach",
            cat="slo",
            step=step,
            quorum_id=quorum_id,
            slo="goodput",
            target=self.target,
            burn_rate=burn_out,
            goodput=round(goodput, 6),
            windows=self.streak,
        )
        tracing.open_incident(
            "slo_goodput",
            step,
            quorum_id,
            journal=j,
            reason=(
                f"goodput {goodput:.4f} below target {self.target} for "
                f"{self.streak} consecutive windows (burn {burn_out})"
            ),
        )

    def status(self) -> Dict[str, Any]:
        return {
            "slo": "goodput",
            "target": self.target,
            "windows": self.windows,
            "burn_threshold": self.burn_threshold,
            "burn_rate": (
                round(self.last_burn_rate, 4)
                if math.isfinite(self.last_burn_rate)
                else None
            ),
            "streak": self.streak,
            "latched": self.latched,
            "breaches": self.breaches,
        }


class GoodputLedger:
    """Per-replica goodput accounting riding the metrics-push cadence.

    Holds an open window starting where the last one closed; ``collect``
    (called from ``Manager._push_metrics``, i.e. every push) closes it
    once it is at least ``TPUFT_GOODPUT_WINDOW_SEC`` wide, folds the trace
    ring over it, retains the window in a byte-budgeted
    :class:`metrics.WindowedSeries`, counts ``tpuft_goodput_seconds_total``
    per bucket, gauges the rolling ``tpuft_goodput_fraction``, scores the
    SLO, and returns the store-push payload. All clocks come from the
    journal (injectable), so threads-as-replicas drills replay scripted
    timelines deterministically. With the trace plane disabled
    (``TPUFT_TRACE=0``) the ledger degrades to an explicit
    ``{"enabled": False}`` payload — never a silently-idle fleet.
    """

    def __init__(
        self,
        journal: Optional["tracing.TraceJournal"] = None,
        window_sec: Optional[float] = None,
        max_windows: Optional[int] = None,
        max_bytes: Optional[int] = None,
        slo: Optional[SloEvaluator] = None,
        labels: Optional[Dict[str, str]] = None,
    ) -> None:
        self._journal = journal if journal is not None else tracing.current()
        self._window_sec = (
            window_sec
            if window_sec is not None
            else _env_float(ENV_WINDOW_SEC, 5.0, floor=1e-3)
        )
        self._series = metrics.WindowedSeries(
            max_windows=(
                max_windows
                if max_windows is not None
                else _env_int(ENV_WINDOWS, 60, floor=1)
            ),
            max_bytes=(
                max_bytes
                if max_bytes is not None
                else _env_int(ENV_BYTES, 262144, floor=1024)
            ),
        )
        self._slo = slo if slo is not None else SloEvaluator.from_env(labels)
        self._labels = dict(labels or {})
        self._t0 = self._journal._mono()
        self._totals = dict.fromkeys(BUCKETS, 0.0)

    @property
    def slo(self) -> Optional[SloEvaluator]:
        return self._slo

    @property
    def series(self) -> "metrics.WindowedSeries":
        return self._series

    def collect(
        self,
        now_mono: Optional[float] = None,
        step: Optional[int] = None,
        quorum_id: Optional[int] = None,
        force: bool = False,
    ) -> Dict[str, Any]:
        """Closes the open window when due (or ``force``); returns the
        payload either way. Never raises — this rides the metrics push."""
        journal = self._journal
        if not journal.enabled:
            return {"enabled": False}
        try:
            now = journal._mono() if now_mono is None else now_mono
            due = (now - self._t0) >= self._window_sec
            if (due or force) and now > self._t0:
                self._close_window(now, step, quorum_id)
        except Exception:  # noqa: BLE001 — observability must not wound
            pass
        return self.payload()

    def _close_window(
        self, now: float, step: Optional[int], quorum_id: Optional[int]
    ) -> None:
        journal = self._journal
        seconds = fold_events(journal._copy_ring(), self._t0, now)
        duration = now - self._t0
        goodput = seconds["committed_compute"] / duration if duration > 0 else 0.0
        window = {
            "t0": round(self._t0, 6),
            "t1": round(now, 6),
            "wall": journal._wall(),
            "step": journal.step if step is None else step,
            "goodput": round(goodput, 6),
            "seconds": {b: round(s, 6) for b, s in seconds.items() if s > 0},
        }
        self._t0 = now
        self._series.append(window)
        for bucket, value in seconds.items():
            self._totals[bucket] += value
            if value > 0:
                metrics.inc(
                    "tpuft_goodput_seconds_total",
                    value,
                    bucket=bucket,
                    **self._labels,
                )
        metrics.inc("tpuft_goodput_windows_total", **self._labels)
        metrics.set_gauge(
            "tpuft_goodput_series_bytes",
            self._series.total_bytes(),
            **self._labels,
        )
        rolling = self.rolling_goodput()
        if rolling is not None:
            metrics.set_gauge(
                "tpuft_goodput_fraction", rolling, **self._labels
            )
        if self._slo is not None:
            self._slo.observe(
                goodput,
                step=window["step"],
                quorum_id=(
                    journal.quorum_id if quorum_id is None else quorum_id
                ),
                journal=journal,
            )

    def _aggregate(self) -> Dict[str, float]:
        agg = dict.fromkeys(BUCKETS, 0.0)
        for window in self._series.windows():
            for bucket, value in (window.get("seconds") or {}).items():
                if bucket in agg:
                    agg[bucket] += value
        return agg

    def rolling_goodput(self) -> Optional[float]:
        """Goodput fraction over the retained window ring (None until the
        first window closes) — the stable headline the GOODPUT column and
        the bench line read, vs. a single window's noise."""
        agg = self._aggregate()
        total = sum(agg.values())
        if total <= 0:
            return None
        return agg["committed_compute"] / total

    def payload(self, max_windows: int = 30) -> Dict[str, Any]:
        """The store-push / report payload: rolling aggregate + the most
        recent windows (bounded — the series ring itself is the live
        local view, the push only needs enough for fleet merging)."""
        if not self._journal.enabled:
            return {"enabled": False}
        agg = self._aggregate()
        total = sum(agg.values())
        payload: Dict[str, Any] = {
            "enabled": True,
            "window_sec": self._window_sec,
            "goodput": round(agg["committed_compute"] / total, 6)
            if total > 0
            else None,
            "seconds": {b: round(s, 6) for b, s in agg.items() if s > 0},
            "totals": {
                b: round(s, 6) for b, s in self._totals.items() if s > 0
            },
            "windows": self._series.windows()[-max_windows:],
        }
        if self._slo is not None:
            payload["slo"] = self._slo.status()
        return payload


def merge_windows(
    snapshots: Sequence[Dict[str, Any]]
) -> Dict[str, Any]:
    """Merges per-replica goodput payloads into one fleet accounting.

    ``snapshots`` are metrics-push snapshot dicts (``{"replica_id", ...,
    "region", "goodput": payload}`` as fleet_status collects them) or bare
    ledger payloads. Returns fleet totals, the fleet goodput fraction, a
    per-cause badput breakdown (largest first), and per-region /
    per-replica splits (regions ride the PR-16 topology labels)."""
    agg = dict.fromkeys(BUCKETS, 0.0)
    regions: Dict[str, Dict[str, float]] = {}
    per_replica: Dict[str, Dict[str, Any]] = {}
    replicas = 0
    for snap in snapshots:
        if not isinstance(snap, dict):
            continue
        nested = snap.get("goodput")
        payload = nested if isinstance(nested, dict) else snap
        if not payload.get("enabled", True):
            continue
        seconds = payload.get("seconds") or {}
        if not isinstance(seconds, dict) or not seconds:
            continue
        replicas += 1
        replica_id = str(snap.get("replica_id", f"replica{replicas}"))
        region = str(snap.get("region") or "unknown")
        region_agg = regions.setdefault(region, dict.fromkeys(BUCKETS, 0.0))
        local = dict.fromkeys(BUCKETS, 0.0)
        for bucket, value in seconds.items():
            if bucket in agg:
                value = float(value)
                agg[bucket] += value
                region_agg[bucket] += value
                local[bucket] += value
        local_total = sum(local.values())
        per_replica[replica_id] = {
            "region": region,
            "goodput": round(local["committed_compute"] / local_total, 6)
            if local_total > 0
            else None,
            "seconds": {b: round(s, 6) for b, s in local.items() if s > 0},
        }
    total = sum(agg.values())
    badput = [
        {
            "bucket": bucket,
            "seconds": round(value, 6),
            "fraction": round(value / total, 6) if total > 0 else 0.0,
        }
        for bucket, value in top_badput(agg, n=len(BUCKETS))
    ]
    region_out = {}
    for region, region_agg in sorted(regions.items()):
        region_total = sum(region_agg.values())
        region_out[region] = {
            "goodput": round(
                region_agg["committed_compute"] / region_total, 6
            )
            if region_total > 0
            else None,
            "seconds": {
                b: round(s, 6) for b, s in region_agg.items() if s > 0
            },
        }
    return {
        "replicas": replicas,
        "wall_seconds": round(total, 6),
        "goodput": round(agg["committed_compute"] / total, 6)
        if total > 0
        else None,
        "seconds": {b: round(s, 6) for b, s in agg.items() if s > 0},
        "badput": badput,
        "regions": region_out,
        "per_replica": per_replica,
    }
