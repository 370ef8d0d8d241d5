"""Tracing/profiling spans + chrome-trace export.

Role-equivalent of the reference's ``torch.profiler.record_function`` spans
on every manager phase (manager.py:385-827), the ``_time``/``_timeit``
transfer logs (http_transport.py:31-36), and its chrome-trace export loops
(train_ddp.py:159-174): spans emit ``jax.profiler.TraceAnnotation`` markers
(TensorBoard/perfetto timeline when a ``jax.profiler.trace`` capture is
active), optionally log wall time when ``TPUFT_TRACE_LOG`` is set, and —
when a :func:`chrome_trace` capture is active — record begin/end events
into a self-contained ``trace.json`` loadable in ``chrome://tracing`` or
https://ui.perfetto.dev. The span itself is ``tracing.phase``'s (one
primitive for every span of the tree); :func:`trace_span` is its form for
a span that has an annotation name and no table entry.
"""

from __future__ import annotations

import json
import logging
import os
import threading
from contextlib import contextmanager
from typing import Any, Generator, List

from torchft_tpu import tracing

logger = logging.getLogger("torchft_tpu.trace")


class _ChromeCapture:
    """One active chrome-trace capture: the event list plus per-thread
    bookkeeping so each thread's FIRST span also emits a ``thread_name``
    metadata ("M") event — without it the pipelined-commit spans (which
    resolve on the tpuft_quorum executor and the PG op-worker threads)
    interleave as anonymous numeric tids in chrome://tracing."""

    def __init__(self) -> None:
        self.events: List[dict] = []
        self.lock = threading.Lock()
        self._named_tids: set = set()

    def add_span(self, name: str, start: float, elapsed: float, args: dict) -> None:
        thread = threading.current_thread()
        tid = threading.get_ident() % 2**31
        event = {
            "name": name,
            "ph": "X",
            "ts": start * 1e6,
            "dur": elapsed * 1e6,
            "pid": os.getpid(),
            "tid": tid,
            "cat": "tpuft",
        }
        if args:
            event["args"] = args
        with self.lock:
            if tid not in self._named_tids:
                self._named_tids.add(tid)
                self.events.append(
                    {
                        "name": "thread_name",
                        "ph": "M",
                        "pid": os.getpid(),
                        "tid": tid,
                        "args": {"name": thread.name},
                    }
                )
            self.events.append(event)


@contextmanager
def chrome_trace(path: str) -> Generator[None, None, None]:
    """Captures every :func:`trace_span` in the with-body as chrome-trace
    "X" (complete) events — plus one ``thread_name`` metadata event per
    emitting thread — and writes them to ``path`` on exit. Captures may
    nest/overlap (the previous capture is restored on exit); spans still
    open on other threads when the capture ends record into the old list
    harmlessly (they are not in the written file)."""
    capture = _ChromeCapture()
    previous = tracing._chrome_capture
    tracing._chrome_capture = capture
    try:
        yield
    finally:
        tracing._chrome_capture = previous
        with capture.lock:
            snapshot = list(capture.events)
        # Fleet-merge metadata: stamp the trace plane's replica identity
        # and last store-sampled clock offset onto the capture, so a
        # single-process chrome trace drops cleanly into a merged fleet
        # timeline (scripts/fleet_trace.py shifts by clock_offset_ms and
        # keys tracks by replica_id) instead of arriving as an anonymous
        # pid with an unaligned clock.
        other_data: dict = {}
        try:
            journal = tracing.current()
            offset_ms = (
                round(journal.clock_offset_s * 1e3, 3)
                if journal.clock_offset_s is not None
                else None
            )
            other_data = {
                "replica_id": journal.replica_id,
                "group_rank": journal.group_rank,
                "clock_offset_ms": offset_ms,
            }
            snapshot.insert(
                0,
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": os.getpid(),
                    "args": {
                        "name": f"{journal.replica_id}/{journal.group_rank}"
                    },
                },
            )
            for event in snapshot:
                if event.get("ph") == "X":
                    event.setdefault("args", {}).setdefault(
                        "replica_id", journal.replica_id
                    )
        except Exception:  # noqa: BLE001 — profiling must never break training
            pass
        with open(path, "w") as f:
            json.dump(
                {
                    "traceEvents": snapshot,
                    "displayTimeUnit": "ms",
                    "otherData": other_data,
                },
                f,
            )
        logger.info(
            "chrome trace with %d events written to %s", len(snapshot), path
        )


def trace_span(name: str, **args: "int | float | str") -> Any:
    """Marks a region on the jax profiler timeline (no-op cost when no
    capture is active) and on any active :func:`chrome_trace` capture.
    ``args`` (e.g. ``step=``, ``quorum_id=``) land in the chrome event's
    ``args`` dict, and the identifiers among them on the annotation, so a
    merged kill/heal trace stays correlatable across the train-loop /
    quorum / op-worker threads."""
    return tracing._Span(tracing._PhaseSpec(None, name), None, None, args)


def heal_wall_times(kill_t: "float | None", commit_times: dict) -> "dict | None":
    """Kill → first-committed-step wall time per replica group, the
    operator-facing recovery number (BASELINE.md north stars time-bound
    what steps_lost_per_kill only counts). ``commit_times`` maps group
    index → monotonic commit timestamps; group 0 is labeled the survivor
    and group 1 the joiner (the drills' kill target), higher groups keep
    an index label. Returns None when no kill happened; a group with no
    commit after the kill reports None for its role."""
    if kill_t is None:
        return None
    out = {}
    for idx, times in sorted(commit_times.items()):
        after = [t for t in times if t > kill_t]
        role = "joiner" if idx == 1 else ("survivor" if idx == 0 else f"g{idx}")
        out[role] = round(min(after) - kill_t, 3) if after else None
    return out
