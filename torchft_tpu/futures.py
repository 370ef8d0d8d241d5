"""Fault-tolerant future/timeout engine.

Role-equivalent of the reference's ``torchft/futures.py``: a singleton
timer service that can bound any future or code region with a deadline, plus
a watchdog thread that hard-exits the process if the timer service itself
wedges — the last line of defense against undetectable hangs
(/root/reference/torchft/futures.py:97-120).

CUDA-event timeouts don't apply on TPU; the JAX analogue of "did the step
finish" is a ``jax.block_until_ready`` bounded by :func:`context_timeout`.

Env: ``TPUFT_WATCHDOG_TIMEOUT_SEC`` (default 30).
"""

from __future__ import annotations

import heapq
import itertools
import os
import sys
import threading
import time
from concurrent.futures import Future
from contextlib import contextmanager
from typing import Any, Callable, Generator, Optional

__all__ = [
    "future_timeout",
    "future_wait",
    "context_timeout",
    "stream_timeout",
    "CommitPipeline",
]

WATCHDOG_TIMEOUT_SEC = float(os.environ.get("TPUFT_WATCHDOG_TIMEOUT_SEC", "30"))


class _TimerHandle:
    __slots__ = ("cancelled",)

    def __init__(self) -> None:
        self.cancelled = False

    def cancel(self) -> None:
        self.cancelled = True


class _TimeoutManager:
    """Single scheduler thread firing deadline callbacks, watched by a
    watchdog that hard-exits the process if the scheduler stalls."""

    def __init__(self) -> None:
        self._lock = threading.Condition()
        self._heap: list = []  # (deadline, seq, handle, callback)
        self._seq = itertools.count()
        self._thread: Optional[threading.Thread] = None
        self._watchdog: Optional[threading.Thread] = None
        self._last_tick = time.monotonic()
        self._watchdog_enabled = True

    def _ensure_started(self) -> None:
        with self._lock:
            if self._thread is not None:
                return
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="tpuft-timeout-manager"
            )
            self._thread.start()
            self._watchdog = threading.Thread(
                target=self._run_watchdog, daemon=True, name="tpuft-watchdog"
            )
            self._watchdog.start()

    def schedule(self, delay: float, callback: Callable[[], None]) -> _TimerHandle:
        self._ensure_started()
        handle = _TimerHandle()
        deadline = time.monotonic() + delay
        with self._lock:
            heapq.heappush(self._heap, (deadline, next(self._seq), handle, callback))
            self._note_heap()
            self._lock.notify()
        return handle

    def _note_heap(self) -> None:
        """The heap's length as a gauge (called under the lock). Cancelling
        a handle only marks it: the entry, its callback and whatever the
        callback holds (a finished future's result) stay until the deadline
        pops them, and this is where that shows."""
        from torchft_tpu import metrics  # lazy: futures stays a leaf module

        metrics.set_gauge("tpuft_timeout_heap_entries", len(self._heap))

    def _run(self) -> None:
        while True:
            with self._lock:
                self._last_tick = time.monotonic()
                if not self._heap:
                    self._lock.wait(timeout=1.0)
                    continue
                deadline, _, handle, callback = self._heap[0]
                now = time.monotonic()
                if deadline > now:
                    self._lock.wait(timeout=min(deadline - now, 1.0))
                    continue
                heapq.heappop(self._heap)
                self._note_heap()
            if not handle.cancelled:
                try:
                    callback()
                except Exception:  # noqa: BLE001
                    # A failing timeout callback must not kill the scheduler.
                    import traceback

                    traceback.print_exc()

    def _run_watchdog(self) -> None:
        while True:
            time.sleep(WATCHDOG_TIMEOUT_SEC / 4)
            if not self._watchdog_enabled:
                continue
            stalled = time.monotonic() - self._last_tick
            if stalled > WATCHDOG_TIMEOUT_SEC:
                sys.stderr.write(
                    f"tpuft watchdog: timeout scheduler stalled {stalled:.1f}s "
                    f"(> {WATCHDOG_TIMEOUT_SEC}s); exiting\n"
                )
                sys.stderr.flush()
                self._exit(1)
                # Only reachable when the exit seam is mocked (tests): end
                # the watchdog thread instead of re-firing forever.
                return

    def _exit(self, code: int) -> None:  # test seam
        # os._exit, not sys.exit: SystemExit raised in a non-main thread
        # only kills that thread — the watchdog contract is a process
        # hard-exit when the timeout scheduler is wedged.
        os._exit(code)


_TIMEOUT_MANAGER = _TimeoutManager()


def future_timeout(fut: "Future[Any]", timeout: float) -> "Future[Any]":
    """A future mirroring ``fut`` but failing with TimeoutError after
    ``timeout`` seconds (reference: futures.py:146-191)."""
    out: Future = Future()

    def on_timeout() -> None:
        if not out.done():
            out.set_exception(TimeoutError(f"future timed out after {timeout}s"))

    handle = _TIMEOUT_MANAGER.schedule(timeout, on_timeout)

    def on_done(f: "Future[Any]") -> None:
        handle.cancel()
        if out.done():
            return
        err = f.exception()
        if err is not None:
            try:
                out.set_exception(err)
            except Exception:  # noqa: BLE001  (already resolved by timeout race)
                pass
        else:
            try:
                out.set_result(f.result())
            except Exception:  # noqa: BLE001
                pass

    fut.add_done_callback(on_done)
    return out


def future_wait(fut: "Future[Any]", timeout: float) -> Any:
    """Blocks on ``fut`` up to ``timeout``; raises TimeoutError on expiry."""
    return fut.result(timeout=timeout)


@contextmanager
def context_timeout(
    callback: Callable[[], None], timeout: float
) -> Generator[None, None, None]:
    """Runs ``callback`` if the with-body hasn't finished within ``timeout``
    (reference: futures.py:228-243). Used to abort a wedged collective."""
    handle = _TIMEOUT_MANAGER.schedule(timeout, callback)
    try:
        yield
    finally:
        handle.cancel()


def stream_timeout(callback: Callable[[], None], timeout: float) -> _TimerHandle:
    """Schedules ``callback`` unless cancelled within ``timeout`` — the
    TPU analogue of the reference's CUDA-event stream timeout: pair it with
    ``jax.block_until_ready`` and cancel on completion."""
    return _TIMEOUT_MANAGER.schedule(timeout, callback)


class CommitPipeline:
    """Depth-bounded queue of pending pipelined-commit steps — the
    speculative window behind ``Manager(commit_pipeline_depth=...)``.

    At most ``depth`` steps may be awaiting their commit verdict at once:
    the owner (optim.Optimizer's pipelined step_fn) pushes one record per
    dispatched step and must resolve enough of the oldest records to make
    room before pushing past ``depth``. The bound is dynamic
    (:meth:`set_depth`) so the adaptive controller can deepen or shrink
    the window between steps; records already admitted are never evicted
    by a shrink — the owner drains down to the new bound. Records are
    opaque beyond the two idempotent phases every pipelined step has — a
    vote resolution (owner-driven, may roll state back) and a device
    bound (``bound_device(raise_on_error=...)``, safe from any thread).
    The queue itself only does thread-safe bookkeeping: the manager's
    quorum-change drain and the optimizer's step loop touch it from
    different threads.
    """

    def __init__(self, depth: int = 1) -> None:
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self._depth = depth
        self._lock = threading.Lock()
        self._records: list = []

    def _note_occupancy(self) -> None:
        # Called under self._lock. Lazy import: futures is a leaf module
        # metrics itself may one day time — keep the import edge one-way.
        from torchft_tpu import metrics

        metrics.set_gauge("tpuft_pipeline_pending", len(self._records))

    @property
    def depth(self) -> int:
        return self._depth

    def set_depth(self, depth: int) -> None:
        """Rebounds the window (the adaptive controller's lever). Growing
        takes effect on the next push; shrinking never evicts — the owner
        resolves oldest records until occupancy fits the new bound."""
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        with self._lock:
            self._depth = depth

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)

    def push(self, record: Any) -> None:
        """Admits a newly dispatched step. The owner resolves the oldest
        record before pushing past ``depth`` — exceeding it means a step
        was dispatched with more than ``depth`` commits unaccounted, which
        the bounded envelope forbids."""
        from torchft_tpu.utils import schedules

        schedules.point("pipeline.push")
        with self._lock:
            if len(self._records) >= self._depth:
                raise RuntimeError(
                    f"commit pipeline full (depth={self._depth}); resolve the "
                    "oldest pending step before dispatching another"
                )
            self._records.append(record)
            from torchft_tpu import metrics

            metrics.inc("tpuft_pipeline_steps_total")
            self._note_occupancy()

    def oldest(self) -> Optional[Any]:
        with self._lock:
            return self._records[0] if self._records else None

    def remove(self, record: Any) -> None:
        with self._lock:
            if record in self._records:
                self._records.remove(record)
                self._note_occupancy()

    def pending(self) -> tuple:
        """Snapshot of the pending records, oldest first."""
        with self._lock:
            return tuple(self._records)

    def drain(self) -> tuple:
        """Pops every pending record (oldest first); the caller resolves
        them. Used at step-loop boundaries: flush, shutdown, switching
        step protocols."""
        from torchft_tpu.utils import schedules

        schedules.point("pipeline.drain")
        with self._lock:
            records, self._records = tuple(self._records), []
            self._note_occupancy()
            return records
