"""Versioned weight history: ONE step-labeled ring of committed snapshots.

The repo previously held "recent committed state" in three independent
stores with different lifetimes: the pipelined-commit rollback ring
(optim._PendingStep slots, dropped at resolution), the serving
publisher's staged version (checkpointing/http_transport.py, replaced on
every stage), and the donor/serve-child staging area (one epoch,
replaced on restage). The seams between them were the two documented
weaknesses: a deep-window donor could only serve its DRAINED step (the
first post-drain heal round failed cleanly and retried), and a retracted
published version left readers with no sanctioned fallback. This module
unifies them:

- :class:`WeightHistory` — the manager-side ring of committed STATE
  REFS, keyed by step. Entries are per-registered-key immutable pytrees
  (jax/numpy leaves are never mutated in place — holding a reference IS
  a snapshot, exactly the argument ``WeightPublisher.publish`` already
  relies on). The pipelined optimizer promotes each slot's committed
  state here at resolution instead of dropping it, so a donor asked for
  ``quorum.max_step`` can stage that exact committed step even when its
  live window drained past it — the PR-9 "fail cleanly and retry"
  envelope becomes an immediate serve. The ring only ever ingests
  COMMITTED state (promotion happens at commit resolution; rollbacks
  retract), so analyzer rule R7's speculation discipline is untouched.

- :class:`StagedVersionStore` — the serving-side ring of fully staged
  versions in the exact PR-4 heal format (per-chunk CRCs, sha256 digest,
  era tag): the publisher's transport keeps the last K staged versions
  servable so ``/serving/version/{step}`` and ``latest-1`` reads hit
  real bytes, retraction can converge readers to V-1, and a lagging
  relay/rejoiner delta-chains across resident manifests instead of
  paying a full pull. In ``TPUFT_HEAL_SERVE_MODE=child`` the resident
  versions live as /dev/shm epoch directories owned by the serve child
  (serve_child.py keeps the same budgeted ring of epochs).

Budget: K adapts to ``TPUFT_HISTORY_BYTES`` (total resident payload
bytes; the same accounting as ``tpuft_pipeline_snapshot_bytes`` — one
full (params, opt_state) copy per version is THE memory cost) and is
capped by ``TPUFT_HISTORY_MAX_VERSIONS``. The newest committed version
is never evicted; ``K=1`` degrades bit-for-bit to the pre-history
behavior (only the live committed state exists). Defaults: the manager
ring sizes itself by the commit window, window + 1 at every depth (the
versions the rollback ring already held; ``auto`` counts its adaptive
maximum as the window), so a strict depth-0 manager runs at ``K=1``: its
one version IS the live committed state, held by reference at no cost in
memory. A ring of one version is also what lets that state be updated IN
PLACE: the lone replica's step votes first and then gives ``params`` and
``opt_state`` to its program (``Optimizer``'s ownership contract,
optim.py), so the chip holds ONE copy of the state, and the version this
ring holds is deleted by the step that replaces it, inside the state-dict
write lock, where the promotion of its successor happens too. So
:meth:`WeightHistory.state_dict_at` hands a ring of one version's entry
out as a device copy (:func:`_snapshot`, counted), never as the
references themselves. A ring asked to keep older versions
(``max_versions`` > 1: a pipelined window, or the environment) holds
them by reference, which nothing may then delete: there the step keeps
today's speculative order and two copies of the state (committed N,
speculative N + 1) plus the window's. The serving store keeps
:data:`DEFAULT_SERVING_VERSIONS`.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

from torchft_tpu import metrics, tracing

__all__ = [
    "WeightHistory",
    "StagedVersionStore",
    "ENV_HISTORY_BYTES",
    "ENV_HISTORY_MAX_VERSIONS",
    "history_bytes_budget",
    "history_max_versions",
    "DEFAULT_SERVING_VERSIONS",
]

ENV_HISTORY_BYTES = "TPUFT_HISTORY_BYTES"
ENV_HISTORY_MAX_VERSIONS = "TPUFT_HISTORY_MAX_VERSIONS"

# Serving-side default ring width: latest + latest-1 for rollback/canary
# plus two more for pinned readers and delta chains. Small on purpose —
# every resident version is a full payload copy.
DEFAULT_SERVING_VERSIONS = 4


def history_bytes_budget(default: Optional[int] = None) -> Optional[int]:
    """Total resident-bytes budget for a history ring
    (``$TPUFT_HISTORY_BYTES``; unset/<=0 = count-bounded only)."""
    raw = os.environ.get(ENV_HISTORY_BYTES)
    if raw is None:
        return default
    try:
        value = int(float(raw))
    except ValueError:
        return default
    return value if value > 0 else None


def history_max_versions(default: int) -> int:
    """Resident-version cap for a history ring
    (``$TPUFT_HISTORY_MAX_VERSIONS``; >= 1 — the newest is never
    evicted)."""
    raw = os.environ.get(ENV_HISTORY_MAX_VERSIONS)
    if raw is None:
        return max(1, default)
    try:
        return max(1, int(raw))
    except ValueError:
        return max(1, default)


def _device_copy(tree: Any) -> Any:
    """``tree`` with every ``jax.Array`` in it copied into a buffer of its
    own (same sharding; anything else passes through). The programs that
    update state in place (DiLoCo's, the lone replica's FT-DDP step) donate
    the state they replace, so a buffer that something else must still
    read is copied before the next program is dispatched: the copy is
    queued on the device ahead of that program and reads the old values.
    One small program per leaf, so that leaves on different device sets
    need no common mesh."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x, tree
    )


def _snapshot(key: str, tree: Any) -> Any:
    """A capture of registered state ``key`` that survives later steps: a
    device copy (:func:`_device_copy`), counted. Holding a reference is
    no snapshot where the next step deletes what it refers to."""
    import jax

    nbytes = sum(
        x.nbytes for x in jax.tree_util.tree_leaves(tree) if isinstance(x, jax.Array)
    )
    if nbytes:
        metrics.inc("tpuft_state_snapshot_copies_total", key=key)
        metrics.inc("tpuft_state_snapshot_copy_bytes_total", nbytes, key=key)
    return _device_copy(tree)


class _StateEntry:
    """One committed step's state refs: per-registered-key pytrees plus
    the manager accounting that makes the entry a complete, honestly
    labeled checkpoint (``batches_committed`` at that step)."""

    __slots__ = ("step", "quorum_id", "states", "nbytes", "batches_committed")

    def __init__(self, step: int) -> None:
        self.step = step
        self.quorum_id: Optional[int] = None
        self.states: Dict[str, Any] = {}
        self.nbytes = 0
        self.batches_committed: Optional[int] = None


class WeightHistory:
    """Byte-budgeted, step-labeled ring of committed state references.

    Thread-safe: promotion lands from the train loop, the commit pool,
    and the quorum thread (drain hooks); lookups come from the quorum
    thread's donor-staging path. All entries are committed-only BY
    CONSTRUCTION — callers promote at commit resolution, never from a
    live speculative window — and a rollback-unwind retracts every entry
    newer than the surviving committed step.
    """

    def __init__(
        self,
        max_versions: Optional[int] = None,
        max_bytes: Optional[int] = None,
        ring: str = "state",
        journal: Optional[tracing.TraceJournal] = None,
    ) -> None:
        # Where the ring's one span (``history_evict``) lands: its manager's
        # journal (None: the evicting thread's current one).
        self._journal = journal
        self._max_versions = history_max_versions(
            max_versions if max_versions is not None else 1
        )
        self._max_bytes = history_bytes_budget(max_bytes)
        self._ring = ring
        self._lock = threading.Lock()
        self._entries: "OrderedDict[int, _StateEntry]" = OrderedDict()

    @property
    def max_versions(self) -> int:
        return self._max_versions

    # -- ingestion ---------------------------------------------------------

    def note_state(
        self,
        key: str,
        step: int,
        state: Any,
        nbytes: int = 0,
        quorum_id: Optional[int] = None,
    ) -> None:
        """Promotes one registered key's committed state at ``step``.
        ``state`` must be an immutable pytree (the committed refs); the
        caller supplies its resident-byte estimate (the
        ``tpuft_pipeline_snapshot_bytes`` accounting)."""
        if step <= 0:
            return  # step 0 is the init_sync mosaic: per-rank, never served
        with self._lock:
            entry = self._entries.get(step)
            if entry is None:
                entry = _StateEntry(step)
                self._entries[step] = entry
                # Keep step order even if promotions race slightly out of
                # order across threads (drain vs train loop).
                if list(self._entries) != sorted(self._entries):
                    self._entries = OrderedDict(
                        sorted(self._entries.items())
                    )
            if key not in entry.states:  # idempotent: first promotion wins
                entry.states[key] = state
                entry.nbytes += max(0, int(nbytes))
            if quorum_id is not None:
                entry.quorum_id = quorum_id
            metrics.inc("tpuft_history_promotions_total")
            self._evict_locked()
            self._publish_gauges_locked()

    def note_accounting(self, step: int, batches_committed: int) -> None:
        """Records the manager accounting at a committed step (cheap ints
        — safe on the commit tail, unlike a state sample). Creates the
        entry when it is first: the commit tail runs BEFORE the state
        owner's promotion, and an entry is servable only once both
        halves landed."""
        if step <= 0:
            return
        with self._lock:
            entry = self._entries.get(step)
            if entry is None:
                entry = _StateEntry(step)
                self._entries[step] = entry
                if list(self._entries) != sorted(self._entries):
                    self._entries = OrderedDict(sorted(self._entries.items()))
                self._evict_locked()
            entry.batches_committed = int(batches_committed)

    # -- lookup ------------------------------------------------------------

    def state_dict_at(
        self, step: int, required_keys: Set[str]
    ) -> Optional[Dict[str, Any]]:
        """The full manager-shaped state dict for committed ``step`` —
        ``{"user": {key: state}, "tpuft": {step, batches_committed}}`` —
        or None when the ring cannot serve it exactly (step evicted /
        never promoted, a registered key missing, or accounting absent).
        A miss means the caller falls back to staging its drained step;
        it can never mean serving mislabeled or partial state. A ring of
        one version answers with device copies (the module docstring says
        why): call it under the state-dict read lock, as
        ``Manager._history_state_dict`` does."""
        with self._lock:
            entry = self._entries.get(step)
            if entry is None:
                return None
            if required_keys - set(entry.states):
                return None
            if entry.batches_committed is None:
                return None
            states = {k: entry.states[k] for k in required_keys}
            if self._max_versions == 1:
                # The one version is the live state, which its owner may
                # update in place: the capture has to outlive it.
                states = {k: _snapshot(k, v) for k, v in states.items()}
            return {
                "user": states,
                "tpuft": {
                    "step": step,
                    "batches_committed": entry.batches_committed,
                },
            }

    def resident_steps(self) -> List[int]:
        with self._lock:
            return list(self._entries)

    def nbytes(self) -> int:
        with self._lock:
            return sum(e.nbytes for e in self._entries.values())

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    # -- retraction / lifecycle --------------------------------------------

    def retract_newer(self, committed_step: int) -> int:
        """Drops every entry newer than the surviving committed step (the
        rollback-unwind twin of the publisher's due-mark retraction);
        returns how many were dropped. Promotion is commit-resolution-
        gated so this is belt-and-braces — refused steps were never
        promoted — but it keeps the ring provably on the committed
        trajectory even across the phantom-commit envelope."""
        with self._lock:
            doomed = [s for s in self._entries if s > committed_step]
            for s in doomed:
                del self._entries[s]
            if doomed:
                self._publish_gauges_locked()
            return len(doomed)

    def clear(self) -> None:
        """Forget everything (a user checkpoint restore rewrote the step
        counter: old step labels no longer describe this trajectory)."""
        with self._lock:
            self._entries.clear()
            self._publish_gauges_locked()

    # -- internals ---------------------------------------------------------

    def _evict_locked(self) -> None:
        def over_budget() -> bool:
            if len(self._entries) > self._max_versions:
                return True
            if self._max_bytes is not None and len(self._entries) > 1:
                total = sum(e.nbytes for e in self._entries.values())
                return total > self._max_bytes
            return False

        if not (len(self._entries) > 1 and over_budget()):
            return
        # The span is where a version's buffers are released, when this
        # ring held the last reference to them; opened only when one leaves.
        with tracing.phase("history_evict", self._journal):
            while len(self._entries) > 1 and over_budget():
                self._entries.popitem(last=False)  # oldest; newest never goes
                metrics.inc("tpuft_history_evictions_total")

    def _publish_gauges_locked(self) -> None:
        metrics.set_gauge(
            "tpuft_history_versions", len(self._entries), ring=self._ring
        )
        metrics.set_gauge(
            "tpuft_history_bytes",
            sum(e.nbytes for e in self._entries.values()),
            ring=self._ring,
        )


class StagedVersionStore:
    """Ring of fully STAGED versions (opaque payload handles — the inline
    transport's ``_Staged`` objects, or child-mode epoch records): the
    serving plane's resident history. Same budget/eviction semantics as
    :class:`WeightHistory`; an ``on_evict`` callback releases payload
    resources (child mode deletes the epoch directory). Retraction
    removes a version and remembers its step so later reads answer
    "retracted" (410) instead of "never existed" (404)."""

    def __init__(
        self,
        max_versions: Optional[int] = None,
        max_bytes: Optional[int] = None,
        on_evict: Optional[Callable[[int, Any], None]] = None,
        ring: str = "staged",
    ) -> None:
        self._max_versions = history_max_versions(
            max_versions if max_versions is not None else DEFAULT_SERVING_VERSIONS
        )
        self._max_bytes = history_bytes_budget(max_bytes)
        self._on_evict = on_evict
        self._ring = ring
        self._lock = threading.Lock()
        self._versions: "OrderedDict[int, Tuple[Any, int]]" = OrderedDict()
        self._retracted: Set[int] = set()

    @property
    def max_versions(self) -> int:
        return self._max_versions

    def put(self, step: int, payload: Any, nbytes: int) -> None:
        evicted: List[Tuple[int, Any]] = []
        with self._lock:
            self._versions[step] = (payload, max(0, int(nbytes)))
            if list(self._versions) != sorted(self._versions):
                self._versions = OrderedDict(sorted(self._versions.items()))
            self._retracted.discard(step)
            metrics.inc("tpuft_history_promotions_total")
            while len(self._versions) > 1 and self._over_budget_locked():
                old_step, (old_payload, _n) = self._versions.popitem(last=False)
                metrics.inc("tpuft_history_evictions_total")
                evicted.append((old_step, old_payload))
            self._publish_gauges_locked()
        for old_step, old_payload in evicted:
            self._release(old_step, old_payload)

    def get(self, step: int) -> Optional[Any]:
        with self._lock:
            held = self._versions.get(step)
            return held[0] if held is not None else None

    def steps(self) -> List[int]:
        with self._lock:
            return list(self._versions)

    def latest_steps(self, n: int) -> List[int]:
        """The newest ``n`` resident steps, newest first."""
        with self._lock:
            return list(self._versions)[-n:][::-1]

    def is_retracted(self, step: int) -> bool:
        with self._lock:
            return step in self._retracted

    def drop(self, step: int, retracted: bool = False) -> bool:
        """Removes one resident version (``retracted=True`` remembers the
        step so reads answer 410 — the operator rollback path)."""
        with self._lock:
            held = self._versions.pop(step, None)
            if retracted:
                self._retracted.add(step)
            if held is None:
                return False
            self._publish_gauges_locked()
        self._release(step, held[0])
        return True

    def drop_newer(self, step: int, retracted: bool = True) -> List[int]:
        """Removes every resident version newer than ``step`` (retraction
        convergence: after retracting V the ring must hold nothing past
        V-1, never a torn mix); returns the dropped steps."""
        with self._lock:
            doomed = [(s, self._versions.pop(s)) for s in list(self._versions) if s > step]
            if retracted:
                self._retracted.update(s for s, _ in doomed)
            if doomed:
                self._publish_gauges_locked()
        for s, (payload, _n) in doomed:
            self._release(s, payload)
        return [s for s, _ in doomed]

    def clear(self) -> None:
        with self._lock:
            doomed = list(self._versions.items())
            self._versions.clear()
            self._retracted.clear()
            self._publish_gauges_locked()
        for s, (payload, _n) in doomed:
            self._release(s, payload)

    def __len__(self) -> int:
        with self._lock:
            return len(self._versions)

    def _release(self, step: int, payload: Any) -> None:
        if self._on_evict is not None:
            try:
                self._on_evict(step, payload)
            except Exception:  # noqa: BLE001 — eviction must never wound serving
                pass

    def _over_budget_locked(self) -> bool:
        if len(self._versions) > self._max_versions:
            return True
        if self._max_bytes is not None:
            total = sum(n for _p, n in self._versions.values())
            return total > self._max_bytes
        return False

    def _publish_gauges_locked(self) -> None:
        metrics.set_gauge(
            "tpuft_history_versions", len(self._versions), ring=self._ring
        )
        metrics.set_gauge(
            "tpuft_history_bytes",
            sum(n for _p, n in self._versions.values()),
            ring=self._ring,
        )
