"""Versioned weight-history units (torchft_tpu/history.py): the
step-labeled committed-snapshot rings behind exact deep-window donor
heals and pinned-version/rollback serving.

- WeightHistory (manager state ring): budget eviction (count AND bytes,
  newest never evicted), the completeness contract of state_dict_at
  (every required key + accounting, or None — a miss can only mean
  "fetch more", never a partial/mislabeled checkpoint), rollback
  retraction, restore-time clear.
- StagedVersionStore (serving staged ring): residency, drop/drop_newer
  retraction semantics (410-vs-404 distinction), the on_evict release
  hook (child mode deletes /dev/shm epoch dirs through it).
- Env knobs: TPUFT_HISTORY_BYTES / TPUFT_HISTORY_MAX_VERSIONS parsing
  and the K=1 degradation.
"""

import numpy as np
import pytest

from torchft_tpu.history import (
    ENV_HISTORY_BYTES,
    ENV_HISTORY_MAX_VERSIONS,
    StagedVersionStore,
    WeightHistory,
    history_bytes_budget,
    history_max_versions,
)


def state(step: int, n: int = 8) -> dict:
    return {"w": np.full(n, float(step), np.float32)}


# ---------------------------------------------------------------------------
# WeightHistory
# ---------------------------------------------------------------------------


def test_state_ring_keeps_newest_k_and_serves_complete_dicts() -> None:
    hist = WeightHistory(max_versions=3)
    for s in range(1, 6):
        hist.note_accounting(s, s * 2)
        hist.note_state("optimizer", s, state(s), nbytes=32)
    assert hist.resident_steps() == [3, 4, 5]
    sd = hist.state_dict_at(4, {"optimizer"})
    assert sd is not None
    np.testing.assert_array_equal(sd["user"]["optimizer"]["w"], 4.0)
    assert sd["tpuft"] == {"step": 4, "batches_committed": 8}
    # Evicted step: a miss, not a wrong answer.
    assert hist.state_dict_at(1, {"optimizer"}) is None


def test_state_ring_byte_budget_evicts_oldest_never_newest() -> None:
    hist = WeightHistory(max_versions=10, max_bytes=100)
    hist.note_accounting(1, 1)
    hist.note_state("optimizer", 1, state(1), nbytes=80)
    hist.note_accounting(2, 2)
    hist.note_state("optimizer", 2, state(2), nbytes=80)
    # 160 > 100: the oldest goes; the newest ALWAYS stays, even if it
    # alone exceeds the budget.
    assert hist.resident_steps() == [2]
    hist.note_accounting(3, 3)
    hist.note_state("optimizer", 3, state(3), nbytes=500)
    assert hist.resident_steps() == [3]


def test_state_dict_at_requires_every_key_and_accounting() -> None:
    hist = WeightHistory(max_versions=4)
    hist.note_accounting(1, 1)
    hist.note_state("optimizer", 1, state(1), nbytes=32)
    # A registered key the ring never saw = miss (a mixed-step dict is
    # never assembled).
    assert hist.state_dict_at(1, {"optimizer", "dataloader"}) is None
    # Accounting-only entries are not servable either.
    hist.note_accounting(2, 2)
    assert hist.state_dict_at(2, {"optimizer"}) is None
    assert hist.state_dict_at(1, {"optimizer"}) is not None


def test_state_ring_step0_never_ingested() -> None:
    # Step 0 is the init_sync mosaic: per-LOCAL-rank state that
    # intentionally differs within a group — never history-served.
    hist = WeightHistory(max_versions=4)
    hist.note_state("optimizer", 0, state(0), nbytes=32)
    hist.note_accounting(0, 0)
    assert len(hist) == 0


def test_retract_newer_drops_past_surviving_step_and_clear() -> None:
    hist = WeightHistory(max_versions=8)
    for s in range(1, 5):
        hist.note_accounting(s, s)
        hist.note_state("optimizer", s, state(s), nbytes=32)
    assert hist.retract_newer(2) == 2
    assert hist.resident_steps() == [1, 2]
    hist.clear()
    assert hist.resident_steps() == []


def test_env_knob_parsing(monkeypatch) -> None:
    monkeypatch.setenv(ENV_HISTORY_MAX_VERSIONS, "7")
    assert history_max_versions(3) == 7
    monkeypatch.setenv(ENV_HISTORY_MAX_VERSIONS, "0")
    assert history_max_versions(3) == 1  # >= 1 always
    monkeypatch.setenv(ENV_HISTORY_MAX_VERSIONS, "junk")
    assert history_max_versions(3) == 3
    monkeypatch.setenv(ENV_HISTORY_BYTES, "1000")
    assert history_bytes_budget() == 1000
    monkeypatch.setenv(ENV_HISTORY_BYTES, "0")
    assert history_bytes_budget() is None
    monkeypatch.setenv(ENV_HISTORY_BYTES, "junk")
    assert history_bytes_budget() is None


def test_k1_degrades_to_live_state_only(monkeypatch) -> None:
    monkeypatch.setenv(ENV_HISTORY_MAX_VERSIONS, "1")
    hist = WeightHistory(max_versions=5)  # env overrides the ctor
    for s in (1, 2, 3):
        hist.note_accounting(s, s)
        hist.note_state("optimizer", s, state(s), nbytes=32)
    assert hist.resident_steps() == [3]


@pytest.mark.parametrize("depth, want", [("0", 1), ("1", 2), ("3", 4), ("auto", 5)])
def test_doctor_names_the_ring_width_a_manager_of_that_depth_gets(
    monkeypatch, depth, want
) -> None:
    """The preflight's sentence and ``Manager.__init__`` follow one rule,
    window + 1 (one version at the default depth 0)."""
    from test_manager import make_manager

    from torchft_tpu import doctor

    for name in (
        ENV_HISTORY_MAX_VERSIONS,
        ENV_HISTORY_BYTES,
        "TPUFT_COMMIT_PIPELINE",
        "TPUFT_COMMIT_PIPELINE_ADAPTIVE",
    ):
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setenv("TPUFT_COMMIT_PIPELINE_DEPTH", depth)
    status, message = doctor._check_history()
    assert status == "PASS"
    assert f"manager keeps {want} committed version(s)" in message
    manager, _, _, _ = make_manager()  # reads the same environment
    assert manager.history.max_versions == want


# ---------------------------------------------------------------------------
# StagedVersionStore
# ---------------------------------------------------------------------------


def test_staged_store_residency_eviction_and_release_hook() -> None:
    released = []
    store = StagedVersionStore(
        max_versions=2, on_evict=lambda s, p: released.append(s)
    )
    store.put(1, "v1", nbytes=10)
    store.put(2, "v2", nbytes=10)
    store.put(3, "v3", nbytes=10)
    assert store.steps() == [2, 3]
    assert released == [1]  # evicted payloads are released exactly once
    assert store.get(2) == "v2" and store.get(1) is None
    assert store.latest_steps(2) == [3, 2]


def test_staged_store_drop_and_retraction_semantics() -> None:
    released = []
    store = StagedVersionStore(
        max_versions=4, on_evict=lambda s, p: released.append(s)
    )
    for s in (1, 2, 3, 4):
        store.put(s, f"v{s}", nbytes=10)
    # drop_newer is the rollback sweep: everything past the survivor
    # leaves, marked retracted (reads answer "gone", not "never was").
    assert store.drop_newer(2) == [3, 4]
    assert store.steps() == [1, 2]
    assert store.is_retracted(3) and store.is_retracted(4)
    assert not store.is_retracted(2)
    assert sorted(released) == [3, 4]
    # A later re-publish of a retracted step clears its tombstone.
    store.put(3, "v3b", nbytes=10)
    assert not store.is_retracted(3)
    # Plain eviction is NOT a retraction.
    assert store.drop(1, retracted=False)
    assert not store.is_retracted(1)


def test_staged_store_byte_budget() -> None:
    store = StagedVersionStore(max_versions=10, max_bytes=25)
    store.put(1, "a", nbytes=10)
    store.put(2, "b", nbytes=10)
    store.put(3, "c", nbytes=10)
    assert store.steps() == [2, 3]
    store.put(4, "d", nbytes=1000)  # newest always stays
    assert store.steps() == [4]


# ---------------------------------------------------------------------------
# descriptor ordering helpers (the retraction wire contract)
# ---------------------------------------------------------------------------


def test_newer_than_held_stream_scoping() -> None:
    from torchft_tpu.serving._wire import newer_than_held, same_stream

    held_seq, held_src = 5, "pubA"
    # Same stream: seq governs — a retraction (lower step, higher seq)
    # outranks; a stale endpoint (lower seq) cannot.
    retraction = {"step": 3, "pub_seq": 6, "pub_id": "pubA"}
    stale = {"step": 9, "pub_seq": 4, "pub_id": "pubA"}
    assert same_stream(retraction, held_seq, held_src)
    assert newer_than_held(retraction, 4, held_seq, held_src)
    assert not newer_than_held(stale, 4, held_seq, held_src)
    # Cross-stream: sequences are incomparable counters — step order.
    other = {"step": 5, "pub_seq": 1, "pub_id": "pubB"}
    assert not same_stream(other, held_seq, held_src)
    assert newer_than_held(other, 4, held_seq, held_src)
    assert not newer_than_held(other, 6, held_seq, held_src)
    # Pre-history peers (no seq anywhere): step order.
    assert newer_than_held({"step": 7}, 6, None, None)


def test_changed_chunks_between() -> None:
    from torchft_tpu.serving._wire import changed_chunks_between

    base = {"crc_algo": "crc32", "chunk_crcs": [1, 2, 3], "chunk_sizes": [9, 9, 9]}
    new = {"crc_algo": "crc32", "chunk_crcs": [1, 5, 3], "chunk_sizes": [9, 9, 8]}
    assert changed_chunks_between(base, new) == [1, 2]
    assert changed_chunks_between(None, new) is None
    assert (
        changed_chunks_between({**base, "crc_algo": "crc32c"}, new) is None
    )
    assert (
        changed_chunks_between(
            {"crc_algo": "crc32", "chunk_crcs": [1], "chunk_sizes": [9]}, new
        )
        is None
    )
