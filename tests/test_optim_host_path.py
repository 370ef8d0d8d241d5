"""The FT step's host path from inside (PR 59): ``adopt``'s children in the
journal, the counters of what a dispatch hands to the runtime and takes
back, and the byte accounting that no longer walks the state every committed
step; and who owns the step's state (PR 60): the lone replica's step votes
first and then updates ``params`` / ``opt_state`` in place. On the CPU; the
runtime's own events under a span are tests/test_tracing.py's."""

import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from test_manager import make_manager, make_quorum

from torchft_tpu import metrics, tracing
from torchft_tpu.history import WeightHistory
from torchft_tpu.optim import Optimizer
from torchft_tpu.parallel.process_group import ProcessGroupDummy
from torchft_tpu.zero import ZeroOptimizer


def scripted_manager(**kwargs):
    kwargs.setdefault("min_replica_size", 1)
    manager, client, _pg, _transport = make_manager(pg=ProcessGroupDummy(), **kwargs)
    client._quorum.return_value = make_quorum(replica_world_size=1, max_world_size=1)
    client.should_commit.side_effect = lambda rank, step, vote, timeout: vote
    return manager


def _loss(p, batch):
    return sum(jnp.sum(x ** 2) for x in jax.tree_util.tree_leaves(p)) + jnp.sum(batch)


def _params():
    return {
        "w": jnp.array([1.0, -2.0, 3.0], jnp.float32),
        "pair": (jnp.ones(2, jnp.float32), jnp.ones((2, 2), jnp.bfloat16)),
    }


def _batch(i):
    return jnp.full((3,), 0.1 * i, jnp.float32)


def _leaves(tree):
    return len(jax.tree_util.tree_leaves(tree))


def _nbytes(*trees):
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(trees))


def _inside(child, parent):
    return (
        child["thread"] == parent["thread"]
        and parent["t_mono"] <= child["t_mono"]
        and child["t_mono"] + child["dur"] <= parent["t_mono"] + parent["dur"] + 1e-9
    )


def _spans(journal, name):
    return [e for e in journal.snapshot() if e["ph"] == "X" and e["name"] == name]


def _dispatch_totals():
    return (
        metrics.counter_total("tpuft_step_dispatch_total"),
        metrics.counter_total("tpuft_step_dispatch_buffers_total", direction="in"),
        metrics.counter_total("tpuft_step_dispatch_buffers_total", direction="out"),
    )


def _growth(before):
    return tuple(now - was for now, was in zip(_dispatch_totals(), before))


# ---------------------------------------------------------------------------
# adopt's children
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", [0, 1, 2])
def test_adopt_children_nest_inside_adopt_on_its_thread(depth):
    """``state_swap`` and ``history_promote`` once inside every ``adopt`` of a
    committed step, on its thread and under its step (the strict step's is
    the root's; the pipelined window's the claimed step's)."""
    journal = tracing.TraceJournal(maxlen=1024)
    with tracing.use_journal(journal):
        manager = scripted_manager(commit_pipeline_depth=depth)
        opt = Optimizer(manager, optax.adam(0.1), _params())
        step_fn = opt.make_step_fn(_loss)
        for i in range(4):
            step_fn(_batch(i))
        opt.flush_pipeline()
    adopts = _spans(journal, "adopt")
    assert len(adopts) == 4
    for adopt in adopts:
        for name in ("state_swap", "history_promote"):
            (child,) = [e for e in _spans(journal, name) if _inside(e, adopt)]
            assert child["step"] == adopt["step"], name
    assert len(_spans(journal, "state_swap")) == len(_spans(journal, "history_promote")) == 4
    if depth == 0:
        roots = _spans(journal, "step")
        for adopt, root in zip(adopts, roots):
            assert _inside(adopt, root) and adopt["step"] == root["step"]
    # The pipelined site's old wrapper is gone: nothing but PHASES rows.
    assert all(
        spec.annotation != "tpuft::optim::resolve_pipelined_commit"
        for spec in tracing.PHASES.values()
    )


def test_refused_commit_has_no_adopt_and_no_children():
    journal = tracing.TraceJournal(maxlen=256)
    with tracing.use_journal(journal):
        manager = scripted_manager()
        opt = Optimizer(manager, optax.sgd(0.1), _params())
        step_fn = opt.make_step_fn(_loss)
        manager._client.should_commit.side_effect = lambda *a, **k: False
        _, committed = step_fn(_batch(0))
    assert committed is False
    for name in ("adopt", "state_swap", "history_promote", "history_evict"):
        assert _spans(journal, name) == []


@pytest.mark.parametrize("order", ["vote_first", "speculative"])
def test_history_evict_is_where_the_version_leaves(monkeypatch, order):
    """With a real manager the commit tail's ``note_accounting`` makes the new
    entry, so the previous version leaves the ring THERE, after the barrier
    and before ``adopt`` begins; ``adopt``'s promotion finds nothing to
    evict. The vote-first step takes its verdict on its own thread, so that
    is inside its ``commit_wait``, before the program that deletes the
    version's arrays is dispatched; the speculative step (strict mode here)
    votes on the commit thread. A ring whose accounting half arrives late (a
    scripted manager's) evicts inside ``history_promote``."""
    monkeypatch.setenv("TPUFT_STRICT_COMMIT", "1" if order == "speculative" else "0")
    journal = tracing.TraceJournal(maxlen=1024)
    with tracing.use_journal(journal):
        manager = scripted_manager()
        opt = Optimizer(manager, optax.sgd(0.1), _params())
        step_fn = opt.make_step_fn(_loss)
        for i in range(3):
            step_fn(_batch(i))
        evicts = _spans(journal, "history_evict")
        # Step 1's commit has nothing to evict; steps 2 and 3 evict one each.
        assert len(evicts) == 2
        adopts = {e["step"]: e for e in _spans(journal, "adopt")}
        waits = {e["step"]: e for e in _spans(journal, "commit_wait")}
        dispatches = {e["step"]: e for e in _spans(journal, "update_dispatch")}
        for evict in evicts:
            adopt = adopts[evict["step"]]
            assert evict["t_mono"] + evict["dur"] <= adopt["t_mono"]
            if order == "vote_first":
                assert _inside(evict, waits[evict["step"]])
                assert evict["t_mono"] + evict["dur"] <= dispatches[evict["step"]]["t_mono"]
            else:
                assert evict["thread"] != adopt["thread"]
        # The accounting half left out: the promotion is what evicts.
        manager._history.note_accounting = lambda *a, **k: None
        seen = len(journal.snapshot())
        step_fn(_batch(3))
        fresh = [e for e in journal.snapshot()[seen:] if e["ph"] == "X"]
        (evict,) = [e for e in fresh if e["name"] == "history_evict"]
        (promote,) = [e for e in fresh if e["name"] == "history_promote"]
        (adopt,) = [e for e in fresh if e["name"] == "adopt"]
        assert _inside(evict, promote) and _inside(promote, adopt)
        assert evict["step"] == adopt["step"]


# ---------------------------------------------------------------------------
# counters of what a dispatch hands over
# ---------------------------------------------------------------------------


def _setup(path, tx=None):
    manager = scripted_manager()
    opt = Optimizer(manager, tx or optax.adam(0.1), _params())
    if path == "step":
        grad_fn = jax.jit(jax.grad(_loss))

        def run(batch):
            opt.begin_step()
            return opt.step(grad_fn(opt.params, batch))

        return opt, run
    if path == "wire":
        manager.is_lone_replica = lambda: False
    step_fn = opt.make_step_fn(_loss)
    return opt, lambda batch: step_fn(batch)[1]


@pytest.mark.parametrize("path", ["lone", "wire", "step"])
def test_dispatch_counters_grow_by_the_states_leaf_counts(path):
    opt, run = _setup(path)
    n_params, n_opt = _leaves(opt.params), _leaves(opt.opt_state)
    assert (n_params, n_opt) == (3, 7) == opt._state_leaves
    if path == "lone":  # the fused step: the state and the batch in, the loss and the state out
        want = (1, n_params + n_opt + 1, 1 + n_params + n_opt)
    else:  # the standalone update: gradients, opt_state and params in, the state out
        want = (1, 2 * n_params + n_opt, n_params + n_opt)
    for i in range(3):
        before = _dispatch_totals()
        dispatched = metrics.histogram_stats("tpuft_update_dispatch_seconds")["count"]
        assert run(_batch(i))
        assert _growth(before) == want
        # One count a span: the counters and the histogram tell the same calls.
        assert metrics.histogram_stats("tpuft_update_dispatch_seconds")["count"] == dispatched + 1


def test_dispatch_counters_follow_a_heal_that_changes_the_structure():
    """A donor's state with fewer leaves (a pair restored as one array): the
    counts are taken again in ``_load_state_dict``, not in the step."""
    opt, run = _setup("lone", tx=optax.sgd(0.1))
    assert opt._state_leaves == (3, 0)
    before = _dispatch_totals()
    assert run(_batch(0))
    assert _growth(before) == (1, 3 + 1, 1 + 3)
    era = opt._state_era
    opt._load_state_dict({
        "params": {"w": np.ones(3, np.float32), "pair": np.ones(5, np.float32)},
        "opt_state": opt.opt_state,
    })
    assert opt._state_leaves == (2, 0) and opt._state_era == era + 1
    before = _dispatch_totals()
    assert run(_batch(1))
    assert _growth(before) == (1, 2 + 1, 1 + 2)


def test_batch_leaves_are_counted_once_a_step_function(monkeypatch):
    opt, run = _setup("lone", tx=optax.sgd(0.1))
    assert opt._batch_leaves is None
    assert run(_batch(0))
    assert opt._batch_leaves == (1, 1)
    calls = []
    real = jax.tree_util.tree_leaves
    monkeypatch.setattr(
        jax.tree_util, "tree_leaves",
        lambda tree, *a, **k: calls.append(tree) or real(tree, *a, **k),
    )
    for i in range(3):
        assert run(_batch(i))
    # No walk of the batch, of the state or of a snapshot in a steady step.
    assert calls == []


def test_zero_dispatch_is_a_phase_and_counts_its_shards():
    """ZeRO's two dispatches of a lone step (the gradient program, the shard
    update) are ``update_dispatch`` phases like the base optimizer's: journal
    event, annotation and histogram from one site, and the counters grow by
    the shards' static counts."""
    journal = tracing.TraceJournal(maxlen=512)
    with tracing.use_journal(journal):
        manager = scripted_manager()
        opt = ZeroOptimizer(
            manager, optax.sgd(0.2, momentum=0.9),
            {"w": jnp.array([1.0, -2.0, 3.0], jnp.float32)}, num_shards=4,
        )
        step_fn = opt.make_step_fn(lambda p, b: jnp.sum((p["w"] - b) ** 2))
        step_fn(_batch(0))  # bootstraps the four shards
        before = _dispatch_totals()
        observed = metrics.histogram_stats("tpuft_update_dispatch_seconds")["count"]
        seen = len(journal.snapshot())
        _, committed = step_fn(_batch(1))
    assert committed and sorted(opt.opt_state.held) == [0, 1, 2, 3]
    n_opt = len(opt._opt_leaf_templates)
    assert n_opt == 1  # the momentum trace
    # Gradient program: params and batch in, loss and gradients out; shard
    # update: range, master and optax state of 4 shards in, the last two out.
    assert _growth(before) == (2, (1 + 1) + 4 * (2 + n_opt), (1 + 1) + 4 * (1 + n_opt))
    assert metrics.histogram_stats("tpuft_update_dispatch_seconds")["count"] == observed + 2
    fresh = [e for e in journal.snapshot()[seen:] if e["name"] == "update_dispatch"]
    (root,) = [e for e in journal.snapshot()[seen:] if e["name"] == "step" and e["ph"] == "X"]
    assert len(fresh) == 2 and all(_inside(e, root) for e in fresh)
    assert tracing.PHASES["update_dispatch"].annotation == "tpuft::optim::update_dispatch"


def test_zero_rebalance_and_heal_open_a_new_era():
    manager = scripted_manager()
    opt = ZeroOptimizer(
        manager, optax.sgd(0.2, momentum=0.9),
        {"w": jnp.array([1.0, -2.0, 3.0], jnp.float32)}, num_shards=2,
    )
    step_fn = opt.make_step_fn(lambda p, b: jnp.sum((p["w"] - b) ** 2))
    era = opt._state_era
    step_fn(_batch(0))  # the first quorum balances the shards
    assert opt._state_era == era + 1
    step_fn(_batch(1))  # a steady step sets no structure
    assert opt._state_era == era + 1
    assert metrics.gauge_value("tpuft_history_bytes", ring="state") == (
        _nbytes(opt.params) + opt.opt_state.owned_bytes()
    )
    opt._load_state_dict(opt._state_dict())
    assert opt._state_era == era + 2


# ---------------------------------------------------------------------------
# the byte accounting
# ---------------------------------------------------------------------------


def _count_snapshot_walks(monkeypatch):
    """Counts the walks ``_snapshot_nbytes`` makes (its ``is_leaf`` is its
    own lambda)."""
    walks = []
    real = jax.tree_util.tree_leaves

    def spy(tree, is_leaf=None):
        if is_leaf is not None and "_snapshot_nbytes" in getattr(is_leaf, "__qualname__", ""):
            walks.append(tree)
        return real(tree, is_leaf=is_leaf)

    monkeypatch.setattr(jax.tree_util, "tree_leaves", spy)
    return walks


def test_strict_history_bytes_gauge_reads_the_states_bytes_from_one_walk(monkeypatch):
    opt, run = _setup("lone")
    walks = _count_snapshot_walks(monkeypatch)
    for i in range(5):
        assert run(_batch(i))
        # What the gauge read before the cache: the live state's leaves by nbytes.
        assert metrics.gauge_value("tpuft_history_bytes", ring="state") == _nbytes(
            opt.params, opt.opt_state
        ) == 3 * 4 + 2 * 4 + 4 * 2 + 4 + 2 * (3 * 4 + 2 * 4 + 4 * 2)
    assert len(walks) == 1
    assert opt._state_nbytes == (opt._state_era, _nbytes(opt.params, opt.opt_state))


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_pipelined_snapshot_bytes_gauge_reads_the_windows_bytes_from_one_walk(depth, monkeypatch):
    manager = scripted_manager(commit_pipeline_depth=depth)
    opt = Optimizer(manager, optax.adam(0.1), _params())
    step_fn = opt.make_step_fn(_loss)
    walks = _count_snapshot_walks(monkeypatch)
    one = _nbytes(opt.params, opt.opt_state)
    for i in range(6):
        step_fn(_batch(i))
        in_flight = opt.pending_commits()
        assert in_flight == min(i + 1, depth)
        assert metrics.gauge_value("tpuft_pipeline_snapshot_bytes") == in_flight * one
        assert opt._snapshot_ring_bytes == in_flight * one
    assert opt.flush_pipeline() is True
    assert metrics.gauge_value("tpuft_pipeline_snapshot_bytes") == 0
    assert metrics.gauge_value("tpuft_history_bytes", ring="state") == one * len(
        manager.history.resident_steps()
    )
    assert len(walks) == 1


def test_byte_count_is_taken_again_after_load_state_dict(monkeypatch):
    opt, run = _setup("lone", tx=optax.sgd(0.1))
    walks = _count_snapshot_walks(monkeypatch)
    assert run(_batch(0)) and run(_batch(1))
    small = _nbytes(opt.params, opt.opt_state)
    assert metrics.gauge_value("tpuft_history_bytes", ring="state") == small
    opt._load_state_dict({
        "params": {
            "w": np.ones(300, np.float32),
            "pair": (np.ones(2, np.float32), np.ones((2, 2), np.float32)),
        },
        "opt_state": opt.opt_state,
    })
    assert run(_batch(2)) and run(_batch(3))
    large = _nbytes(opt.params, opt.opt_state)
    assert large > small + 1000
    assert metrics.gauge_value("tpuft_history_bytes", ring="state") == large
    assert len(walks) == 2  # once a structure


def test_a_heal_during_the_walk_leaves_no_stale_byte_count():
    """The era is read before the walk: a count measured across a heal is
    filed under the era it began in, which no later step reads."""
    opt, _run = _setup("lone", tx=optax.sgd(0.1))
    pair = (opt.params, opt.opt_state)

    class Racing:
        """A leaf whose ``owned_bytes`` lands a heal mid-walk."""

        def owned_bytes(self):
            opt._note_state_structure()
            return 7

    era = opt._state_era
    assert opt._snapshot_nbytes((pair, Racing())) == _nbytes(pair) + 7
    assert opt._state_era == era + 1 and opt._state_nbytes[0] == era
    assert opt._snapshot_nbytes(pair) == _nbytes(pair)  # walked again, not the stale 7 more
    assert opt._state_nbytes == (era + 1, _nbytes(pair))


def test_weight_history_evict_span_only_when_a_version_leaves():
    journal = tracing.TraceJournal(maxlen=64)
    hist = WeightHistory(max_versions=2, journal=journal)
    evictions = metrics.counter_total("tpuft_history_evictions_total")
    hist.note_state("optimizer", 1, {"w": 1}, nbytes=10)
    hist.note_state("optimizer", 2, {"w": 2}, nbytes=10)
    hist.note_state("optimizer", 2, {"w": 2}, nbytes=10)  # idempotent: none leaves
    assert _spans(journal, "history_evict") == []
    hist.note_state("optimizer", 3, {"w": 3}, nbytes=10)
    hist.note_accounting(5, 40)  # the accounting half evicts too: 3 and 5 stay
    assert len(_spans(journal, "history_evict")) == 2
    assert metrics.counter_total("tpuft_history_evictions_total") == evictions + 2
    assert hist.resident_steps() == [3, 5]
    hist.retract_newer(3)
    hist.clear()  # retraction and clearing are no eviction
    assert len(_spans(journal, "history_evict")) == 2


# ---------------------------------------------------------------------------
# who owns the step's state: vote first, then update in place
# ---------------------------------------------------------------------------


def _state(opt):
    return jax.tree_util.tree_leaves((opt.params, opt.opt_state))


def _host(tree):
    # A copy: on the CPU ``np.asarray`` is a view of the device's buffer, and
    # a buffer with a view outstanding is quietly not given away.
    return jax.tree_util.tree_map(np.array, tree)


def _copies(key="optimizer"):
    return metrics.counter_total("tpuft_state_snapshot_copies_total", key=key)


def _donated_kept():
    return (
        metrics.counter_total("tpuft_step_state_donated_total"),
        metrics.counter_total("tpuft_step_state_kept_total"),
    )


def test_committed_lone_step_deletes_the_state_it_replaces():
    """The donated program is given ``params`` and ``opt_state``: after a
    committed step every array of the state before it is deleted, none of
    the new state's is, the caller's arrays among the former (they are the
    Optimizer's from construction), and the ring's one version is the new
    state by reference. No copy is made anywhere."""
    given = _params()
    copies = _copies()
    manager = scripted_manager()
    opt = Optimizer(manager, optax.adam(0.1), given)
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(given), jax.tree_util.tree_leaves(opt.params)))
    step_fn = opt.make_step_fn(_loss)
    for i in range(3):
        before = _state(opt)
        loss, committed = step_fn(_batch(i))
        assert committed and np.isfinite(float(loss))
        assert all(x.is_deleted() for x in before)
        assert not any(x.is_deleted() for x in _state(opt))
        entry = manager.history._entries[manager.current_step()]
        assert entry.states["optimizer"]["params"] is opt.params
        assert entry.states["optimizer"]["opt_state"] is opt.opt_state
    assert all(x.is_deleted() for x in jax.tree_util.tree_leaves(given))
    assert _copies() == copies


@pytest.mark.parametrize("why", ["too_few_replicas", "reported_error"])
def test_refused_lone_step_dispatches_nothing_to_the_state(why):
    """A refused verdict comes BEFORE any dispatch: no step program runs, the
    state is the very objects it was, alive, the manager's step stays, and
    the call still returns ``(loss, False)``, the loss from the program that
    takes nothing."""
    journal = tracing.TraceJournal(maxlen=256)
    with tracing.use_journal(journal):
        manager = scripted_manager(
            min_replica_size=2 if why == "too_few_replicas" else 1
        )
        opt = Optimizer(manager, optax.adam(0.1), _params())
        step_fn = opt.make_step_fn(_loss)
        if why == "reported_error":
            # An error that lands after the step chose its path (another
            # thread's, in a deployment): the vote carries it.
            lone = manager.is_lone_replica
            manager.is_lone_replica = lambda: (
                manager.report_error(RuntimeError("injected")), lone()
            )[1]
        state, params, opt_state = _state(opt), opt.params, opt.opt_state
        want = float(_loss(_host(opt.params), np.asarray(_batch(1))))
        before, counted = _dispatch_totals(), _donated_kept()
        loss, committed = step_fn(_batch(1))
    assert committed is False and float(loss) == pytest.approx(want, rel=1e-6)
    assert manager.current_step() == 0
    assert opt.params is params and opt.opt_state is opt_state
    assert all(a is b for a, b in zip(_state(opt), state))
    assert not any(x.is_deleted() for x in state)
    assert _growth(before) == (0, 0, 0) and _donated_kept() == counted
    for name in ("update_dispatch", "adopt", "state_swap", "history_promote"):
        assert _spans(journal, name) == []
    assert len(_spans(journal, "commit_wait")) == 1


def test_donated_steps_equal_kept_steps_bit_for_bit(monkeypatch):
    """The same program on the same inputs: N steps that update in place and
    N that keep both copies (``TPUFT_STRICT_COMMIT=1``) end in bit-equal
    state and return bit-equal losses."""

    def run(strict):
        monkeypatch.setenv("TPUFT_STRICT_COMMIT", "1" if strict else "0")
        counted = _donated_kept()
        opt = Optimizer(scripted_manager(), optax.adamw(0.05), _params())
        step_fn = opt.make_step_fn(_loss)
        losses = [np.asarray(step_fn(_batch(i))[0]) for i in range(6)]
        grew = tuple(n - w for n, w in zip(_donated_kept(), counted))
        assert grew == ((0, 6) if strict else (6, 0))
        return losses, _host((opt.params, opt.opt_state))

    donated, kept = run(False), run(True)
    for a, b in zip(jax.tree_util.tree_leaves(donated), jax.tree_util.tree_leaves(kept)):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def _kept_setup(case, monkeypatch):
    """An optimizer whose step may give nothing away, and ``run(batch) ->
    committed``."""
    tx = optax.sgd(0.2, momentum=0.9)
    if case == "strict":
        monkeypatch.setenv("TPUFT_STRICT_COMMIT", "1")
    if case == "ring_of_two":
        monkeypatch.setenv("TPUFT_HISTORY_MAX_VERSIONS", "2")
    manager = scripted_manager(commit_pipeline_depth=1 if case == "depth_1" else 0)
    if case == "zero":
        opt = ZeroOptimizer(manager, tx, _params(), num_shards=2)
    elif case == "tied_leaves":
        w = jnp.ones(3, jnp.float32)
        opt = Optimizer(manager, tx, {"w": w, "tied": w})
        assert opt._state_aliased
    else:
        opt = Optimizer(manager, tx, _params())
    if case == "wire":
        manager.is_lone_replica = lambda: False
    step_fn = opt.make_step_fn(
        lambda p, b: sum(jnp.sum((x.astype(jnp.float32) - 0.5) ** 2) for x in jax.tree_util.tree_leaves(p)) + jnp.sum(b)
    )
    return manager, opt, step_fn


@pytest.mark.parametrize(
    "case", ["strict", "depth_1", "ring_of_two", "wire", "zero", "tied_leaves"]
)
def test_a_step_that_may_give_nothing_away_deletes_nothing(case, monkeypatch):
    """Where the old state may still be asked for (strict mode votes after
    completion, a window or a ring of two holds older versions by reference,
    the wire path waits for its peers, ZeRO's programs differ, a tied array
    cannot be given twice) the step is the speculative one: every array ever
    read from the optimizer stays readable."""
    manager, opt, step_fn = _kept_setup(case, monkeypatch)
    counted = _donated_kept()
    seen = list(jax.tree_util.tree_leaves(opt.params))
    for i in range(4):
        step_fn(_batch(i))
        seen += [x for x in jax.tree_util.tree_leaves(opt.params) if isinstance(x, jax.Array)]
    opt.flush_pipeline()
    assert manager.current_step() == 4
    assert not any(x.is_deleted() for x in seen)
    assert _donated_kept()[0] == counted[0]
    assert _donated_kept()[1] >= counted[1] + 4


def test_state_dict_read_racing_the_step_never_sees_a_deleted_array():
    """A reader that takes the state-dict read lock in a loop (what a
    checkpoint serve does) while the step updates in place: the dispatch that
    deletes the old arrays, the rebinding and the ring's promotion are one
    write-locked section, so under the read lock the registered state and
    the ring's version are always whole and alive."""
    manager = scripted_manager()
    opt = Optimizer(manager, optax.adam(0.1), _params())
    step_fn = opt.make_step_fn(_loss)
    step_fn(_batch(0))
    stop, seen, reads = threading.Event(), [], [0]

    def reader():
        try:
            while not stop.is_set():
                with manager._state_dict_lock.r_lock(timeout=10):
                    state = manager._user_state_dicts["optimizer"]()
                    with manager.history._lock:
                        ring = [e.states.get("optimizer") for e in manager.history._entries.values()]
                    for x in jax.tree_util.tree_leaves((state, ring)):
                        assert not x.is_deleted()
                    total = sum(float(jnp.sum(x)) for x in jax.tree_util.tree_leaves(state["params"]))
                    assert np.isfinite(total)
                    reads[0] += 1
        except BaseException as e:  # noqa: BLE001 - handed to the test's thread
            seen.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=reader, daemon=True) for _ in range(3)]
    try:
        for t in threads:
            t.start()
        for i in range(60):
            before = _state(opt)
            _, committed = step_fn(_batch(i))
            assert committed and all(x.is_deleted() for x in before)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert seen == [] and reads[0] > 0


def test_a_capture_from_the_ring_of_one_version_outlives_the_steps_after_it():
    """``state_dict_at`` on a ring of one version (what a deep donor or a
    publisher that pins a step would hold past the step): a device copy,
    counted under the registered key, that still reads step N's values after
    step N + 2 deleted step N's arrays. A steady run without such a capture
    counts none."""
    manager = scripted_manager()
    opt = Optimizer(manager, optax.adam(0.1), _params())
    step_fn = opt.make_step_fn(_loss)
    copies = _copies()
    for i in range(3):
        step_fn(_batch(i))
    assert _copies() == copies  # nothing copies in a steady run
    at_n = _host(opt.params)
    live = _state(opt)
    with manager._state_dict_lock.r_lock(timeout=1):  # as Manager._history_state_dict
        capture = manager.history.state_dict_at(3, {"optimizer"})
    assert _copies() == copies + 1
    assert capture["tpuft"] == {"step": 3, "batches_committed": 3}
    held = capture["user"]["optimizer"]
    assert not any(
        a is b for a in jax.tree_util.tree_leaves(held) for b in live
    )
    for i in range(3, 5):
        step_fn(_batch(i))
    assert all(x.is_deleted() for x in live)
    for a, b in zip(jax.tree_util.tree_leaves(_host(held["params"])), jax.tree_util.tree_leaves(at_n)):
        assert a.tobytes() == b.tobytes()
    assert manager.history.state_dict_at(3, {"optimizer"}) is None  # the ring moved on


def test_a_ring_of_more_versions_hands_out_references():
    copies = _copies()
    hist = WeightHistory(max_versions=2)
    state = {"params": {"w": jnp.ones(3)}}
    hist.note_state("optimizer", 1, state, nbytes=12)
    hist.note_accounting(1, 1)
    assert hist.state_dict_at(1, {"optimizer"})["user"]["optimizer"] is state
    assert _copies() == copies


def test_donated_counter_and_the_dispatch_events_field():
    """``tpuft_step_state_donated_total`` grows by one a step that updated in
    place, ``tpuft_step_state_kept_total`` by one a step that kept both
    copies, and the ``update_dispatch`` event says which beside ``fused``."""
    journal = tracing.TraceJournal(maxlen=512)
    with tracing.use_journal(journal):
        opt, run = _setup("lone")
        for i in range(3):
            counted = _donated_kept()
            assert run(_batch(i))
            assert _donated_kept() == (counted[0] + 1, counted[1])
        kept_opt, kept_run = _setup("wire")
        counted = _donated_kept()
        assert kept_run(_batch(0))
        assert _donated_kept() == (counted[0], counted[1] + 1)
    events = _spans(journal, "update_dispatch")
    args = [e.get("args") or {} for e in events]
    assert [(a.get("fused"), a.get("donated")) for a in args] == [
        (True, True)
    ] * 3 + [(None, None)]


def test_a_failure_after_a_true_verdict_is_a_phantom_commit():
    """The verdict is in before the dispatch, so a dispatch that fails on the
    host (here: a batch the program cannot take) has advanced the step
    counter without its update: counted and journalled as the phantom commit
    the speculative order already knows, and raised. The state is what it
    was: the failed call gave nothing away."""
    journal = tracing.TraceJournal(maxlen=256)
    phantoms = metrics.counter_total("tpuft_phantom_commits_total")
    with tracing.use_journal(journal):
        manager = scripted_manager()
        opt = Optimizer(manager, optax.sgd(0.1), _params())
        step_fn = opt.make_step_fn(lambda p, b: _loss(p, b) + jnp.sum(b @ p["w"]))
        state = _state(opt)
        with pytest.raises(TypeError):
            step_fn(jnp.ones((2, 5), jnp.float32))  # 5 against w's 3
    assert manager.current_step() == 1
    assert metrics.counter_total("tpuft_phantom_commits_total") == phantoms + 1
    assert [e["name"] for e in journal.snapshot() if e["name"] == "phantom_commit"] == ["phantom_commit"]
    assert all(a is b for a, b in zip(_state(opt), state))
    assert not any(x.is_deleted() for x in state)
    # The write lock was released on the way out.
    with manager._state_dict_lock.r_lock(timeout=1):
        pass
