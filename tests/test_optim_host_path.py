"""The FT step's host path from inside (PR 59): ``adopt``'s children in the
journal, the counters of what a dispatch hands to the runtime and takes
back, and the byte accounting that no longer walks the state every committed
step. On the CPU; the runtime's own events under a span are
tests/test_tracing.py's."""

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


def test_history_evict_is_where_the_version_leaves():
    """With a real manager the commit tail's ``note_accounting`` makes the new
    entry, so the previous version leaves the ring THERE, on the commit
    thread after the barrier, before ``adopt`` begins; ``adopt``'s promotion
    finds nothing to evict. A ring whose accounting half arrives
    late (a scripted manager's) evicts inside ``history_promote``."""
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
        for evict in evicts:
            adopt = adopts[evict["step"]]
            assert evict["thread"] != adopt["thread"]
            assert evict["t_mono"] + evict["dur"] <= adopt["t_mono"]
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
