"""LocalSGD / DiLoCo unit tests against mocked coordination (parity:
local_sgd_test.py) plus golden-file numerics regression (parity:
diloco_regression_test.py)."""

import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ft_harness import diloco_live_state
from test_manager import make_manager, make_quorum
from test_quantization import interpreted_kernels  # noqa: F401  (a fixture)

from torchft_tpu.local_sgd import DiLoCo, LocalSGD
from torchft_tpu.parallel.process_group import ProcessGroupDummy

FIXTURES = Path(__file__).parent / "fixtures"


def make_params():
    return {
        "w1": jnp.array([1.0, 2.0], dtype=jnp.float32),
        "w2": jnp.array([[3.0], [4.0]], dtype=jnp.float32),
        "b": jnp.array([0.5], dtype=jnp.float32),
    }


def fixed_grads(step: int):
    return {
        "w1": jnp.full(2, 0.1 * (step + 1), dtype=jnp.float32),
        "w2": jnp.full((2, 1), 0.2, dtype=jnp.float32),
        "b": jnp.array([0.05], dtype=jnp.float32),
    }


def scripted_manager(**kwargs):
    kwargs.setdefault("min_replica_size", 1)
    manager, client, pg, transport = make_manager(pg=ProcessGroupDummy(), **kwargs)
    client._quorum.return_value = make_quorum(replica_world_size=1, max_world_size=1)
    client.should_commit.side_effect = lambda rank, step, vote, timeout: vote
    return manager


# -- LocalSGD ---------------------------------------------------------------


def test_local_sgd_syncs_every_n_steps() -> None:
    manager = scripted_manager()
    algo = LocalSGD(manager, optax.sgd(0.1), make_params(), sync_every=2)

    assert not algo.step(fixed_grads(0))  # local only
    assert algo.step(fixed_grads(1))  # sync round commits
    # With a single participant averaging is identity: params equal plain SGD.
    expected = make_params()
    opt_state = optax.sgd(0.1).init(expected)
    for s in range(2):
        updates, opt_state = optax.sgd(0.1).update(fixed_grads(s), opt_state, expected)
        expected = optax.apply_updates(expected, updates)
    for key in expected:
        np.testing.assert_allclose(algo.params[key], expected[key], rtol=1e-6)


def test_local_sgd_sync_preserves_shardings() -> None:
    """The parameter-averaging sync rides the shard-preserving path: after
    a committed sync, sharded leaves keep their NamedShardings (a host
    round-trip that re-landed them replicated would desync multi-rank
    groups' jitted programs, and a whole-leaf fetch would raise outright
    on non-fully-addressable state)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("fsdp", "tp"))
    sharding = NamedSharding(mesh, P("fsdp", "tp"))
    params = {
        "w": jax.device_put(jnp.ones((4, 4), jnp.float32), sharding),
        "b": jnp.zeros((3,), jnp.float32),
    }
    manager = scripted_manager()
    algo = LocalSGD(manager, optax.sgd(0.1), params, sync_every=1)
    grads = {
        "w": jax.device_put(jnp.full((4, 4), 0.5, jnp.float32), sharding),
        "b": jnp.full((3,), 0.1, jnp.float32),
    }
    assert algo.step(grads)  # sync round commits
    assert algo.params["w"].sharding == sharding
    np.testing.assert_allclose(
        np.asarray(algo.params["w"]), np.full((4, 4), 0.95), rtol=1e-6
    )


def test_local_sgd_failed_commit_keeps_local_params() -> None:
    manager = scripted_manager()
    manager._client.should_commit.side_effect = None
    manager._client.should_commit.return_value = False
    algo = LocalSGD(manager, optax.sgd(0.1), make_params(), sync_every=1)
    committed = algo.step(fixed_grads(0))
    assert not committed
    # Local inner step still applied.
    assert not np.allclose(algo.params["w1"], make_params()["w1"])


# -- DiLoCo -----------------------------------------------------------------


def test_diloco_requires_sync_quorum() -> None:
    manager = scripted_manager(use_async_quorum=True)
    with pytest.raises(ValueError, match="synchronous quorum"):
        DiLoCo(manager, optax.sgd(0.1), optax.sgd(1.0), make_params(), sync_every=2)


def test_diloco_validations() -> None:
    manager = scripted_manager(use_async_quorum=False)
    with pytest.raises(ValueError, match="multiple"):
        DiLoCo(
            manager, optax.sgd(0.1), optax.sgd(1.0), make_params(),
            sync_every=3, n_fragments=2,
        )
    with pytest.raises(ValueError, match="synced before"):
        DiLoCo(
            manager, optax.sgd(0.1), optax.sgd(1.0), make_params(),
            sync_every=2, n_fragments=1, fragment_sync_delay=5,
        )


def test_diloco_outer_step_applies_averaged_pseudogradient() -> None:
    manager = scripted_manager(use_async_quorum=False)
    inner = optax.sgd(0.1)
    outer = optax.sgd(1.0)  # lr=1: global = backup - avg pseudograd exactly
    algo = DiLoCo(manager, inner, outer, make_params(), sync_every=2)

    p0 = make_params()
    assert not algo.step(fixed_grads(0))
    assert algo.step(fixed_grads(1))

    # Single participant: avg pseudograd == backup - local. Outer SGD(lr=1)
    # on the backup gives exactly the local params; alpha=0 takes the global.
    inner_state = inner.init(p0)
    local = p0
    for s in range(2):
        updates, inner_state = inner.update(fixed_grads(s), inner_state, local)
        local = optax.apply_updates(local, updates)
    for key in local:
        np.testing.assert_allclose(algo.params[key], local[key], rtol=1e-6)


def test_diloco_failed_commit_restores_global_params() -> None:
    manager = scripted_manager(use_async_quorum=False)
    manager._client.should_commit.side_effect = None
    manager._client.should_commit.return_value = False
    p0 = make_params()
    algo = DiLoCo(manager, optax.sgd(0.1), optax.sgd(0.7), p0, sync_every=1)
    committed = algo.step(fixed_grads(0))
    assert not committed
    # Failed sync resets the fragment to the last global state (= init).
    for key in p0:
        np.testing.assert_allclose(algo.params[key], p0[key], rtol=1e-6)


def test_diloco_fragments_rotate_and_cover_all_leaves() -> None:
    manager = scripted_manager(use_async_quorum=False)
    algo = DiLoCo(
        manager, optax.sgd(0.1), optax.sgd(1.0), make_params(),
        sync_every=2, n_fragments=2,
    )
    covered = sorted(i for frag in algo._fragments for i in frag.leaf_indices)
    assert covered == list(range(3))
    # Fragment choice keyed by manager step.
    assert algo._current_fragment() == 0
    manager._step = 1
    assert algo._current_fragment() == 1


def test_diloco_update_alpha_mixes_local_and_global() -> None:
    manager = scripted_manager(use_async_quorum=False)
    p0 = make_params()
    algo = DiLoCo(
        manager, optax.sgd(0.1), optax.sgd(1.0), p0, sync_every=1,
        fragment_update_alpha=1.0,  # keep local entirely
    )
    inner = optax.sgd(0.1)
    inner_state = inner.init(p0)
    updates, _ = inner.update(fixed_grads(0), inner_state, p0)
    local = optax.apply_updates(p0, updates)
    algo.step(fixed_grads(0))
    for key in local:
        np.testing.assert_allclose(algo.params[key], local[key], rtol=1e-6)


# -- golden-file regression (parity: diloco_regression_test.py) -------------


def check_or_regen_golden(name: str, history: list) -> None:
    """Compares a parameter history to the committed fixture (or regenerates
    it under TPUFT_REGEN_FIXTURES=1)."""
    path = FIXTURES / name
    if os.environ.get("TPUFT_REGEN_FIXTURES") == "1":
        FIXTURES.mkdir(exist_ok=True)
        path.write_text(json.dumps(history, indent=1))
        pytest.skip("regenerated fixture")
    assert path.exists(), f"fixture {name} missing; run with TPUFT_REGEN_FIXTURES=1"
    golden = json.loads(path.read_text())
    assert len(golden) == len(history), "fixture/history length mismatch"
    for step, (got, want) in enumerate(zip(history, golden)):
        for key in want:
            np.testing.assert_allclose(
                got[key], want[key], rtol=1e-6, err_msg=f"step {step} key {key}"
            )


@pytest.mark.parametrize(
    "n_fragments,sync_delay,alpha",
    [(1, 0, 0.0), (2, 0, 0.0), (2, 1, 0.0), (2, 0, 0.5)],
)
def test_diloco_golden_history(n_fragments, sync_delay, alpha) -> None:
    manager = scripted_manager(use_async_quorum=False)
    algo = DiLoCo(
        manager,
        optax.sgd(0.1),
        optax.sgd(0.7, momentum=0.9, nesterov=True),
        make_params(),
        sync_every=4,
        n_fragments=n_fragments,
        fragment_sync_delay=sync_delay,
        fragment_update_alpha=alpha,
    )
    history = []
    for step in range(12):
        algo.step(fixed_grads(step))
        history.append(
            {k: np.asarray(v).tolist() for k, v in sorted(algo.params.items())}
        )

    check_or_regen_golden(f"diloco_f{n_fragments}_d{sync_delay}_a{alpha}.json", history)


@pytest.mark.parametrize("fail_sync_index", [1])
def test_diloco_failure_timeline_golden(fail_sync_index: int) -> None:
    """Failure-recovery timeline numerics (parity: diloco_regression_test.py
    mocked failure timelines :288-639): a commit failure at sync round k
    resets the in-flight fragment to its last global state, and the
    subsequent history matches the committed fixture."""
    manager = scripted_manager(use_async_quorum=False)
    sync_calls = [0]

    def should_commit(rank, step, vote, timeout):
        sync_calls[0] += 1
        if sync_calls[0] - 1 == fail_sync_index:
            return False
        return vote

    manager._client.should_commit.side_effect = should_commit

    algo = DiLoCo(
        manager,
        optax.sgd(0.1),
        optax.sgd(0.7, momentum=0.9, nesterov=True),
        make_params(),
        sync_every=2,
        n_fragments=1,
    )
    history = []
    committed_flags = []
    for step in range(10):
        committed_flags.append(algo.step(fixed_grads(step)))
        history.append(
            {k: np.asarray(v).tolist() for k, v in sorted(algo.params.items())}
        )
    # The scripted failure lands at sync round fail_sync_index (sync rounds
    # commit on steps 2k+1 with sync_every=2).
    for sync_round in range(5):
        expected = sync_round != fail_sync_index
        assert committed_flags[2 * sync_round + 1] is expected, sync_round

    check_or_regen_golden(
        f"diloco_failure_timeline_{fail_sync_index}.json", history
    )


def test_heal_restore_preserves_shardings() -> None:
    """Healing restores state onto the EXISTING leaves' shardings: a
    joiner whose params carry fsdp/tp NamedShardings must not end up with
    replicated arrays after _load_inner/_load_state (replicated restores
    made the joiner's jitted programs partition differently from the
    donor's — one-ulp drift per sync, breaking the bitwise invariant)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchft_tpu.local_sgd import _restore_like

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("fsdp", "tp"))
    sharding = NamedSharding(mesh, P("fsdp", "tp"))
    params = {
        "w": jax.device_put(jnp.ones((4, 4), jnp.float32), sharding),
        "b": jnp.zeros((3,), jnp.float32),
    }

    manager = scripted_manager(use_async_quorum=False)
    algo = DiLoCo(
        manager, optax.sgd(1.0), optax.sgd(1.0), params,
        sync_every=2, n_fragments=2, should_quantize=True,
    )
    # Simulate a heal: host-numpy state (what the checkpoint wire carries).
    algo._load_inner(
        {
            "leaves": [np.full((3,), 7.0, np.float32), np.full((4, 4), 5.0, np.float32)],
            "opt_state": jax.tree_util.tree_map(
                lambda x: np.asarray(x) if hasattr(x, "shape") else x,
                algo.inner_opt_state,
            ),
        }
    )
    # Flatten order: "b" then "w" (sorted dict keys) — w is leaf 1.
    healed_w = algo._leaves[1]
    assert healed_w.sharding == sharding, healed_w.sharding
    np.testing.assert_array_equal(np.asarray(healed_w), np.full((4, 4), 5.0))

    # Quantized fragments keep device backups: heal restores their
    # shardings too (fragment 1 owns leaf index 1 = w).
    frag = algo._fragments[1]
    frag._load_state(
        {
            "original_parameters": [np.full((4, 4), 9.0, np.float32)],
            "outer_optimizer": jax.tree_util.tree_map(
                lambda x: np.asarray(x) if hasattr(x, "shape") else x,
                frag.outer_opt_state,
            ),
        }
    )
    assert frag.backup[0].sharding == sharding
    np.testing.assert_array_equal(np.asarray(frag.backup[0]), np.full((4, 4), 9.0))

    # Structure mismatch falls back to a plain restore instead of raising.
    out = _restore_like({"different": np.ones(2, np.float32)}, {"x": 1}, device=True)
    assert isinstance(out["different"], jax.Array)

    # LocalSGD heal restores the params' shardings the same way.
    algo2 = LocalSGD(manager, optax.sgd(1.0), params, sync_every=2, register_key="ls2")
    algo2._load_state(
        {
            "params": {
                "w": np.full((4, 4), 3.0, np.float32),
                "b": np.zeros((3,), np.float32),
            },
            "opt_state": jax.tree_util.tree_map(
                lambda x: np.asarray(x) if hasattr(x, "shape") else x,
                algo2.opt_state,
            ),
        }
    )
    assert algo2.params["w"].sharding == sharding


def test_diloco_fused_step_matches_grads_path() -> None:
    """make_step_fn (fused loss+update dispatch) produces bitwise the same
    trajectory as step(grads) with the same schedule."""

    def loss_fn(params, x):
        pred = x @ params["w2"] * params["w1"].sum() + params["b"]
        return (pred**2).mean()

    x = jnp.full((4, 2), 0.1, dtype=jnp.float32)

    managers = [scripted_manager(), scripted_manager()]
    algos = [
        DiLoCo(
            m,
            inner_tx=optax.sgd(0.01),
            outer_tx=optax.sgd(0.7, momentum=0.9, nesterov=True),
            params=make_params(),
            sync_every=4,
            n_fragments=2,
        )
        for m in managers
    ]
    fused = algos[1].make_step_fn(loss_fn)

    for step in range(8):
        grads = jax.grad(loss_fn)(algos[0].params, x)
        committed_a = algos[0].step(grads)
        loss, committed_b = fused(x)
        assert committed_a == committed_b
        assert float(loss) >= 0.0
    for leaf_a, leaf_b in zip(
        jax.tree_util.tree_leaves(algos[0].params),
        jax.tree_util.tree_leaves(algos[1].params),
    ):
        np.testing.assert_array_equal(np.asarray(leaf_a), np.asarray(leaf_b))


def test_local_sgd_make_step_fn_fused_matches_plain() -> None:
    """The fused inner step must reproduce the exact plain trajectory (one
    jitted program per step) and sync/commit at the boundary."""
    manager = scripted_manager()
    tx = optax.sgd(0.2, momentum=0.9)
    params = {"w": jnp.array([1.0, -2.0, 3.0], jnp.float32)}
    algo = LocalSGD(manager, tx, params, sync_every=3)

    def loss_fn(p, batch):
        return jnp.sum((p["w"] - batch) ** 2)

    step_fn = algo.make_step_fn(loss_fn)
    batches = [jnp.full((3,), 0.1 * i, jnp.float32) for i in range(6)]
    synced = []
    for batch in batches:
        _, s = step_fn(batch)
        synced.append(s)
    assert synced == [False, False, True, False, False, True]

    # Identically-structured fused plain program (single participant:
    # averaging is identity, so the trajectory must match bitwise).
    @jax.jit
    def fused(p, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(p, batch)
        updates, opt_state = tx.update(grads, opt_state, p)
        return loss, optax.apply_updates(p, updates), opt_state

    expected, opt_state = params, tx.init(params)
    for batch in batches:
        _, expected, opt_state = fused(expected, opt_state, batch)
    np.testing.assert_array_equal(
        np.asarray(algo.params["w"]), np.asarray(expected["w"])
    )


# -- the fragment sync's own spans (tracing.phase) ---------------------------


def _inside(child, parent) -> bool:
    return (
        parent["t_mono"] <= child["t_mono"]
        and child["t_mono"] + child["dur"] <= parent["t_mono"] + parent["dur"] + 1e-9
    )


@pytest.mark.parametrize("quantize", [False, True])
def test_fragment_sync_leaves_its_stages_in_the_journal(quantize) -> None:
    """A streaming DiLoCo round: every inner step is a root ``step`` span
    numbered by the inner step; prepare_sync holds quantize and launch,
    perform_sync holds wait, restore, commit and apply_outer, each stamped
    with the fragment and the manager's step, and each a sample of
    tpuft_outer_sync_seconds under its stage."""
    from torchft_tpu import metrics, tracing

    stages = ("quantize", "launch", "wait", "restore", "commit", "apply_outer")
    before = {
        s: metrics.histogram_stats("tpuft_outer_sync_seconds", stage=s)["count"]
        for s in stages
    }
    journal = tracing.TraceJournal(maxlen=1024)
    with tracing.use_journal(journal):
        manager = scripted_manager(use_async_quorum=False)
        algo = DiLoCo(
            manager, optax.sgd(0.1), optax.sgd(0.7), make_params(), sync_every=4,
            n_fragments=2, fragment_sync_delay=1, should_quantize=quantize,
        )
        committed = [algo.step(fixed_grads(i)) for i in range(4)]
    assert committed == [False, True, False, True]
    spans = [e for e in journal.snapshot() if e["ph"] == "X"]
    roots = [e for e in spans if e["name"] == "step"]
    assert [r["args"]["inner_step"] for r in roots] == [0, 1, 2, 3]
    by_name = lambda n: [e for e in spans if e["name"] == n]  # noqa: E731
    for fragment, (launch_root, sync_root) in enumerate([(0, 1), (2, 3)]):
        (prepare,) = [e for e in by_name("prepare_sync") if e["args"]["fragment"] == fragment]
        (perform,) = [e for e in by_name("perform_sync") if e["args"]["fragment"] == fragment]
        assert _inside(prepare, roots[launch_root]) and _inside(perform, roots[sync_root])
        assert prepare["step"] == perform["step"] == fragment  # the step it commits
        for parent, children in (
            (prepare, ("sync_quantize", "sync_launch")),
            (perform, ("sync_wait", "sync_restore", "sync_commit", "sync_apply_outer")),
        ):
            starts = []
            for name in children:
                (child,) = [e for e in by_name(name) if e["args"]["fragment"] == fragment]
                assert _inside(child, parent) and child["step"] == parent["step"]
                starts.append(child["t_mono"])
            assert starts == sorted(starts), "stages out of order"
        # The manager's own span sits inside the stage that calls it.
        (commit,) = [e for e in by_name("sync_commit") if e["args"]["fragment"] == fragment]
        assert any(_inside(b, commit) for b in by_name("commit_barrier"))
    for s in stages:
        after = metrics.histogram_stats("tpuft_outer_sync_seconds", stage=s)["count"]
        assert after - before[s] == 2, s


def test_failed_sync_still_closes_its_spans() -> None:
    from torchft_tpu import tracing

    journal = tracing.TraceJournal(maxlen=256)
    with tracing.use_journal(journal):
        manager = scripted_manager(use_async_quorum=False)
        manager._client.should_commit.side_effect = None
        manager._client.should_commit.return_value = False
        algo = DiLoCo(manager, optax.sgd(0.1), optax.sgd(0.7), make_params(), sync_every=1)
        assert not algo.step(fixed_grads(0))
    names = [e["name"] for e in journal.snapshot() if e["ph"] == "X"]
    assert "sync_commit" in names and "perform_sync" in names and "step" in names
    assert "sync_apply_outer" not in names  # refused: no outer step


def test_local_sgd_sync_is_one_perform_sync_span() -> None:
    from torchft_tpu import tracing

    journal = tracing.TraceJournal(maxlen=256)
    with tracing.use_journal(journal):
        manager = scripted_manager()
        algo = LocalSGD(manager, optax.sgd(0.1), make_params(), sync_every=2)
        assert [algo.step(fixed_grads(i)) for i in range(2)] == [False, True]
    spans = [e for e in journal.snapshot() if e["ph"] == "X"]
    (sync,) = [e for e in spans if e["name"] == "perform_sync"]
    assert any(_inside(e, sync) for e in spans if e["name"] == "commit_barrier")


# -- the state is updated in place (donation) and every buffer has one owner --


def _arrays(tree):
    return [x for x in jax.tree_util.tree_leaves(tree) if isinstance(x, jax.Array)]


def _host(tree):
    """Values by COPY: ``np.asarray`` of a CPU jax.Array is a view that pins
    its buffer, and a pinned buffer is not donated."""
    return jax.tree_util.tree_map(
        lambda x: np.array(x, copy=True) if hasattr(x, "shape") else x, tree
    )


def _assert_state_alive(algo, payloads=()) -> None:
    """Nothing the algorithm still needs was deleted by a donated program,
    and no fragment's backup shares a buffer with the live leaves."""
    held = {
        "leaves": algo._leaves,
        "inner_opt_state": algo.inner_opt_state,
        "payloads": list(payloads),
    }
    for frag in algo._fragments:
        held[f"backup{frag._fragment_id}"] = frag.backup
        held[f"outer{frag._fragment_id}"] = frag.outer_opt_state
    for name, tree in held.items():
        for x in _arrays(tree):
            assert not x.is_deleted(), name
    live = {id(x) for x in algo._leaves}
    for frag in algo._fragments:
        assert not live & {id(b) for b in frag.backup}, "a leaf IS a backup array"


@pytest.mark.parametrize("fused", [False, True], ids=["step_grads", "make_step_fn"])
@pytest.mark.parametrize("quantize", [True, False], ids=["quantized", "host"])
def test_diloco_inner_step_is_in_place_and_owns_what_it_donates(quantize, fused) -> None:
    """Every inner step deletes the leaves and inner state it replaces, and
    never anything else: not the caller's arrays, a fragment's backup, an
    outer state or a payload in flight: through construction, a committed
    sync, a failed commit and the steps after it, an allreduce that gave
    nothing, and a heal. The values are those of DiLoCo written out in
    numpy (outer SGD at lr 1: the new global is the local state at launch)."""
    from torchft_tpu.work import _DummyWork

    lr, sync_every, delay = 0.1, 2, 1
    # sync index -> what happens to it
    script = {1: "refused", 3: "nothing_averaged"}

    manager = scripted_manager(use_async_quorum=False)
    syncs = [0]

    def should_commit(rank, step, vote, timeout):
        return vote and script.get(syncs[0]) != "refused"

    manager._client.should_commit.side_effect = should_commit
    payloads = []
    for name in ("allreduce_prequantized", "allreduce_pytree"):
        real = getattr(manager, name)

        def launch(*args, _real=real):
            if script.get(syncs[0]) == "nothing_averaged":
                return _DummyWork(None)
            payloads[:] = _arrays(args)
            return _real(*args)

        setattr(manager, name, launch)

    p0 = make_params()
    algo = DiLoCo(
        manager, optax.sgd(lr), optax.sgd(1.0), p0, sync_every=sync_every,
        fragment_sync_delay=delay, should_quantize=quantize,
    )
    for x in _arrays(p0):
        assert not x.is_deleted()
    _assert_state_alive(algo)

    if fused:
        # The gradient of sum(p * g) in p is g: the same updates as step(grads).
        step_fn = algo.make_step_fn(
            lambda params, g: sum(
                (params[k] * g[k]).sum() for k in sorted(params)
            )
        )
        inner_step = lambda g: step_fn(g)[1]  # noqa: E731
    else:
        inner_step = algo.step

    keys = sorted(p0)  # the flatten order of a dict
    local = {k: np.array(p0[k]) for k in keys}
    backup = {k: v.copy() for k, v in local.items()}
    pseudograd, local_step = None, 0
    # Where the codec rounds (fp8: 3 bits of mantissa of a block's largest
    # pseudogradient, which is at most 0.06 here), and where nothing does.
    codec_tol = dict(rtol=0, atol=4e-3) if quantize else dict(rtol=1e-6, atol=0)

    def check_values(tol) -> None:
        for k, leaf in zip(keys, algo._leaves):
            np.testing.assert_allclose(_host(leaf), local[k], err_msg=k, **tol)
        for k, b in zip(keys, algo._fragments[0].backup):
            np.testing.assert_allclose(_host(b), backup[k], err_msg=k, **tol)

    def heal() -> None:
        """What a joiner's should_commit applies: the state as the wire
        carries it, host arrays."""
        state = _host(manager._manager_state_dict()["user"])
        algo._load_inner(state["diloco_inner"])
        algo._fragments[0]._load_state(state["StreamingDiLoCoFragment_0"])

    tol = dict(rtol=1e-6, atol=0)
    for step in range(12):
        if step == 10:
            heal()
            _assert_state_alive(algo)
            check_values(tol)
        before = _arrays(algo._leaves) + _arrays(algo.inner_opt_state)
        grads = fixed_grads(step)
        committed = inner_step(grads)
        for x in before:
            assert x.is_deleted(), f"step {step}: the old state was kept"
        _assert_state_alive(algo, payloads)
        for x in _arrays(p0):
            assert not x.is_deleted(), "the caller's array was donated"

        for k in keys:
            local[k] = local[k] - np.float32(lr) * np.asarray(grads[k])
        local_step += 1
        if local_step == sync_every - delay:
            pseudograd = {k: backup[k] - local[k] for k in keys}
        if local_step == sync_every:
            outcome = script.get(syncs[0], "committed")
            assert committed is (outcome == "committed"), (step, outcome)
            if outcome == "committed":
                backup = {k: backup[k] - pseudograd[k] for k in keys}
                tol = codec_tol
            local = {k: v.copy() for k, v in backup.items()}
            if outcome != "committed" and quantize:
                # The reset is a copy: bitwise the backup, in other buffers.
                for leaf, b in zip(algo._leaves, algo._fragments[0].backup):
                    assert _host(leaf).tobytes() == _host(b).tobytes()
            syncs[0] += 1
            local_step = 0
        else:
            assert not committed
        check_values(tol)
    assert syncs[0] == 6


@pytest.mark.parametrize("quantize", [True, False], ids=["quantized", "host"])
def test_state_capture_survives_the_steps_after_it(quantize) -> None:
    """A capture of the registered state is a device copy, not a reference:
    taken between two inner steps it is still readable, and bitwise the
    state at capture, after the delay window's steps have deleted the
    arrays it was taken from. Each capture is counted; a run without one
    counts nothing."""
    from torchft_tpu import metrics

    def copies(**label) -> float:
        return metrics.counter_total("tpuft_state_snapshot_copies_total", **label)

    def copied_bytes(**label) -> float:
        return metrics.counter_total("tpuft_state_snapshot_copy_bytes_total", **label)

    delay = 1
    manager = scripted_manager(use_async_quorum=False)
    algo = DiLoCo(
        manager, optax.adam(0.1), optax.sgd(0.7, momentum=0.9, nesterov=True),
        make_params(), sync_every=4, n_fragments=2, fragment_sync_delay=delay,
        should_quantize=quantize,
    )
    before = copies(), copied_bytes()
    before_inner = copies(key="diloco_inner"), copied_bytes(key="diloco_inner")
    for step in range(9):  # four committed fragment syncs, no capture
        algo.step(fixed_grads(step))
    assert manager.current_step() == 4
    assert (copies(), copied_bytes()) == before

    captures = []
    for step in range(9, 9 + 2 * (delay + 2)):
        if step in (9, 9 + delay + 2):
            at_capture = _host(diloco_live_state(algo))
            captures.append((manager._manager_state_dict()["user"], at_capture))
        algo.step(fixed_grads(step))
    assert manager.current_step() >= 6  # fragments synced after each capture
    for capture, at_capture in captures:
        assert set(capture) >= set(at_capture)  # and the scripted manager's own
        got = jax.tree_util.tree_leaves({k: capture[k] for k in at_capture})
        want = jax.tree_util.tree_leaves(at_capture)
        assert len(got) == len(want) > 0
        for x, y in zip(got, want):
            if isinstance(x, jax.Array):
                assert not x.is_deleted()
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes()
    inner_bytes = sum(x.nbytes for x in _arrays(captures[0][0]["diloco_inner"]))
    assert copies(key="diloco_inner") - before_inner[0] == len(captures)
    assert copied_bytes(key="diloco_inner") - before_inner[1] == 2 * inner_bytes
    # The device pipeline's fragments copy their backups and outer state
    # too; the host pipeline's are numpy copies already.
    per_capture = 1 + (len(algo._fragments) if quantize else 0)
    assert copies() - before[0] == per_capture * len(captures)


# -- the quantized sync's two programs against the flat formulation ----------


def _flat_sync_programs(leaves, outer_tx, alpha):
    """The formulation the leaf-layout codec replaced, kept here as the plain
    reference: the pseudogradient concatenated into one flat float32 array,
    quantized in blocks, and the decoded flat array sliced back a leaf."""
    from torchft_tpu.ops import quantization as q

    sizes = [int(np.prod(leaf.shape)) for leaf in leaves]
    shapes = [tuple(leaf.shape) for leaf in leaves]
    dtypes = [leaf.dtype for leaf in leaves]
    offsets = np.cumsum([0] + sizes)

    def quantize_pseudograd(backup_leaves, local_leaves):
        flat = jnp.concatenate([
            (b.astype(jnp.float32) - l.astype(jnp.float32)).reshape(-1)
            for b, l in zip(backup_leaves, local_leaves)
        ])
        return q.quantize_blocks_device(flat)

    def apply_outer(payload, scales, backup_leaves, local_leaves, outer_state):
        flat = q.dequantize_blocks_device(payload, scales)[: sum(sizes)]
        avg_pg = [
            flat[offsets[i] : offsets[i + 1]].reshape(shapes[i]).astype(dtypes[i])
            for i in range(len(sizes))
        ]
        updates, new_state = outer_tx.update(avg_pg, outer_state, backup_leaves)
        new_backup = optax.apply_updates(backup_leaves, updates)
        merged = [
            (g.astype(jnp.float32) * (1.0 - alpha)
             + l.astype(jnp.float32) * alpha).astype(g.dtype)
            for g, l in zip(new_backup, local_leaves)
        ]
        return new_backup, merged, new_state

    return jax.jit(quantize_pseudograd), jax.jit(apply_outer)


def _bits(tree):
    return [np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("impl", ["jnp", "pallas-interpret"])
@pytest.mark.parametrize("alpha", [0.0, 0.5])
@pytest.mark.parametrize("ragged", [False, True], ids=["whole-blocks", "flat-tail"])
def test_quantized_sync_is_bit_for_bit_the_flat_formulation(
    ragged, alpha, impl, request
) -> None:
    """Two rounds of a quantized fragment sync (the second with momentum in
    the outer state): the wire's payload and scales, the new backup, the
    merged leaves and the outer state are bit for bit what the flat
    formulation's two programs give on the same inputs (the wire carries
    the same blocks), and the counter grows by the static element counts of
    the two paths."""
    from torchft_tpu import metrics

    if impl == "pallas-interpret":
        request.getfixturevalue("interpreted_kernels")
    rng = np.random.default_rng(7)
    # Tree order is the keys': the leaves of no 32-row tile come last, as the
    # codec's flat tail does, so both formulations cut the same blocks.
    params = {
        "a_embed": jnp.asarray(rng.normal(0, 1, (64, 512)), jnp.bfloat16),
        "b_stack": jnp.asarray(rng.normal(0, 1, (2, 32, 256)), jnp.bfloat16),
        "c_heads": jnp.asarray(rng.normal(0, 1, (2, 32, 2, 128)), jnp.bfloat16),
        "x_norm": jnp.ones((2, 256), jnp.float32),
    }
    if ragged:
        params["y_bias"] = jnp.asarray(rng.normal(0, 1, (7, 100)), jnp.bfloat16)
        params["z_scale"] = jnp.asarray(rng.normal(0, 1, (5,)), jnp.float32)
    outer_tx = optax.sgd(0.7, momentum=0.9, nesterov=True)
    manager = scripted_manager(use_async_quorum=False)
    algo = DiLoCo(
        manager, optax.sgd(0.1), outer_tx, params, sync_every=2,
        should_quantize=True, fragment_update_alpha=alpha,
    )
    (fragment,) = algo._fragments
    calls = []
    apply_outer = fragment._jit_apply_outer

    def spy(*args):
        host = jax.tree_util.tree_map(np.asarray, args)  # before the donation
        out = apply_outer(*args)
        calls.append((host, jax.tree_util.tree_map(np.asarray, out)))
        return out

    fragment._jit_apply_outer = spy
    counted = lambda path: metrics.counter_total(  # noqa: E731
        "tpuft_codec_elements_total", path=path
    )
    before = counted("leaf"), counted("flat")
    for step in range(4):
        grads = jax.tree_util.tree_map(
            lambda p: jnp.asarray(rng.normal(0, 0.5, p.shape), p.dtype), params
        )
        committed = algo.step(grads)
        assert committed == (step % 2 == 1)
    assert len(calls) == 2

    flat_quantize, flat_apply = _flat_sync_programs(
        jax.tree_util.tree_leaves(params), outer_tx, alpha
    )
    for (payload, scales, backup, local, state), out in calls:
        # The wire holds the flat formulation's blocks, each with its scale,
        # in another order (tests/test_quantization.py pins which).
        want_payload, want_scales = flat_quantize(backup, local)
        blocks = lambda p, s: sorted(  # noqa: E731
            row.tobytes() + scale.tobytes() for row, scale in zip(np.asarray(p), np.asarray(s))
        )
        assert blocks(payload, scales) == blocks(want_payload, want_scales)
        want = flat_apply(want_payload, want_scales, backup, local, state)
        assert _bits(out) == _bits(want)
    # perform_sync left the programs' outputs in place.
    new_backup, merged, new_state = calls[-1][1]
    assert _bits(fragment.backup) == _bits(new_backup)
    assert _bits(algo.params) == _bits(merged)
    assert _bits(fragment.outer_opt_state) == _bits(new_state)
    leaf = sum(p.size for k, p in params.items() if k < "x")
    flat = sum(p.size for k, p in params.items() if k >= "x")
    assert (counted("leaf") - before[0], counted("flat") - before[1]) == (
        2 * leaf, 2 * flat
    )


def test_a_ragged_fragment_counts_all_flat() -> None:
    """No leaf of ``make_params`` holds a whole block: the codec takes the
    flat path for all of it, and the counter says so."""
    from torchft_tpu import metrics

    counted = lambda path: metrics.counter_total(  # noqa: E731
        "tpuft_codec_elements_total", path=path
    )
    before = counted("leaf"), counted("flat")
    manager = scripted_manager(use_async_quorum=False)
    algo = DiLoCo(
        manager, optax.sgd(0.1), optax.sgd(0.7), make_params(), sync_every=1,
        should_quantize=True,
    )
    assert algo.step(fixed_grads(0))
    assert counted("leaf") == before[0]
    assert counted("flat") - before[1] == 5
