"""Fault-tolerant optimizer wrapper (optax).

The canonical step protocol of the reference's ``OptimizerWrapper``
(/root/reference/torchft/optim.py:24-63) — ``zero_grad()`` starts the quorum,
``step()`` commits — adapted for JAX/optax:

    opt = Optimizer(manager, optax.adamw(3e-4), params)
    for batch in data:
        opt.begin_step()                        # zero_grad() analogue
        grads = grad_fn(opt.params, batch)
        avg = manager.allreduce_pytree(grads).wait()
        committed = opt.step(avg)
        # opt.params / opt.opt_state hold the live state

The wrapper *owns* ``params``/``opt_state`` and registers them with the
manager under the key ``"optimizer"`` — this is load-bearing for healing:
``should_commit()`` may replace the state with a donor's checkpoint
mid-call, and the gradient update must apply to the *healed* state, exactly
as torch's in-place ``load_state_dict`` + ``optimizer.step()`` sequence
does. A functional step that captured params before the commit barrier
would silently clobber the heal (the bug class this design avoids).

For custom state management, call ``manager.should_commit()`` directly and
re-read any registered state *after* it returns.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any, Optional

import jax
import numpy as np

from torchft_tpu import health, metrics, tracing
from torchft_tpu.history import WeightHistory
from torchft_tpu.manager import Manager
from torchft_tpu.utils import schedules

logger = logging.getLogger(__name__)

__all__ = [
    "Optimizer",
    "OptimizerWrapper",
    "make_jit_update",
    "make_jit_shard_update",
    "make_jit_fused_step",
    "make_microbatch_grad",
]


def _bound_device(x: Any) -> Any:
    """Readiness seam for every commit-ordering device sync.

    One named chokepoint instead of inline ``jax.block_until_ready`` calls
    so (a) the ordering tests can spy the sync relative to the vote for all
    three commit orderings, and (b) the emulated-DCN bench can shim it with
    ``netem.emulated_device_sync`` to model a high-latency device's
    readiness round trip (the cost the pipelined mode exists to hide)."""
    return jax.block_until_ready(x)


def _replica_labels(manager: Any) -> dict:
    """The manager's stable replica labels for optimizer-side counters
    (rollbacks, phantom commits), so drills can count them per replica
    group; {} for scripted/mocked managers without the attribute."""
    return getattr(manager, "_metric_labels", None) or {}


def _trace_of(manager: Any) -> "tracing.TraceJournal":
    """The manager's trace journal (so optimizer events land in the same
    per-replica timeline its manager records into), falling back to the
    thread's current journal for scripted/mocked managers."""
    return getattr(manager, "_trace", None) or tracing.current()


def _count_dispatch(
    buffers_in: int, buffers_out: int, donated: Optional[bool] = None
) -> None:
    """One call of a jitted step program and the buffers (array leaves) it
    hands to the runtime and takes back. The host cost of a dispatch grows
    with them, so beside the ``update_dispatch`` span's seconds they give
    a cost per buffer. The counts are the step's static ones, taken where
    the state's structure is set (:meth:`Optimizer._note_state_structure`);
    no tree is walked here. What is handed over is the same whether the
    state is donated or kept; what the runtime then allocates is not.
    ``donated`` says which it was for the program that writes the step's
    new state (None: a program that leaves the state alone, as ZeRO's
    gradient program), and counts the step under it."""
    metrics.inc("tpuft_step_dispatch_total")
    metrics.inc("tpuft_step_dispatch_buffers_total", buffers_in, direction="in")
    metrics.inc("tpuft_step_dispatch_buffers_total", buffers_out, direction="out")
    if donated:
        metrics.inc("tpuft_step_state_donated_total")
    elif donated is not None:
        metrics.inc("tpuft_step_state_kept_total")


def _sync_device(x: Any) -> Any:
    """Every step's device sync, timed into ``tpuft_device_sync_seconds``.

    Calls through the module global so spies and the netem shim that rebind
    ``_bound_device`` still intercept the sync — and their emulated/observed
    latency lands in the phase histogram like the real one."""
    with tracing.phase("device_sync"):
        # Gray-failure chaos seam: a punisher-armed slow_replica/
        # wedge_device installs a persistent per-replica stall/wedge
        # here (one env lookup when unarmed) — the injected latency
        # lands in the phase histogram and the health scorer's EWMA
        # exactly like a real gray device.
        health.injected_stall("device_sync")
        return _bound_device(x)


def make_microbatch_grad(loss_fn: Any, num_microbatches: int):
    """Gradient accumulation the TPU way: ``(params, *batch) -> (loss,
    grads)`` that splits each batch array's leading axis into
    ``num_microbatches`` equal chunks and ``lax.scan``s value_and_grad over
    them inside ONE traced program — activations for only one microbatch
    are live at a time (the standard HBM lever when the global batch
    doesn't fit), with f32 accumulators so bf16 models don't lose gradient
    mass across chunks. Equal-sized chunks make mean-of-means exactly the
    full-batch mean for per-example/token-mean losses (up to f32 reduction
    order). Every ``*batch`` arg must carry the batch axis at dim 0; pass
    non-batched aux (rng keys, constants) via closure.

    The reference leans on torch's eager semantics for this —
    ``loss.backward()`` accumulates into ``.grad`` buffers between
    ``zero_grad()`` and ``step()`` (the train-loop protocol at
    /root/reference/train_ddp.py:185-196), so users accumulate by simply
    calling backward N times. Under XLA the scan is the idiomatic
    equivalent — no data-dependent Python control flow, one compiled loop
    body reused across chunks."""
    import jax.numpy as jnp

    if num_microbatches < 1:
        raise ValueError(f"num_microbatches must be >= 1, got {num_microbatches}")

    def grad_fn(params: Any, *batch: Any):
        def split(x):
            # Every *batch leaf must carry the batch axis at dim 0 —
            # pass non-batched aux (rng keys, scalars) via closure, not
            # as a batch arg.
            if getattr(x, "ndim", 0) == 0:
                raise ValueError(
                    "make_microbatch_grad: got a rank-0 batch arg; every "
                    "batch array must have the batch axis at dim 0 (close "
                    "over non-batched aux instead)"
                )
            if x.shape[0] % num_microbatches:
                raise ValueError(
                    f"batch dim {x.shape[0]} not divisible by "
                    f"num_microbatches={num_microbatches}"
                )
            return x.reshape(
                (num_microbatches, x.shape[0] // num_microbatches) + x.shape[1:]
            )

        micro = jax.tree_util.tree_map(split, batch)
        vg = jax.value_and_grad(loss_fn)

        def body(carry, mb):
            loss_acc, g_acc = carry
            loss, g = vg(params, *mb)
            g_acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), g_acc, g
            )
            return (loss_acc + loss.astype(jnp.float32), g_acc), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (loss_sum, g_sum), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), micro
        )
        inv = 1.0 / num_microbatches
        grads = jax.tree_util.tree_map(
            lambda g, p: (g * inv).astype(p.dtype), g_sum, params
        )
        return loss_sum * inv, grads

    return grad_fn


def make_jit_fused_step(
    tx: Any, loss_fn: Any, num_microbatches: int = 1, donate_state: bool = False
):
    """ONE jitted program for a whole local train step:
    ``(params, opt_state, *batch) -> (loss, new_params, new_opt_state)``.
    ``loss_fn(params, *batch) -> scalar``. The fused form is the plain-JAX
    train step; Optimizer (lone-replica path) and LocalSGD (inner steps)
    share it — DiLoCo keeps its own leaves-layout variant
    (local_sgd.py make_step_fn). ``num_microbatches > 1`` accumulates
    gradients over equal batch chunks inside the same program
    (:func:`make_microbatch_grad`). ``donate_state`` gives ``params`` and
    ``opt_state`` to the program, as :func:`make_jit_update`'s does: the
    new state is written where the old one was and both inputs are
    deleted, so only for a caller that owns both and has its verdict
    (the lone replica's vote-first step)."""
    import optax

    if num_microbatches < 1:
        raise ValueError(f"num_microbatches must be >= 1, got {num_microbatches}")
    if num_microbatches > 1:
        grad_fn = make_microbatch_grad(loss_fn, num_microbatches)
    else:
        grad_fn = jax.value_and_grad(loss_fn)

    def _fused(params: Any, opt_state: Any, *batch: Any):
        loss, grads = grad_fn(params, *batch)
        updates, new_state = tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), new_state

    return jax.jit(_fused, donate_argnums=(0, 1) if donate_state else ())


def make_jit_update(tx: Any, donate_state: bool = False):
    """One fused-dispatch optax update: (grads, opt_state, params) ->
    (new_params, new_opt_state). Shared by Optimizer/LocalSGD/DiLoCo —
    unjitted optax updates issue hundreds of tiny device ops, which dominates
    on high-latency device links. ``donate_state`` gives ``opt_state`` and
    ``params`` to the program (the update is in place and both inputs are
    deleted): only for a caller that owns both and never needs the old
    state back, as DiLoCo's inner step."""
    import optax

    def _update(grads: Any, opt_state: Any, params: Any):
        updates, new_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_state

    return jax.jit(_update, donate_argnums=(1, 2) if donate_state else ())


def make_jit_shard_update(tx: Any):
    """One fused-dispatch optax update over a LIST of optimizer shards:
    ``(avg_shards, shard_states, master_shards) -> (new_masters,
    new_states)`` where each position is one ZeRO shard's flat f32 range
    (torchft_tpu.zero). Each shard keeps its OWN optax state (``tx.init``
    per shard — shard states must stay independently addressable for the
    re-balance exchange and the shard-wise heal), but all owned shards
    update inside ONE jitted program, so the per-step dispatch count stays
    constant regardless of how many shards a replica owns (the
    unjitted-optax invariant: eager per-shard updates would issue hundreds
    of tiny device ops on high-latency links)."""
    import optax

    def _update(avg_shards: Any, shard_states: Any, master_shards: Any):
        new_masters, new_states = [], []
        for grad, state, master in zip(avg_shards, shard_states, master_shards):
            updates, next_state = tx.update(grad, state, master)
            new_masters.append(optax.apply_updates(master, updates))
            new_states.append(next_state)
        return new_masters, new_states

    return jax.jit(_update)


def _align_opt_state(opt_state: Any, params: Any) -> Any:
    """Places optimizer-state leaves on the params' device set.

    Param-shaped leaves (moments) already inherit the params' sharding via
    zeros_like; scalar bookkeeping (e.g. optax's ``count``) lands on one
    local device, which breaks the jitted update under a multi-host mesh —
    replicate those over the params' mesh instead."""
    from jax.sharding import NamedSharding, PartitionSpec

    param_leaves = [
        leaf for leaf in jax.tree_util.tree_leaves(params) if isinstance(leaf, jax.Array)
    ]
    if not param_leaves:
        return opt_state
    sharding = param_leaves[0].sharding
    mesh = getattr(sharding, "mesh", None)
    if mesh is None:
        return opt_state
    target_ids = {d.id for d in param_leaves[0].sharding.device_set}
    if len(target_ids) <= 1:
        return opt_state
    replicated = NamedSharding(mesh, PartitionSpec())

    def fix(leaf: Any) -> Any:
        if isinstance(leaf, jax.Array):
            if {d.id for d in leaf.sharding.device_set} != target_ids:
                return jax.device_put(np.asarray(leaf), replicated)
        return leaf

    return jax.tree_util.tree_map(fix, opt_state)


def _restore_leaf(new: Any, current: Any) -> Any:
    """Restores a healed leaf onto the device layout of ``current``.

    Plain hosts arrays follow the current sharding; a
    :class:`~torchft_tpu.checkpointing._serialization.ShardedLeaf` (multi-
    host donor capture) is reassembled shard-by-shard against the current
    array's sharding — donor and joiner lay out identically by the HSDP
    contract (same model, same intra-group mesh)."""
    import jax.numpy as jnp

    from torchft_tpu.checkpointing._serialization import ShardedLeaf, _resolve_dtype

    if isinstance(new, ShardedLeaf):
        if not isinstance(current, jax.Array):
            raise TypeError(
                "received a sharded checkpoint leaf but the local state is "
                "not a jax.Array to supply its sharding"
            )
        by_index = dict(new.shards)
        buffers = []
        for shard in current.addressable_shards:
            key = ShardedLeaf.index_key(shard.index, new.global_shape)
            if key not in by_index:
                raise ValueError(
                    f"donor checkpoint lacks shard {key}: donor/joiner "
                    "shardings must match"
                )
            buffers.append(
                jax.device_put(
                    np.asarray(by_index[key], dtype=_resolve_dtype(new.dtype)),
                    shard.device,
                )
            )
        return jax.make_array_from_single_device_arrays(
            new.global_shape, current.sharding, buffers
        )
    if isinstance(current, jax.Array) and hasattr(new, "shape"):
        return jax.device_put(np.asarray(new), current.sharding)
    if hasattr(new, "shape"):
        return jnp.asarray(new)
    return new


def _as_device_tree(tree: Any, like: Any = None) -> Any:
    import jax.numpy as jnp

    if like is not None:
        return jax.tree_util.tree_map(
            _restore_leaf, tree, like,
            is_leaf=lambda x: not isinstance(x, (dict, list, tuple)),
        )
    return jax.tree_util.tree_map(
        lambda x: jnp.asarray(x) if hasattr(x, "shape") else x, tree
    )


# Rollback-unwind depth is a small count (1..window depth), not seconds:
# its histogram gets count-shaped edges instead of the shared time ladder.
_UNWIND_DEPTH_BUCKETS = (1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0)


class _PendingStep:
    """One slot of the speculative commit window: the slot's speculative
    ``(params, opt_state)`` is already adopted as the live state (so
    younger steps could dispatch on it), and this record carries
    everything needed to confirm, roll back, or re-derive it once its
    commit verdict lands — plus the window bookkeeping the depth-N
    generalization needs: ``claimed_step`` (the step this slot
    speculates), ``gen`` (the speculation generation at dispatch; a
    rollback bumps the owner's generation, turning every younger
    undrained slot into a discard), and ``snapshot_bytes`` (this slot's
    share of the snapshot ring, for the resident-bytes gauge).

    Both phases are idempotent and lock-guarded because two threads may
    reach them: the train loop (the normal resolution path) and the
    manager's quorum thread (the drain-before-reconfigure hook)."""

    __slots__ = (
        "manager",
        "heal_count",
        "loss",
        "snapshot",
        "recompute",
        "commit_future",
        "committed",
        "gen",
        "claimed_step",
        "discarded",
        "snapshot_bytes",
        "_bound",
        "_bound_error",
        "_lock",
    )

    def __init__(
        self, manager: Manager, heal_count: int, loss: Any, snapshot: Any,
        recompute: Any, commit_future: Any, gen: int = 0,
        claimed_step: int = -1, snapshot_bytes: int = 0,
    ) -> None:
        self.manager = manager
        self.heal_count = heal_count
        self.loss = loss
        self.snapshot = snapshot
        self.recompute = recompute
        self.commit_future = commit_future
        self.committed: Optional[bool] = None  # set by the vote resolution
        self.gen = gen
        self.claimed_step = claimed_step
        self.discarded = False
        self.snapshot_bytes = snapshot_bytes
        self._bound = False
        self._bound_error: Optional[BaseException] = None
        self._lock = threading.Lock()

    def bound_device(self, raise_on_error: bool = True) -> None:
        """Observes this step's device completion (once). A failure here is
        the widened envelope's bounded-accounting case: the step may
        already have committed (vote resolved before completion), so the
        error is logged with that context and funneled into
        :meth:`Manager.report_error` — poisoning the NEXT commit, whose
        resolution rolls the speculative successor back. ``raise_on_error``
        is False on the quorum-thread drain (report, don't unwind the
        quorum) and True on the train-loop path (the supervisor-restart
        boundary owns hard device failures, as in the non-pipelined
        orderings)."""
        with self._lock:
            if not self._bound:
                self._bound = True
                try:
                    _sync_device(self.loss)
                except BaseException as e:  # noqa: BLE001
                    self._bound_error = e
                    if self.committed:
                        metrics.inc(
                            "tpuft_phantom_commits_total",
                            **_replica_labels(self.manager),
                        )
                        _trace_of(self.manager).record(
                            "phantom_commit", error=str(e)
                        )
                    logger.error(
                        "pipelined step's device work failed after its commit "
                        "vote resolved committed=%s (a committed step here "
                        "advanced the step counter without a verified update "
                        "— the bounded phantom-commit envelope, at most "
                        "window-depth steps)",
                        self.committed,
                    )
                    if isinstance(e, Exception):
                        self.manager.report_error(e)
        if self._bound_error is not None and raise_on_error:
            raise self._bound_error


class Optimizer:
    """Owns (params, opt_state); steps only on quorum-wide commit.

    **Who owns the state, and when it is updated in place.** The arrays
    given as ``params`` are the Optimizer's from construction, as a plain
    donated train step takes them: no copy is made (a copy would put the
    peak of HBM at state + params), so the caller keeps no use of them and
    takes a host copy (``np.array``; on the CPU ``np.asarray`` is a view of
    the buffer, which then is quietly not given away) first of whatever it
    wants to compare with later. What follows:

    - a lone replica's step at commit-pipeline depth 0
      (:meth:`make_step_fn`, :meth:`_lone_step`) takes its verdict FIRST
      and then runs one fused program that is given ``params`` and
      ``opt_state`` (``jax.jit`` donation): the new state is written where
      the old one was, the device holds ONE copy of the state, and a
      dispatch allocates one output (the loss) instead of one per leaf.
      A refused step dispatches nothing to the state, which stays the very
      objects it was. This is the reference's order (``if
      manager.should_commit(): optimizer.step()``, torchft updates in
      place after the vote), and it engages on what the step can observe:
      lone replica, no error, ``TPUFT_STRICT_COMMIT`` unset, no array that
      appears twice in the state, and a history ring that keeps one
      version (``manager.history.max_versions == 1``: a ring asked to keep
      older versions by reference cannot have them deleted). Otherwise,
      and on the wire path (:meth:`step`), in the pipelined window and in
      ``ZeroOptimizer``, the step keeps the speculative order and both
      copies;
    - an array read from :attr:`params` (or ``opt_state``) is valid until
      the next step, which may DELETE it: read them afresh after every
      step, ``jnp.copy`` what must outlive one;
    - the state is mutated only between a True verdict and the next
      ``start_quorum``, under the state-dict write lock, and the history
      ring's version is replaced inside the same lock: the old arrays are
      never reachable from ``self`` or the ring outside it;
    - who else holds the state: the heal donor's ``send_checkpoint`` reads
      it BY REFERENCE (no copy: a donor's HBM does not grow during a heal),
      on the quorum thread while the train thread waits for the quorum,
      and stages host copies before ``wait_quorum`` returns;
      ``Manager._maybe_publish`` / ``WeightPublisher.publish`` and the
      joiner's delta-rejoin local state likewise read by reference and are
      done (host copies staged) before the step that follows dispatches; a
      capture that outlives a step (``WeightHistory.state_dict_at`` on a
      ring of one version, a checkpoint saved asynchronously) is a device
      copy, counted by ``tpuft_state_snapshot_copies_total{key=
      "optimizer"}`` / ``tpuft_state_snapshot_copy_bytes_total``. A steady
      window without heals reads 0 copies.
    - ``tpuft_step_state_donated_total`` / ``tpuft_step_state_kept_total``
      count the steps by which program wrote their state, and the
      ``update_dispatch`` event carries ``donated``.
    """

    def __init__(
        self,
        manager: Manager,
        tx: Any,
        params: Any,
        register_key: str = "optimizer",
    ) -> None:
        self.manager = manager
        self.tx = tx
        self.params = params
        self._heal_count = 0
        self._register_key = register_key
        self.opt_state = self._init_state(tx, params)
        # What the step reads of the state's structure without walking it:
        # (params leaves, opt_state leaves), the era they were counted in,
        # (era, bytes) of one (params, opt_state) pair once a snapshot was
        # measured, and (arguments, leaves) of a step function's batch.
        self._state_era = 0
        self._state_leaves = (0, 0)
        self._state_aliased = False
        self._state_nbytes: Optional[tuple] = None
        self._batch_leaves: Optional[tuple] = None
        self._note_state_structure()
        manager.register_state_dict_fn(
            register_key, self._load_state_dict, self._state_dict
        )

        self._jit_update = make_jit_update(tx)

        # Pipelined-commit state (populated by make_step_fn when the
        # manager's commit_pipeline_depth >= 1; the depth-N window keeps
        # up to N of these records in flight at once).
        self._pipeline: Optional[Any] = None
        self._pipeline_hooked = False
        self._next_pipelined_step = 0
        self.rollback_count = 0
        # Speculation generation: bumped by a rollback so every younger
        # undrained window slot resolves as a discard (the step never
        # happened, quorum-wide) instead of adopting state computed on a
        # refused speculation.
        self._speculation_gen = 0
        self._snapshot_ring_bytes = 0

    def _init_state(self, tx: Any, params: Any) -> Any:
        """Builds the initial optimizer state this wrapper owns. The ZeRO
        subclass (torchft_tpu.zero.ZeroOptimizer) overrides this to hold
        only its 1/N shard of the state; everything downstream (snapshots,
        rollback, heal re-binding) treats ``opt_state`` as opaque."""
        return _align_opt_state(tx.init(params), params)

    def _note_state_structure(self) -> None:
        """Called wherever the owned state's structure is set (construction,
        a heal's ``_load_state_dict``, a ZeRO re-balance): counts the leaves
        a dispatch hands over and opens a new era, so the next
        :meth:`_snapshot_nbytes` measures the state again. Between two
        calls every step reuses both."""
        leaves = jax.tree_util.tree_leaves
        params, opt_state = leaves(self.params), leaves(self.opt_state)
        self._state_leaves = (len(params), len(opt_state))
        # An array that appears twice (tied leaves) cannot be given away
        # twice: such a state is never donated.
        arrays = [id(x) for x in params + opt_state if isinstance(x, jax.Array)]
        self._state_aliased = len(set(arrays)) != len(arrays)
        self._state_era += 1

    def _batch_buffers(self, batch: Any) -> int:
        """Leaves of a step function's batch: counted at its first call, and
        again only when a call passes another number of arguments."""
        known = self._batch_leaves
        if known is None or known[0] != len(batch):
            known = self._batch_leaves = (
                len(batch), len(jax.tree_util.tree_leaves(batch)),
            )
        return known[1]

    def _state_dict(self) -> Any:
        return {"params": self.params, "opt_state": self.opt_state}

    # tpuft: allow(lock-discipline): heal apply — the registered load fns run under the state-dict writer taken by Manager._apply_pending_state_dict
    def _load_state_dict(self, state: Any) -> None:
        # Restore against the CURRENT layouts so multi-host shardings are
        # reassembled locally (each rank received its own shards).
        self.params = _as_device_tree(state["params"], like=self.params)
        self.opt_state = _as_device_tree(state["opt_state"], like=self.opt_state)
        self._note_state_structure()
        # Any speculative update dispatched before this heal is stale.
        self._heal_count += 1

    def begin_step(
        self, timeout: Optional[float] = None, shrink_only: bool = False
    ) -> None:
        """Starts the (async) quorum for this step; call before the forward
        pass so quorum latency overlaps compute."""
        self.manager.start_quorum(shrink_only=shrink_only, timeout=timeout)

    # torch-API alias: the reference starts quorum in zero_grad().
    zero_grad = begin_step

    def step(self, grads: Any, timeout: Optional[float] = None) -> bool:
        """Commits the step; on success applies ``grads`` to the (possibly
        just-healed) owned state. Returns whether the step committed.

        The update is dispatched **speculatively** and the commit-barrier
        RPC rides the manager's executor, so BOTH the RPC wire time and the
        device-side optimizer math overlap (the analogue of the reference
        overlapping should_commit's stream syncs, manager.py:569-581 +
        :816-827). If the barrier heals this replica (state replaced
        mid-call), the speculation is discarded and the update re-applies
        against the healed state."""
        # Bound the device work before voting: a replica whose math never
        # finished must not vote to commit (the stream-sync analogue of
        # reference manager.py:816-827).
        grads = _sync_device(grads)
        heal_count = self._heal_count
        # Snapshot the state refs, THEN launch the barrier: the RPC is in
        # flight while the update dispatches below. A concurrent heal can
        # rebind self.params mid-dispatch — harmless, because the
        # heal_count check discards the speculation in that case.
        params, opt_state = self.params, self.opt_state
        commit_future = self.manager.should_commit_async(timeout)
        try:
            with tracing.phase("update_dispatch", _trace_of(self.manager)):
                spec = self._jit_update(grads, opt_state, params)
        except BaseException:
            # The barrier is already in flight and may commit the step
            # (the vote was computed from pre-dispatch health); never leave
            # it dangling on the executor — resolve it, then surface the
            # dispatch failure (the supervisor restart + heal path owns
            # recovery from a step counter that advanced without its
            # update).
            try:
                barrier_result = commit_future.result()
            except Exception:
                # Both causes matter to a supervisor diagnosing "step
                # advanced without its update": keep the barrier's failure
                # (e.g. should_commit's max_retries RuntimeError) visible
                # alongside the dispatch failure we re-raise below.
                logger.exception(
                    "commit barrier also failed while handling an optimizer "
                    "dispatch failure; barrier outcome lost to the re-raise"
                )
            else:
                if barrier_result:
                    metrics.inc(
                        "tpuft_phantom_commits_total",
                        **_replica_labels(self.manager),
                    )
                    _trace_of(self.manager).record("phantom_commit")
                logger.error(
                    "optimizer dispatch failed with the commit barrier in "
                    "flight; barrier resolved committed=%s (a committed step "
                    "here advanced the step counter without its update)",
                    barrier_result,
                )
            raise
        n_params, n_opt = self._state_leaves
        _count_dispatch(2 * n_params + n_opt, n_params + n_opt, donated=False)
        return self._commit_and_adopt(
            heal_count,
            spec,
            lambda: self._jit_update(grads, self.opt_state, self.params),
            timeout,
            commit_future=commit_future,
        )

    def _commit_and_adopt(
        self,
        heal_count: int,
        speculation: Any,
        recompute: Any,
        timeout: Optional[float],
        commit_future: Any = None,
    ) -> bool:
        """The shared barrier protocol: vote/commit, then adopt the
        speculatively computed ``(params, opt_state)`` — unless the barrier
        healed this replica (state replaced mid-call), in which case
        ``recompute()`` re-derives the update against the healed state.

        NOTE: should_commit may invoke _load_state_dict (healing); read
        self.params/opt_state only after it returns. The mutation is
        write-locked so a concurrent checkpoint capture (donor staging on
        the quorum thread) never reads a torn params/opt pair."""
        trace = _trace_of(self.manager)
        with tracing.phase("commit_wait", trace):
            committed = (
                commit_future.result()
                if commit_future is not None
                else self.manager.should_commit(timeout=timeout)
            )
        if not committed:
            return False
        with tracing.phase("adopt", trace):
            with tracing.phase("state_swap", trace):
                self.manager.disallow_state_dict_read()
                try:
                    if self._heal_count != heal_count:
                        self.params, self.opt_state = recompute()
                    else:
                        self.params, self.opt_state = speculation
                finally:
                    self.manager.allow_state_dict_read()
            # Promote the just-committed state into the manager's history
            # ring (refs only — immutable trees make holding a reference a
            # true snapshot). The barrier already advanced the step counter.
            self._promote_committed(
                self._int_or_none(self.manager.current_step()),
                self.params,
                self.opt_state,
            )
        return True

    # ------------------------------------------------------------------
    # versioned weight history (torchft_tpu/history.py)
    # ------------------------------------------------------------------

    @staticmethod
    def _int_or_none(value: Any) -> Optional[int]:
        return value if isinstance(value, int) else None

    def _promote_committed(
        self, step: Optional[int], params: Any, opt_state: Any,
        span_step: Optional[int] = None,
    ) -> None:
        """Hands one committed step's ``(params, opt_state)`` refs to the
        manager's history ring — the slot promotion that replaces simply
        dropping resolved window snapshots. Best-effort: history is an
        availability plane (exact deep-window heals, pinned serving);
        its bookkeeping must never wound a commit. ``span_step`` is the
        step its ``history_promote`` span belongs to (the enclosing
        ``adopt``'s; None: the step's root's)."""
        if step is None:
            return
        with tracing.phase(
            "history_promote", _trace_of(self.manager), step=span_step
        ):
            hist = getattr(self.manager, "history", None)
            try:
                if not isinstance(hist, WeightHistory):
                    return  # scripted/mocked managers without a real ring
                state = {"params": params, "opt_state": opt_state}
                hist.note_state(
                    self._register_key,
                    step,
                    state,
                    nbytes=self._snapshot_nbytes((params, opt_state)),
                    quorum_id=getattr(self.manager, "_quorum_id", None),
                )
            except Exception:  # noqa: BLE001 — bookkeeping must not wound a step
                logger.exception("history promotion failed (ignored)")

    def _post_commit_state(self, rec: "_PendingStep") -> Any:
        """The committed state AFTER ``rec``'s step: the next younger
        same-generation window slot's pre-step snapshot (speculations
        chain — slot k+1's snapshot IS post-k state), or the live state
        when ``rec`` is the window's newest resolved slot."""
        if self._pipeline is not None:
            seen = False
            for r in self._pipeline.pending():
                if r is rec:
                    seen = True
                    continue
                if not seen or r.gen != rec.gen or r.committed is not None:
                    continue
                return r.snapshot
        return (self.params, self.opt_state)

    # ------------------------------------------------------------------
    # pipelined commit (depth N): resolution machinery
    # ------------------------------------------------------------------

    def pending_commits(self) -> int:
        """Uncommitted pipelined steps currently in flight (0 up to the
        window depth)."""
        return len(self._pipeline) if self._pipeline is not None else 0

    def _snapshot_nbytes(self, snapshot: Any) -> int:
        """Approximate resident bytes of one rollback snapshot, a
        ``(params, opt_state)`` pair of the owned state (device array
        leaves by ``nbytes``; opaque states that expose ``owned_bytes`` —
        the ZeRO shard state — by that). Feeds the
        ``tpuft_pipeline_snapshot_bytes`` gauge: the window holds one
        (params, opt_state) copy per slot, which is THE memory cost of
        deepening it (the doctor's depth probe states the formula).

        Every pair of one structure weighs the same, so the state is walked
        once an era (:meth:`_note_state_structure`), not once a committed
        step. The era is read BEFORE the walk: a heal that lands meanwhile
        leaves an entry no later step reads."""
        era = self._state_era
        known = self._state_nbytes
        if known is not None and known[0] == era:
            return known[1]
        total = 0
        try:
            for leaf in jax.tree_util.tree_leaves(
                snapshot, is_leaf=lambda x: hasattr(x, "owned_bytes")
            ):
                owned = getattr(leaf, "owned_bytes", None)
                if owned is not None:
                    # ZeroState's is a method.
                    total += int(owned() if callable(owned) else owned)
                else:
                    total += int(getattr(leaf, "nbytes", 0) or 0)
        except Exception:  # noqa: BLE001 — a gauge must never wound a step
            return 0
        self._state_nbytes = (era, total)
        return total

    def _note_snapshot(self, rec: "_PendingStep", admitted: bool) -> None:
        if admitted:
            self._snapshot_ring_bytes += rec.snapshot_bytes
        else:
            self._snapshot_ring_bytes = max(
                0, self._snapshot_ring_bytes - rec.snapshot_bytes
            )
        metrics.set_gauge(
            "tpuft_pipeline_snapshot_bytes", self._snapshot_ring_bytes
        )

    def next_pipelined_step(self) -> int:
        """The step index the next pipelined ``step_fn`` call will compute.

        ``manager.current_step()`` is unstable while a pipelined vote is in
        flight (it advances on the manager's executor the moment the
        barrier resolves), so DDP loops that key their data stream on the
        step must use this caller-thread-maintained prediction instead. It
        assumes every in-flight step commits; a failed commit or a heal
        makes up to window-depth predictions stale, and the next call
        re-anchors — every replica observes the same quorum-wide verdicts,
        so the streams stay in lockstep."""
        return self._next_pipelined_step

    def _resolve_pipelined_record(self, rec: _PendingStep) -> bool:
        """Vote phase: reads the barrier verdict and reconciles the already
        adopted speculation — confirm (no-op), roll back to the pre-step
        snapshot on a failed commit (discarding every younger slot of the
        window: the refusal is quorum-wide, so all survivors unwind the
        same suffix identically), or (same semantics as
        :meth:`_commit_and_adopt`) re-derive the update against a state the
        barrier healed — younger slots re-derive in turn when they become
        oldest, replaying the whole window's grads onto the healed state.
        Idempotent: the quorum-change drain and the train loop may both
        reach it."""
        schedules.point("optim.resolve_record")
        with rec._lock:
            if rec.committed is not None:
                return rec.committed
            if rec.gen != self._speculation_gen:
                # A rollback unwound the window past this slot: the step
                # never happened (quorum-wide). Consume the in-flight
                # verdict WITHOUT accounting and skip the device bound —
                # the work was discarded along with the state it computed.
                rec.discarded = True
                rec._bound = True
                discard = getattr(rec.commit_future, "discard", None)
                if discard is not None:
                    discard()
                else:  # pragma: no cover — depth-1 windows have no youngers
                    try:
                        rec.commit_future.result()
                    except Exception:  # noqa: BLE001
                        pass
                _trace_of(self.manager).record(
                    "speculation_discarded", step=rec.claimed_step
                )
                rec.committed = False
                return False
            trace = _trace_of(self.manager)
            with tracing.phase("commit_wait", trace, step=rec.claimed_step):
                committed = rec.commit_future.result()
            rolled_back = False
            discarded = 0
            with tracing.phase("adopt", trace, step=rec.claimed_step):
                with tracing.phase("state_swap", trace, step=rec.claimed_step):
                    self.manager.disallow_state_dict_read()
                    try:
                        if self._heal_count != rec.heal_count:
                            # Healed mid-flight: the donor state is
                            # authoritative; a committed step still owes its
                            # update (pre-heal grads applied to the healed state
                            # — reference load_state_dict + optimizer.step()
                            # order).
                            if committed:
                                self.params, self.opt_state = rec.recompute()
                        elif not committed:
                            # Refuse to adopt: restore the pre-step state the
                            # speculation was dispatched from, and turn every
                            # younger in-flight slot into a discard — their
                            # speculations chain from this refused one.
                            self.params, self.opt_state = rec.snapshot
                            self.rollback_count += 1
                            rolled_back = True
                            pending = (
                                self._pipeline.pending()
                                if self._pipeline is not None
                                else ()
                            )
                            discarded = sum(
                                1
                                for r in pending
                                if r is not rec and r.gen == rec.gen
                            )
                            self._speculation_gen += 1
                            metrics.inc(
                                "tpuft_rollbacks_total",
                                **_replica_labels(self.manager),
                            )
                            metrics.histogram(
                                "tpuft_rollback_unwind_depth",
                                buckets=_UNWIND_DEPTH_BUCKETS,
                            ).observe(1 + discarded)
                    finally:
                        self.manager.allow_state_dict_read()
                if committed:
                    # Ring-slot promotion: the resolved slot's committed
                    # state enters the step-labeled history instead of
                    # being dropped — after a heal it is the live
                    # (just-recomputed) state; otherwise the next younger
                    # slot's snapshot (speculations chain).
                    self._promote_committed(
                        rec.claimed_step + 1
                        if rec.claimed_step >= 0
                        else self._int_or_none(self.manager.current_step()),
                        *(
                            (self.params, self.opt_state)
                            if self._heal_count != rec.heal_count
                            else self._post_commit_state(rec)
                        ),
                        span_step=rec.claimed_step,
                    )
            if rolled_back:
                # Incident capture runs OUTSIDE the writer: dumping
                # journals is file I/O a concurrent checkpoint serve
                # must not wait on. Quorum-wide refusal means every
                # survivor rolls this step back identically and derives
                # the SAME incident id — the fleet's journals + flight
                # recorders dump under one correlatable stamp.
                journal = _trace_of(self.manager)
                rolled_step = self.manager.current_step()
                rolled_quorum = getattr(self.manager, "_quorum_id", -1)
                journal.record(
                    "rollback",
                    step=rolled_step,
                    quorum_id=rolled_quorum,
                    unwound_to=rolled_step,
                    discarded=discarded,
                )
                tracing.open_incident(
                    "rollback", rolled_step, rolled_quorum,
                    journal=journal,
                    reason="speculative step refused by the commit barrier",
                )
                # Serving plane: an unwind retracts any due-but-
                # unpublished version newer than the surviving
                # committed step — a discarded speculation must never
                # surface to readers (published versions are post-
                # barrier and final, so this is the only window).
                publisher = getattr(self.manager, "_publisher", None)
                if publisher is not None:
                    publisher.retract_after(rolled_step)
                # History ring: drop anything newer than the surviving
                # committed step (belt-and-braces — refused steps were
                # never promoted, but the ring must stay provably on
                # the committed trajectory).
                hist = getattr(self.manager, "_history", None)
                if hist is not None and hasattr(hist, "retract_newer"):
                    hist.retract_newer(rolled_step)
            rec.committed = committed
            return committed

    def flush_pipeline(self, raise_on_error: bool = True) -> Optional[bool]:
        """Resolves every pending pipelined step (vote + rollback + device
        bound), oldest first; returns the last resolved verdict (False when
        the tail of the window was unwound by a refusal), or None when the
        pipeline was idle. Call at train-loop boundaries — end of run,
        before a checkpoint restore, before switching step protocols."""
        if self._pipeline is None:
            return None
        last: Optional[bool] = None
        while True:
            rec = self._pipeline.oldest()
            if rec is None:
                break
            # Records stay in the pipeline until resolved so a refusal's
            # unwind can see (and discard) the younger slots.
            last = self._resolve_pipelined_record(rec)
            self._pipeline.remove(rec)
            self._note_snapshot(rec, admitted=False)
            rec.bound_device(raise_on_error=raise_on_error)
        return last

    def _drain_pipeline_for_quorum_change(self) -> None:
        """Quorum-change hook (runs on the manager's quorum thread): fully
        resolve the WHOLE speculative window before the PG reconfigures or
        a donor send samples this replica's state — a joiner must never
        heal from an uncommitted speculative step (tpuft_check rule R7
        pins the call ordering in the manager). Safe here at every depth:
        depth-1 votes ran earlier on the same single-thread executor
        (FIFO), so their result() cannot deadlock, and depth>=2 votes ride
        the manager's dedicated commit pool — never this thread; the
        train-loop thread is parked in wait_quorum while this runs.
        Records stay in the pipeline (resolved in place, both phases
        idempotent) so the train loop still observes each step's verdict
        on its own thread."""
        schedules.point("optim.window_drain")
        if self._pipeline is None:
            return
        pending = self._pipeline.pending()
        if not pending:
            return
        # The goodput ledger attributes this span to its `drain` bucket —
        # window-resolution time spent on the quorum thread is neither
        # quorum wait nor committed compute.
        with tracing.phase(
            "pipeline_drain", _trace_of(self.manager), depth=len(pending)
        ):
            for rec in pending:
                self._resolve_pipelined_record(rec)
                rec.bound_device(raise_on_error=False)


    def make_step_fn(
        self,
        loss_fn: Any,
        should_quantize: bool = False,
        on_quorum: Any = None,
    ):
        """Builds the fastest correct FT-DDP step for the current quorum:
        ``step_fn(*batch) -> (loss, committed)``.

        With other replica groups participating, the step is the standard
        split program — fused loss+grad dispatch, pipelined bucket gradient
        sync (:func:`~torchft_tpu.ddp.ft_allreduce_gradients`), speculative
        update under the commit barrier.

        For a **lone replica** (sole participant and a wire group of one —
        the identity-skip condition, see ``Manager.is_lone_replica``) the
        averaged gradient IS the local gradient, so nothing needs to leave
        the device: the whole loss+grad+update runs as ONE jitted XLA
        program, exactly like a plain non-FT train step, and like it the
        program is GIVEN the state: the step takes its verdict first and
        then updates ``params`` / ``opt_state`` in place, one copy of the
        state on the device (:meth:`_lone_step`; the class docstring says
        who owns what, and when the step keeps the speculative order and
        both copies instead). A refused step dispatches nothing to the
        state; a heal the barrier applied is what steps. The fusion removes
        the last fixed cost the split program pays (the standalone
        optimizer dispatch), making single-group FT-DDP bitwise-plain
        compute with only the quorum + commit RPCs on top (the reference's
        'FT for free' design point, lighthouse.rs:202-215).

        ``loss_fn(params, *batch) -> scalar``; ``on_quorum(seconds)``, when
        given, receives each step's measured quorum wait (telemetry hook).

        With ``Manager(commit_pipeline_depth=N)`` for N >= 1 (or
        ``TPUFT_COMMIT_PIPELINE_DEPTH=N|auto``) the returned step_fn runs
        the **pipelined-commit** schedule instead: up to N steps' device
        syncs and commit votes resolve while younger steps are already
        dispatched — a bounded speculative window that hides up to N
        control-plane round trips per step (``auto`` sizes N per quorum
        era from the measured RTT/step ratio). The returned ``committed``
        flag then reports the verdict of the OLDEST in-flight step
        resolved during the call — lagging dispatch by up to N steps, None
        while the window still has room; call :meth:`flush_pipeline` at
        the loop boundary for the rest. ``TPUFT_STRICT_COMMIT=1``
        overrides any pipeline depth back to the strict per-step ordering.
        """
        fused = make_jit_fused_step(self.tx, loss_fn)
        # The same program given its state: compiled at the first lone step
        # that updates in place (:meth:`_lone_step`), never otherwise.
        fused_in_place = make_jit_fused_step(self.tx, loss_fn, donate_state=True)
        grad_fn = jax.jit(jax.value_and_grad(loss_fn))

        depth = self.manager.commit_pipeline_depth
        if depth and os.environ.get("TPUFT_STRICT_COMMIT", "0") == "1":
            logger.warning(
                "TPUFT_STRICT_COMMIT=1 overrides commit_pipeline_depth=%d: "
                "running strict per-step commits (vote only after observed "
                "completion)",
                depth,
            )
            depth = 0
        if depth:
            return self._make_pipelined_step_fn(
                fused, grad_fn, should_quantize, on_quorum, depth
            )

        def step_fn(*batch):
            # The root span of one FT-DDP step: every gap of the device that
            # no child span covers lands here, which is the test of the
            # children.
            with tracing.phase(
                "optim_step", _trace_of(self.manager),
                step=self._int_or_none(self.manager.current_step()),
            ):
                return run_step(*batch)

        def run_step(*batch):
            self.begin_step()
            if on_quorum is not None:
                import time as _time

                t0 = _time.monotonic()
                self.manager.wait_quorum()
                on_quorum(_time.monotonic() - t0)
            else:
                self.manager.wait_quorum()
            if self.manager.errored() is None and self.manager.is_lone_replica():
                return self._lone_step(fused, fused_in_place, grad_fn, batch)
            return self._wire_step(grad_fn, batch, should_quantize)

        return step_fn

    # ------------------------------------------------------------------
    # make_step_fn seams (overridden by zero.ZeroOptimizer)
    # ------------------------------------------------------------------

    def _lone_step(
        self, fused: Any, fused_in_place: Any, grad_fn: Any, batch: Any
    ):
        """One lone-replica step at depth 0, after the quorum: returns
        ``(loss, committed)``. Base: vote first and update the state in
        place wherever nothing else may hold the old state (the class
        docstring's ownership contract), else the speculative order.
        ``ZeroOptimizer``, whose programs differ, stays on the latter."""
        if self._may_update_in_place():
            return self._vote_then_update_in_place(fused_in_place, grad_fn, batch)
        return self._speculative_lone_step(fused, grad_fn, batch)

    def _may_update_in_place(self) -> bool:
        """Whether the old state may be given away once this step's verdict
        is in. Read every step from what can be observed, no option:
        strict mode votes only after observed completion, so nothing can be
        given away before its vote; a tied array cannot be given twice; and
        a ring asked to keep older versions holds them by reference."""
        if os.environ.get("TPUFT_STRICT_COMMIT", "0") == "1" or self._state_aliased:
            return False
        hist = getattr(self.manager, "history", None)
        return isinstance(hist, WeightHistory) and hist.max_versions == 1

    def _vote_then_update_in_place(
        self, fused_in_place: Any, grad_fn: Any, batch: Any
    ):
        """The reference's order (``if manager.should_commit():
        optimizer.step()``): the verdict, then ONE fused program that is
        given the state. The lone replica's verdict is a function of what
        the host already knows (enough participants, no reported error: no
        peer's gradient to wait for), the speculative order sent it before
        the device had finished anyway, and ``should_commit`` has stopped
        serving checkpoints before it returns. What it costs is the commit
        RPC no longer hidden under the step's compute.

        A refused step dispatches nothing to the state. An accepted one
        reads the state AFTER the verdict (a heal that ``should_commit``
        applied is what steps, gradient and all), and replaces it, in
        ``self`` and in the history ring, inside the state-dict write
        lock. A failure after a True verdict, on the host or on the device,
        is the phantom commit the speculative order knows: the counter
        advanced without a verified update, the supervisor-restart path
        owns the recovery."""
        manager = self.manager
        trace = _trace_of(manager)
        with tracing.phase("commit_wait", trace):
            committed = manager.should_commit()
        if not committed:
            # The return contract's loss, from the program that takes
            # nothing: compiled at the first refusal only.
            loss, _ = grad_fn(self.params, *batch)
            return _sync_device(loss), False
        try:
            manager.disallow_state_dict_read()
            try:
                with tracing.phase(
                    "update_dispatch", trace, fused=True, donated=True
                ):
                    loss, params, opt_state = fused_in_place(
                        self.params, self.opt_state, *batch
                    )
                n_state = sum(self._state_leaves)
                _count_dispatch(
                    n_state + self._batch_buffers(batch), 1 + n_state,
                    donated=True,
                )
                with tracing.phase("adopt", trace):
                    with tracing.phase("state_swap", trace):
                        self.params, self.opt_state = params, opt_state
                    self._promote_committed(
                        self._int_or_none(manager.current_step()),
                        params, opt_state,
                    )
            finally:
                manager.allow_state_dict_read()
            _sync_device(loss)
        except BaseException as e:
            metrics.inc("tpuft_phantom_commits_total", **_replica_labels(manager))
            trace.record("phantom_commit", error=str(e))
            logger.error(
                "fused step failed after its commit vote resolved "
                "committed=True (the step counter advanced without a "
                "verified update)"
            )
            raise
        return loss, True

    def _speculative_lone_step(self, fused: Any, grad_fn: Any, batch: Any):
        """The lone step that keeps the old state until its verdict:
        dispatch speculatively, vote with the device work in flight (or
        after it, ``TPUFT_STRICT_COMMIT=1``), adopt on commit."""
        heal_count = self._heal_count
        loss, spec, recompute = self._lone_dispatch(fused, grad_fn, batch)
        # Launch the barrier BEFORE the device sync so the commit
        # RPC rides under the readiness wait instead of after it
        # (the wait lasts as long as the step's remaining compute,
        # so serializing sync -> RPC was pure addition). This widens
        # .step()'s accepted envelope slightly: .step() bounds the
        # GRADS pre-vote and risks only a host-side dispatch
        # failure post-vote, while here a device-side failure of
        # the whole fused step can land after the vote was sent.
        # The blast radius in this LONE topology is bounded
        # accounting, not divergence: there is no peer to diverge
        # from, and recovery is the same supervisor-restart path
        # .step() documents — the committed counter can run one
        # step ahead of the restored state (a phantom commit).
        # Deployments that prefer the strict reference ordering
        # (vote only after observed completion; reference
        # manager.py:816-827) set TPUFT_STRICT_COMMIT=1 and pay
        # the serialized sync; a sync failure then raises before
        # any vote leaves, the pre-change semantics exactly.
        strict = os.environ.get("TPUFT_STRICT_COMMIT", "0") == "1"
        if strict:
            _sync_device(loss)
        commit_future = self.manager.should_commit_async(None)
        if not strict:
            try:
                _sync_device(loss)
            except BaseException:
                try:
                    barrier_result = commit_future.result()
                except Exception:
                    logger.exception(
                        "commit barrier also failed while handling a "
                        "fused-step sync failure; barrier outcome lost "
                        "to the re-raise"
                    )
                else:
                    if barrier_result:
                        metrics.inc(
                            "tpuft_phantom_commits_total",
                            **_replica_labels(self.manager),
                        )
                        _trace_of(self.manager).record("phantom_commit")
                    logger.error(
                        "fused step sync failed with the commit barrier "
                        "in flight; barrier resolved committed=%s (a "
                        "committed step here advanced the step counter "
                        "without its update)",
                        barrier_result,
                    )
                raise

        committed = self._commit_and_adopt(
            heal_count, spec, recompute, None, commit_future=commit_future
        )
        return loss, committed

    def _lone_dispatch(self, fused: Any, grad_fn: Any, batch: Any):
        """Dispatches the lone-replica step's device work; returns
        ``(loss, speculation, recompute)``. The caller owns the barrier
        ordering (strict/overlapped/pipelined) around the returned loss.
        Base: the whole loss+grad+update as ONE fused XLA program."""
        # Heals rebind self.params (never mutate buffers), so this
        # reference keeps the pre-heal state alive for the rare
        # heal-during-barrier recompute below.
        pre_params = self.params
        with tracing.phase(
            "update_dispatch", _trace_of(self.manager), fused=True, donated=False
        ):
            loss, spec_params, spec_opt_state = fused(
                self.params, self.opt_state, *batch
            )
        n_state = sum(self._state_leaves)
        _count_dispatch(
            n_state + self._batch_buffers(batch), 1 + n_state, donated=False
        )

        def recompute():
            # Same semantics as :meth:`step` (and the reference's
            # load_state_dict + optimizer.step() sequence): the
            # gradients computed on the PRE-heal params apply to the
            # healed state.
            _, grads = grad_fn(pre_params, *batch)
            return self._jit_update(grads, self.opt_state, self.params)

        return loss, (spec_params, spec_opt_state), recompute

    def _wire_step(self, grad_fn: Any, batch: Any, should_quantize: bool):
        """The non-pipelined step with other replica groups participating:
        grad dispatch, cross-replica sync, :meth:`step`. Base: bucketed
        gradient allreduce, then the standard averaged-grads step."""
        from torchft_tpu.ddp import ft_allreduce_gradients

        loss, grads = grad_fn(self.params, *batch)
        committed = self.step(
            ft_allreduce_gradients(self.manager, grads, should_quantize)
        )
        return loss, committed

    def _wire_speculate(self, grads: Any, pre_opt: Any, pre_params: Any,
                        should_quantize: bool):
        """The pipelined wire path's speculative update: syncs ``grads``
        across replicas and computes the speculative ``(params,
        opt_state)`` from the PRE-step state; returns ``(speculation,
        recompute)``. Must complete its collectives before returning —
        the caller launches the commit vote right after, and a rank whose
        sync failed must not vote commit."""
        from torchft_tpu.ddp import ft_allreduce_gradients

        avg = ft_allreduce_gradients(self.manager, grads, should_quantize)
        spec = self._jit_update(avg, pre_opt, pre_params)

        def recompute(avg=avg):
            return self._jit_update(avg, self.opt_state, self.params)

        return spec, recompute

    def _make_pipelined_step_fn(
        self, fused: Any, grad_fn: Any, should_quantize: bool,
        on_quorum: Any, depth: int,
    ):
        """The pipelined-commit schedule (window depth N >= 1): per call —

        1. (wire path) speculatively dispatch this step's forward/backward
           and start staging the gradients to host, BEFORE any older vote
           resolves;
        2. resolve just enough of the OLDEST window slots to open one:
           with the window full, exactly one verdict per call — confirm,
           roll the live state back to that slot's pre-step snapshot
           (discarding every younger slot: their speculations chain from
           the refused one), or heal-recompute (younger slots replay their
           grads onto the healed state as they resolve in turn);
        3. quorum (a membership change drains the FULL window on the
           quorum thread before the PG reconfigures or any donor send —
           see Manager.register_quorum_change_hook);
        4. dispatch this step and tentatively adopt its speculative
           (params, opt_state) — the window grows to at most depth
           uncommitted steps;
        5. observe the resolved slots' device completion: the readiness
           round trips ride under THIS step's device execution instead of
           serializing after it (the per-step RTT this mode kills);
        6. vote with this step's device work still in flight — but only
           AFTER step (N - depth)'s completion was observed in 5, so the
           phantom-commit envelope is bounded at exactly the window depth.

        Depth 1 keeps the single-executor vote path whose FIFO ordering
        the depth-1 tests pin; depth >= 2 (and adaptive mode at any depth)
        votes through Manager.speculative_commit_async so the whole
        window's barrier RPCs overlap on the wire — that overlap is what
        hides MULTIPLE control-plane round trips per step. In adaptive
        mode the target depth is re-read from the manager every call, so
        the controller's per-era re-evaluation (and mid-era deepening)
        takes effect between steps without rebuilding the step_fn.

        The widened envelope vs the overlapped ordering: post-vote device
        failures can phantom-commit up to DEPTH steps (vote N observed
        completion only through N - depth). The blast radius is bounded
        accounting, not divergence — a failure discovered at a vote makes
        that commit fail quorum-wide, every survivor unwinds the same
        suffix of the window identically, and recovery for hard device
        failures is the same supervisor-restart + heal path the
        non-pipelined orderings document.
        """
        import time as _time

        from torchft_tpu.ddp import prefetch_gradients
        from torchft_tpu.futures import CommitPipeline

        if self._pipeline is not None and len(self._pipeline):
            self.flush_pipeline()
        manager = self.manager
        pipeline = CommitPipeline(max(1, depth))
        self._pipeline = pipeline
        if not self._pipeline_hooked:
            manager.register_quorum_change_hook(
                self._drain_pipeline_for_quorum_change
            )
            manager.register_shutdown_hook(
                lambda: self.flush_pipeline(raise_on_error=False)
            )
            self._pipeline_hooked = True
        self._next_pipelined_step = manager.current_step()
        was_wire = [False]
        # Depth 1 static keeps the legacy single-executor vote (its FIFO
        # ordering is pinned); deeper/adaptive windows vote concurrently.
        speculative_votes = manager.commit_pipeline_adaptive or depth >= 2

        def step_fn(*batch):
            with tracing.phase(
                "optim_step", _trace_of(manager), step=self._next_pipelined_step
            ):
                return run_step(*batch)

        def run_step(*batch):
            target_depth = max(1, manager.commit_pipeline_depth)
            pipeline.set_depth(target_depth)
            # Next-step dispatch before any vote resolution: the wire
            # path's forward/backward depends only on the (already
            # adopted, speculative) params, so its device work and d2h
            # staging start under the vote wait + quorum RPC. A rollback
            # or heal below invalidates it — detected by identity on the
            # exact params it read — and it is recomputed.
            early = None
            if was_wire[0]:
                early_heal = self._heal_count
                early_params = self.params
                early = grad_fn(early_params, *batch)
                prefetch_gradients(early[1])

            # Resolve the oldest slots until the window has room (plus any
            # slot a rollback already unwound — zombies consume instantly).
            stall_t0 = _time.monotonic()
            first_verdict: Optional[bool] = None
            to_bound = []
            while True:
                rec = pipeline.oldest()
                if rec is None:
                    break
                zombie = (
                    rec.committed is not None
                    or rec.gen != self._speculation_gen
                )
                if not zombie and len(pipeline) < target_depth:
                    break
                verdict = self._resolve_pipelined_record(rec)
                pipeline.remove(rec)
                self._note_snapshot(rec, admitted=False)
                to_bound.append(rec)
                if first_verdict is None:
                    first_verdict = verdict
            vote_stall = _time.monotonic() - stall_t0

            self.begin_step()
            if on_quorum is not None:
                t0 = _time.monotonic()
                manager.wait_quorum()
                on_quorum(_time.monotonic() - t0)
            else:
                manager.wait_quorum()

            heal_count = self._heal_count
            pre_params, pre_opt = self.params, self.opt_state
            lone = manager.errored() is None and manager.is_lone_replica()
            was_wire[0] = not lone
            if lone:
                loss, spec, recompute = self._lone_dispatch(
                    fused, grad_fn, batch
                )
            else:
                if (
                    early is not None
                    and early_heal == self._heal_count
                    and early_params is pre_params
                ):
                    loss, grads = early
                else:
                    loss, grads = grad_fn(pre_params, *batch)
                spec, recompute = self._wire_speculate(
                    grads, pre_opt, pre_params, should_quantize
                )

            # Tentative adoption — one more slot of the uncommitted
            # window. Write-locked so a concurrent donor capture never
            # reads a torn pair.
            schedules.point("optim.speculate_adopt")
            manager.disallow_state_dict_read()
            try:
                self.params, self.opt_state = spec
            finally:
                manager.allow_state_dict_read()
            # Claim the step this slot speculates: committed + in-flight.
            # Count only UNRESOLVED slots — the quorum-thread drain
            # resolves records in place without removing them (the train
            # loop still observes each verdict), so raw occupancy can
            # overcount right after a membership change.
            claimed_step = manager.current_step() + sum(
                1 for r in pipeline.pending() if r.committed is None
            )
            self._next_pipelined_step = claimed_step + 1

            # Observe the resolved slots' device completion BEFORE this
            # step's vote leaves: the envelope invariant — vote N is sent
            # only after step (N - depth)'s completion was observed. The
            # sync rides under this step's (already dispatched) execution.
            stall_t0 = _time.monotonic()
            for done_rec in to_bound:
                done_rec.bound_device(raise_on_error=True)
            manager.observe_pipeline_step(
                vote_stall + (_time.monotonic() - stall_t0)
            )

            if speculative_votes:
                commit_future = manager.speculative_commit_async(claimed_step)
            else:
                commit_future = manager.should_commit_async(None)
            rec = _PendingStep(
                manager=manager,
                heal_count=heal_count,
                loss=loss,
                snapshot=(pre_params, pre_opt),
                recompute=recompute,
                commit_future=commit_future,
                gen=self._speculation_gen,
                claimed_step=claimed_step,
                snapshot_bytes=self._snapshot_nbytes((pre_params, pre_opt)),
            )
            pipeline.push(rec)
            self._note_snapshot(rec, admitted=True)
            _trace_of(manager).record(
                "speculate",
                step=claimed_step,
                window=len(pipeline),
                depth=target_depth,
            )
            return loss, first_verdict

        return step_fn


# Name parity with the reference export.
OptimizerWrapper = Optimizer
