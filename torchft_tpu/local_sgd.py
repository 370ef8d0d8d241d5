"""LocalSGD and (Streaming) DiLoCo: semi-synchronous training.

Role-equivalent of the reference's ``torchft/local_sgd.py``. Both algorithms
run ``sync_every`` cheap local steps between expensive cross-replica syncs —
the natural fit for the TPU replica axis riding DCN between slices:

- :class:`LocalSGD` (reference :46-173): every ``sync_every`` steps, average
  the *parameters* across replica groups and commit.
- :class:`DiLoCo` (reference :570-797, DiLoCo https://arxiv.org/pdf/2311.08105,
  Streaming DiLoCo https://arxiv.org/pdf/2501.18512): keep a backup of the
  last-synced "global" parameters; every cycle, average the *pseudogradient*
  (global − local) for one model fragment and apply it with an outer
  optimizer (typically Nesterov SGD). Fragments rotate by manager step so all
  replicas reduce the same fragment (cross-replica deadlock avoidance,
  reference :753-764); ``fragment_sync_delay`` overlaps the allreduce with
  further local steps.

Both own (params, inner_opt_state) like :class:`torchft_tpu.optim.Optimizer`
and register their state with the manager for live healing.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from torchft_tpu import metrics, tracing
from torchft_tpu.history import _device_copy, _snapshot
from torchft_tpu.manager import Manager
from torchft_tpu.optim import _trace_of
from torchft_tpu.utils import netem
from torchft_tpu.utils.transfer import prefetch_to_host
from torchft_tpu.work import Work

logger = logging.getLogger(__name__)

__all__ = ["LocalSGD", "DiLoCo", "cross_region_fleet", "region_split"]


def region_split(replica_ids: Sequence[str]) -> Dict[str, List[str]]:
    """Groups replica ids by their WAN topology region (region name ->
    ids; ``None``-region ids group under ``""``). Pure bookkeeping over
    the netem region map — the replica axis stays OUTSIDE the jax Mesh,
    so a membership change in any region never recompiles a program.
    With no topology configured every id lands in the ``""`` group (the
    single-region degenerate case)."""
    split: Dict[str, List[str]] = {}
    for rid in replica_ids:
        split.setdefault(netem.region_of(rid) or "", []).append(rid)
    return split


def cross_region_fleet() -> bool:
    """True when the configured WAN topology names more than one region —
    the signal DiLoCo uses to default its outer-sync wire to the
    quantized codec (outer syncs are the cross-region traffic; per-step
    DDP inside a region never leaves the cheap links)."""
    topo = netem.describe_topology()
    return bool(topo.get("configured")) and not topo.get("single_region", True)


def _to_device_like(host: np.ndarray, like: Any) -> Any:
    import jax.numpy as jnp

    if isinstance(like, jax.Array):
        return jax.device_put(host, like.sharding)
    return jnp.asarray(host)


def _restore_leaf_like(new: Any, like: Any, device: bool) -> Any:
    """One healed leaf onto ``like``'s layout. Routes through
    ``optim._restore_leaf`` so multi-host donor captures
    (:class:`~torchft_tpu.checkpointing._serialization.ShardedLeaf`) are
    reassembled shard-by-shard against the current sharding — plain host
    arrays land via device_put on the template's sharding."""
    import jax.numpy as jnp

    from torchft_tpu.checkpointing._serialization import ShardedLeaf
    from torchft_tpu.optim import _restore_leaf

    if isinstance(new, ShardedLeaf) or device:
        return _restore_leaf(new, like)
    if hasattr(new, "shape"):
        return np.asarray(new)
    return new


def _restore_like(state: Any, template: Any, device: bool) -> Any:
    """Restores a healed pytree onto the TEMPLATE's shardings (leaf by
    leaf) so a joiner's state lands with the same partitioning the donor
    computes with; falls back to a plain restore only on an explicit
    treedef mismatch (e.g. fresh vs restored optax state) — a leaf-level
    failure inside a matching restore must surface, not silently drop the
    shardings."""
    import jax.numpy as jnp

    from torchft_tpu.checkpointing._serialization import ShardedLeaf

    is_leaf = lambda x: isinstance(x, ShardedLeaf)  # noqa: E731
    if jax.tree_util.tree_structure(
        state, is_leaf=is_leaf
    ) != jax.tree_util.tree_structure(template):
        as_leaf = jnp.asarray if device else np.asarray

        def _fallback_leaf(x: Any) -> Any:
            # A ShardedLeaf here means a multi-host donor capture arrived
            # with a mismatched treedef: there is no template leaf to
            # reassemble its shards against, and passing the dataclass
            # through would only fail later inside jit with an opaque
            # error. Fail now, with guidance.
            if isinstance(x, ShardedLeaf):
                raise ValueError(
                    "healed state contains a multi-host ShardedLeaf but its "
                    "tree structure does not match the local template; "
                    "donor and joiner opt-state structures must match for "
                    "multi-host heal (construct the joiner's optimizer "
                    "state with the same optax chain before healing)"
                )
            return as_leaf(x) if hasattr(x, "shape") else x

        return jax.tree_util.tree_map(_fallback_leaf, state, is_leaf=is_leaf)
    return jax.tree_util.tree_map(
        lambda x, like: _restore_leaf_like(x, like, device),
        state,
        template,
        is_leaf=is_leaf,
    )


class LocalSGD:
    """Parameter-averaging semi-sync training (reference local_sgd.py:46-173).

    Runs the inner optimizer every step; every ``sync_every`` steps averages
    the parameters across replica groups and commits. A failed commit keeps
    the local parameters and retries at the next sync point.
    """

    def __init__(
        self,
        manager: Manager,
        inner_tx: Any,
        params: Any,
        sync_every: int,
        register_key: str = "local_sgd",
    ) -> None:
        assert sync_every >= 1
        self._manager = manager
        self._inner_tx = inner_tx
        self.params = params
        self.opt_state = inner_tx.init(params)
        self._sync_every = sync_every
        self._local_step = 0
        manager.register_state_dict_fn(register_key, self._load_state, self._save_state)

        from torchft_tpu.optim import make_jit_update

        # One fused dispatch per inner step (hot path: sync_every - 1 of
        # every sync_every steps touch no network at all).
        self._jit_update = make_jit_update(inner_tx)

    def _save_state(self) -> Dict[str, Any]:
        return {"params": self.params, "opt_state": self.opt_state}

    # tpuft: allow(lock-discipline): heal apply — runs under the state-dict writer taken by Manager._apply_pending_state_dict
    def _load_state(self, state: Dict[str, Any]) -> None:
        # Sharding-preserving restore (see _restore_like).
        self.params = _restore_like(state["params"], self.params, device=True)
        self.opt_state = _restore_like(
            state["opt_state"], self.opt_state, device=True
        )

    def step(self, grads: Any) -> bool:
        """One inner step; returns whether a sync round committed."""
        # Write-lock mutations so checkpoint captures never see a torn state
        # (reference step pre/post hooks, local_sgd.py:112-128).
        self._manager.disallow_state_dict_read()
        try:
            self.params, self.opt_state = self._jit_update(
                grads, self.opt_state, self.params
            )
        finally:
            self._manager.allow_state_dict_read()
        return self._after_inner_step()

    def make_step_fn(self, loss_fn: Any):
        """``step_fn(*batch) -> (loss, synced)``: the inner step as ONE
        fused jitted dispatch (loss+grad+update — sync_every−1 of every
        sync_every steps touch no network, so their cost is exactly the
        plain train step), with the parameter-averaging sync at the
        boundary. ``loss_fn(params, *batch) -> scalar``. Mirrors
        ``DiLoCo.make_step_fn`` / ``Optimizer.make_step_fn``."""
        from torchft_tpu.optim import make_jit_fused_step

        fused = make_jit_fused_step(self._inner_tx, loss_fn)

        def step_fn(*batch):
            self._manager.disallow_state_dict_read()
            try:
                loss, self.params, self.opt_state = fused(
                    self.params, self.opt_state, *batch
                )
            finally:
                self._manager.allow_state_dict_read()
            return loss, self._after_inner_step()

        return step_fn

    def _after_inner_step(self) -> bool:
        """Shared sync-boundary bookkeeping for step()/make_step_fn()."""
        self._local_step += 1
        if self._local_step < self._sync_every:
            return False
        self._local_step = 0
        return self._sync()

    def _sync(self) -> bool:
        # Shard-preserving parameter averaging (parallel/mesh.py): each
        # rank stages its OWN addressable shards, reduces them with the
        # same-rank shards in the other replica groups, and reassembles
        # onto the original shardings — so LocalSGD composes with
        # multi-host fsdp/tp state (a whole-leaf host fetch would raise on
        # non-fully-addressable arrays and lose the shardings on restore).
        from torchft_tpu.parallel.mesh import ft_allreduce_sharded

        with tracing.phase("perform_sync", _trace_of(self._manager)):
            self._manager.start_quorum()
            averaged = ft_allreduce_sharded(self._manager, self.params)
            if self._manager.should_commit():
                self._manager.disallow_state_dict_read()
                try:
                    self.params = averaged
                finally:
                    self._manager.allow_state_dict_read()
                return True
            return False


def _device_sync_programs(leaves: Sequence[Any], outer_tx: Any, alpha: float):
    """The two jitted programs of a quantized fragment sync, for leaves of
    these shapes and dtypes: ``quantize_pseudograd(backup, local)`` gives the
    wire's (payload, scales) of ``backup - local``, and ``apply_outer(payload,
    scales, backup, local, outer_state)`` the new backup, the merged leaves
    and the new outer state. The codec reads and writes each leaf in the
    layout it has wherever its shape gives whole blocks
    (``make_tree_fp8_codec``), so neither program copies the fragment
    through a flat float32 array."""
    import jax.numpy as jnp

    from torchft_tpu.ops.quantization import make_tree_fp8_codec

    quantize, dequantize = make_tree_fp8_codec(leaves)

    # The programs' names are read from outside (the benchmark finds
    # jit_quantize_pseudograd and jit_apply_outer in the device trace).
    def quantize_pseudograd(backup_leaves, local_leaves):
        return quantize(backup_leaves, local_leaves)

    def apply_outer(payload, scales, backup_leaves, local_leaves, outer_state):
        import optax

        avg_pg = dequantize(payload, scales)
        updates, new_state = outer_tx.update(avg_pg, outer_state, backup_leaves)
        new_backup = optax.apply_updates(backup_leaves, updates)
        merged = [
            (g.astype(jnp.float32) * (1.0 - alpha)
             + l.astype(jnp.float32) * alpha).astype(g.dtype)
            for g, l in zip(new_backup, local_leaves)
        ]
        return new_backup, merged, new_state

    # The fragment's local leaves (dead after the merge) and the outer
    # state are updated in place: their buffers become the merged
    # leaves, the new backup's and the new outer state's. The old
    # backup is not given away: until a fragment's first sync it is
    # the caller's array.
    return jax.jit(quantize_pseudograd), jax.jit(apply_outer, donate_argnums=(3, 4))


class _Fragment:
    """One model fragment's DiLoCo state: the backup of the last-synced
    global parameters, the outer optimizer state, and the in-flight
    pseudogradient allreduce (reference _StreamingDiLoCoFragment:176-568).

    Two sync pipelines:
    - plain (``should_quantize=False``): host-numpy pseudogradients through
      ``manager.allreduce_pytree`` (the reference's default path);
    - quantized (``should_quantize=True``): TPU-first — the backup lives on
      device, pseudogradient + fp8 quantization run as one jitted kernel
      (Pallas on TPU), and only the fp8 payload + block scales cross the
      host boundary (~4x less traffic than f32), riding
      :func:`allreduce_quantized_wire` between replica groups.
    """

    def __init__(
        self,
        manager: Manager,
        fragment_id: int,
        leaf_indices: List[int],
        outer_tx: Any,
        initial_leaves: List[Any],
        should_quantize: bool,
        fragment_update_alpha: float,
    ) -> None:
        import jax.numpy as jnp

        self._manager = manager
        self._fragment_id = fragment_id
        self.leaf_indices = leaf_indices
        self._outer_tx = outer_tx
        self._should_quantize = should_quantize
        self._alpha = fragment_update_alpha
        self._key = f"StreamingDiLoCoFragment_{fragment_id}"
        if should_quantize:
            # Device-resident backup (HBM): no host copy in the hot path.
            # These are the caller's own arrays, held and never donated;
            # the live leaves are the copies (DiLoCo.__init__).
            self.backup: List[Any] = [jnp.asarray(x) for x in initial_leaves]
        else:
            # Host backup (the "CPU-pinned" analogue of the reference).
            # Requires fully-addressable leaves: fail at construction with
            # guidance rather than deep inside the first sync.
            for x in initial_leaves:
                if isinstance(x, jax.Array) and not x.is_fully_addressable:
                    raise ValueError(
                        "DiLoCo's host (non-quantized) pipeline needs "
                        "fully-addressable parameters; for multi-host "
                        "sharded state use should_quantize=True (the "
                        "device pipeline keeps backups sharded on the "
                        "group mesh)"
                    )
            self.backup = [np.array(x, copy=True) for x in initial_leaves]
        self.outer_opt_state = outer_tx.init(self.backup)
        if not should_quantize:
            from torchft_tpu.optim import make_jit_update

            # The host path's outer step still goes through ONE jitted
            # dispatch (the unjitted-optax invariant): an eager optax
            # update issues hundreds of tiny ops on the default backend,
            # one dispatch each. The quantized path's
            # outer step is fused into _jit_apply_outer below.
            self._jit_outer_update = make_jit_update(outer_tx)
        self._work: Optional[Work] = None
        manager.register_state_dict_fn(self._key, self._load_state, self._save_state)

        if should_quantize:
            self._build_device_pipeline()

    def _build_device_pipeline(self) -> None:
        """Jitted device kernels for the quantized path (shared fp8 codec)."""
        from torchft_tpu.ops.quantization import tree_codec_elements

        self._jit_quantize_pg, self._jit_apply_outer = _device_sync_programs(
            self.backup, self._outer_tx, self._alpha
        )
        # How many of the fragment's elements the codec takes in the leaves'
        # own layout and how many flat: static, and counted at every sync.
        self._codec_elements = tree_codec_elements(self.backup)

    def _save_state(self) -> Dict[str, Any]:
        # Device state is handed over as a device copy, not as references:
        # the caller stages it after releasing the read lock, and the next
        # outer step deletes the outer state it replaces. (The checkpoint
        # transport host-converts every leaf at staging time: ShardedLeaf
        # capture for non-fully-addressable arrays — an eager np.array here
        # would RAISE on multi-host shardings.) Host backups are snapshotted
        # since the list is rebound, never mutated, on sync; the host
        # pipeline's outer step donates nothing.
        if self._should_quantize:
            return _snapshot(
                self._key,
                {
                    "original_parameters": list(self.backup),
                    "outer_optimizer": self.outer_opt_state,
                },
            )
        return {
            "original_parameters": [np.array(b) for b in self.backup],
            "outer_optimizer": self.outer_opt_state,
        }

    # tpuft: allow(lock-discipline): heal apply — runs under the state-dict writer taken by Manager._apply_pending_state_dict
    def _load_state(self, state: Dict[str, Any]) -> None:
        # Healing must restore SHARDING, not just values: the joiner's
        # pre-heal backups carry the model's fsdp/tp shardings, and a plain
        # jnp.asarray restore would leave the healed state replicated — the
        # joiner's jitted programs would then partition differently from the
        # donor's, and their reductions drift by an ulp per sync (breaking
        # the bitwise cross-replica invariant the integration tests assert).
        # Multi-host donor captures arrive as ShardedLeaf and reassemble
        # against the current backup's sharding (_restore_leaf_like).
        restored = state["original_parameters"]
        if len(restored) != len(self.backup):
            raise ValueError(
                f"healed fragment has {len(restored)} leaves, expected "
                f"{len(self.backup)}: donor/joiner fragment partitioning "
                "must match"
            )
        if self._should_quantize:
            self.backup = [
                _restore_leaf_like(b, like, device=True)
                for b, like in zip(restored, self.backup)
            ]
        else:
            self.backup = [np.array(b) for b in restored]
        self.outer_opt_state = _restore_like(
            state["outer_optimizer"],
            self.outer_opt_state,
            device=self._should_quantize,
        )

    def prepare_sync(self, local_leaves: List[Any]) -> None:
        """Computes pseudogradients (backup − local) and launches their
        averaging; does not wait (reference :402-421)."""
        assert self._work is None, "fragment already has an allreduce in flight"
        trace, ids = _trace_of(self._manager), self._span_ids()
        with tracing.phase("prepare_sync", trace, **ids):
            if self._should_quantize:
                with tracing.phase("sync_quantize", trace, **ids):
                    payload, scales = self._jit_quantize_pg(
                        self.backup, [local_leaves[i] for i in self.leaf_indices]
                    )
                for path, elements in self._codec_elements.items():
                    metrics.inc("tpuft_codec_elements_total", elements, path=path)
                # Device arrays pass through: the d2h fetch happens on the
                # pipeline thread, overlapping the delay window's inner steps.
                # Participation zeroing + error funnel live in the manager.
                with tracing.phase("sync_launch", trace, **ids):
                    self._work = self._manager.allreduce_prequantized(
                        payload, scales
                    )
            else:
                with tracing.phase("sync_quantize", trace, **ids):
                    locals_ = [local_leaves[i] for i in self.leaf_indices]
                    # Launch every device→host copy before consuming any: the
                    # per-leaf np.asarray below then drains transfers already
                    # in flight instead of serializing one round trip per leaf.
                    prefetch_to_host(locals_)
                    pseudograds = [
                        backup - np.asarray(leaf)
                        for backup, leaf in zip(self.backup, locals_)
                    ]
                with tracing.phase("sync_launch", trace, **ids):
                    self._work = self._manager.allreduce_pytree(pseudograds)

    def _span_ids(self) -> Dict[str, Any]:
        """What every span of this fragment's sync carries: the fragment and
        the manager's step (the step the sync commits)."""
        step = self._manager.current_step()
        # A scripted manager's step may be no number: phase() leaves a None out.
        return {
            "fragment": self._fragment_id,
            "step": step if isinstance(step, int) else None,
        }

    def perform_sync(self, local_leaves: List[Any]) -> bool:
        """Waits for the allreduce, restores globals, commits, and on success
        applies the outer step + local/global merge (reference :423-476)."""
        assert self._work is not None, "perform_sync before prepare_sync"
        trace, ids = _trace_of(self._manager), self._span_ids()
        with tracing.phase("perform_sync", trace, **ids):
            with tracing.phase("sync_wait", trace, **ids):
                averaged = self._work.wait()
            self._work = None
            with tracing.phase("sync_restore", trace, **ids):
                local_copy = self._restore_globals(local_leaves)
            applied = False
            try:
                # The commit barrier must run unlocked: it can apply a healing
                # state dict and peers' serve threads need the read lock meanwhile.
                with tracing.phase("sync_commit", trace, **ids):
                    committed = self._manager.should_commit()
                # averaged is None: quantized-path allreduce error (already reported)
                if committed and averaged is not None:
                    with tracing.phase("sync_apply_outer", trace, **ids):
                        self._apply_outer(averaged, local_copy, local_leaves)
                    applied = True
            finally:
                if not applied:
                    self._disown_backup(local_leaves)
            return applied

    def _disown_backup(self, local_leaves: List[Any]) -> None:
        """After a sync that applied nothing: the leaves that
        ``_restore_globals`` bound to the device backup's own arrays become
        copies of them, so that the next inner step, which deletes the
        leaves it replaces, cannot delete the backup. One device copy of
        the fragment, paid only here (a committed sync replaces the leaves
        with the merged ones). A heal inside the barrier has already put
        new arrays in both places: nothing is shared, nothing is copied."""
        shared = [
            (slot, i)
            for slot, i in enumerate(self.leaf_indices)
            if local_leaves[i] is self.backup[slot]
        ]
        if not shared:
            return
        copies = _snapshot(self._key, [self.backup[slot] for slot, _ in shared])
        self._manager.disallow_state_dict_read()
        try:
            for (_, i), copy in zip(shared, copies):
                local_leaves[i] = copy
        finally:
            self._manager.allow_state_dict_read()

    def _restore_globals(self, local_leaves: List[Any]) -> List[Any]:
        """Copies this fragment's local leaves aside and rebinds them to the
        backups; returns the copies."""
        locals_ = [local_leaves[i] for i in self.leaf_indices]
        if not self._should_quantize:
            # Same launch-then-drain pattern as prepare_sync: this fetch sits
            # on the commit critical path right after wait().
            prefetch_to_host(locals_)
        local_copy = [
            leaf if self._should_quantize else np.asarray(leaf)
            for leaf in locals_
        ]
        # Restore to the last global state before voting: on a failed commit
        # the fragment resets rather than over-training on a divergent copy.
        self._manager.disallow_state_dict_read()
        try:
            for slot, backup in enumerate(self.backup):
                local_leaves[self.leaf_indices[slot]] = (
                    backup
                    if self._should_quantize
                    else _to_device_like(backup, local_leaves[self.leaf_indices[slot]])
                )
        finally:
            self._manager.allow_state_dict_read()
        return local_copy

    def _apply_outer(
        self, averaged: Any, local_copy: List[Any], local_leaves: List[Any]
    ) -> None:
        """The outer step on the averaged pseudogradient and the
        local/global merge, write-locked."""
        self._manager.disallow_state_dict_read()
        try:
            if self._should_quantize:
                import jax.numpy as jnp

                payload, scales = averaged
                # The averaged wire payload arrives as a HOST array on every
                # local rank. With a multi-rank group the backups are global
                # arrays over the group's mesh, and a plain jnp.asarray
                # would make the payload process-LOCAL — mixed local/global
                # inputs desync the ranks' jitted programs (one raises, the
                # peer enters the collective: deadlock). Restore it
                # REPLICATED on the backup's mesh; every rank holds the
                # identical averaged bytes, so the replicated device_put is
                # consistent by construction.
                mesh = (
                    getattr(self.backup[0].sharding, "mesh", None)
                    if isinstance(self.backup[0], jax.Array)
                    else None
                )
                if mesh is not None and len(mesh.devices.flat) > 1:
                    from jax.sharding import NamedSharding, PartitionSpec

                    replicated = NamedSharding(mesh, PartitionSpec())
                    payload = jax.device_put(np.asarray(payload), replicated)
                    scales = jax.device_put(np.asarray(scales), replicated)
                else:
                    payload = jnp.asarray(payload)
                    scales = jnp.asarray(scales)
                new_backup, merged, self.outer_opt_state = self._jit_apply_outer(
                    payload,
                    scales,
                    self.backup,
                    local_copy,
                    self.outer_opt_state,
                )
                self.backup = list(new_backup)
                for slot, i in enumerate(self.leaf_indices):
                    local_leaves[i] = merged[slot]
            else:
                new_global, self.outer_opt_state = self._jit_outer_update(
                    averaged, self.outer_opt_state, self.backup
                )
                new_global = [np.asarray(g) for g in new_global]
                self.backup = [np.array(g, copy=True) for g in new_global]
                for slot, i in enumerate(self.leaf_indices):
                    merged = (
                        new_global[slot] * (1.0 - self._alpha)
                        + local_copy[slot] * self._alpha
                    )
                    local_leaves[i] = _to_device_like(
                        merged.astype(local_copy[slot].dtype), local_leaves[i]
                    )
        finally:
            self._manager.allow_state_dict_read()


class DiLoCo:
    """(Streaming) DiLoCo over the fault-tolerant replica axis.

    **The state is updated in place.** Every device program that replaces
    state is given the state it replaces (``jax.jit`` donation): the inner
    step its leaves and inner optimizer state, the quantized outer step the
    fragment's local leaves and outer optimizer state. So there is one copy
    of (params, inner state) on the device, not three, and the host runs a
    step ahead of the device as in the plain train step. What follows:

    - an array read from :attr:`params` (or ``inner_opt_state``) is valid
      until the next :meth:`step` / ``make_step_fn`` call and is DELETED by
      it, as with any donated ``jax.jit``. Read :attr:`params` afresh after
      every step; ``jnp.copy`` what must outlive one;
    - nothing is ever rolled back to the old inner state: a failed fragment
      sync resets the fragment to its backup, a separate tree, by a device
      copy of that one fragment;
    - a capture of the registered state (heal donor, publisher, checkpoint:
      whatever calls the manager's state-dict functions) is a device copy
      made under the read lock, not a reference: one more copy of the state
      in HBM for as long as the caller holds the capture, nothing otherwise.
      ``tpuft_state_snapshot_copies_total`` and
      ``tpuft_state_snapshot_copy_bytes_total`` (label ``key``: the
      registered key) count every such copy and the failed sync's; a
      steady window without heals reads 0. A joiner healed at quorum step
      S still receives the donor's state as of S, bitwise, whatever the
      donor has stepped since.

    Args:
        manager: must use synchronous quorum (``use_async_quorum=False``).
        inner_tx / outer_tx: optax transforms for the local and global steps.
            ``outer_tx`` may be a list, one per fragment. The canonical outer
            optimizer is SGD with Nesterov momentum.
        params: initial parameters. The live leaves are device COPIES of
            them, made here: the caller's arrays are never donated. The
            quantized pipeline's device backups ARE the caller's arrays
            until each fragment's first committed sync replaces them (they
            are read, never given away): leave them alive and do not
            donate them elsewhere. The host pipeline copies them to numpy.
        sync_every: inner steps per full round of fragment syncs; must be a
            multiple of ``n_fragments``.
        n_fragments: number of streaming fragments (leaf-partitioned).
        fragment_fn: optional override partitioning flattened leaf indices
            into fragments; defaults to contiguous chunks.
        fragment_sync_delay: inner steps between a fragment's allreduce
            launch and its blocking sync (tau in the Streaming DiLoCo paper).
        fragment_update_alpha: local/global mix after a sync (0 = take the
            global params, 1 = keep local).
        should_quantize: quantize the outer-sync wire (fp8 allreduce).
            ``None`` (the default) auto-resolves from the WAN topology
            map: a fleet spanning >1 region quantizes its outer syncs
            (they are the traffic that crosses the expensive inter-region
            links — per-step DDP stays intra-region by construction),
            a single-region or topology-less fleet keeps the full-
            precision wire, exactly the pre-topology default. The split
            comes from the same netem region map as everything else and
            NEVER becomes a jax Mesh axis — membership changes must not
            recompile.
    """

    def __init__(
        self,
        manager: Manager,
        inner_tx: Any,
        outer_tx: Any,
        params: Any,
        sync_every: int,
        n_fragments: int = 1,
        fragment_fn: Optional[Callable[[int], List[List[int]]]] = None,
        should_quantize: Optional[bool] = None,
        fragment_sync_delay: int = 0,
        fragment_update_alpha: float = 0.0,
    ) -> None:
        if manager._use_async_quorum:
            raise ValueError(
                "DiLoCo requires synchronous quorum: construct the Manager "
                "with use_async_quorum=False"
            )
        if sync_every < n_fragments:
            raise ValueError("Only 1 fragment can be synchronized at a time")
        if sync_every % n_fragments != 0:
            raise ValueError("sync_every must be a multiple of n_fragments")
        self._sync_every = sync_every // n_fragments
        if fragment_sync_delay >= self._sync_every:
            raise ValueError("Fragment must be synced before it is reduced again")
        if not 0.0 <= fragment_update_alpha <= 1.0:
            raise ValueError("fragment_update_alpha must be between 0 and 1")

        if should_quantize is None:
            should_quantize = cross_region_fleet()
            if should_quantize:
                logger.info(
                    "DiLoCo: WAN topology spans multiple regions; outer "
                    "syncs ride the quantized wire (pass "
                    "should_quantize=False to override)"
                )

        self._manager = manager
        self._inner_tx = inner_tx
        self._fragment_sync_delay = fragment_sync_delay
        self._local_step = 0
        # Inner steps since construction: the number of the root span
        # (the manager's step counts committed syncs, not inner steps).
        self._inner_steps = 0

        leaves, self._treedef = jax.tree_util.tree_flatten(params)
        # The inner step deletes the leaves and the inner state it replaces
        # (donation), so both are this object's own buffers from the start:
        # the leaves are copies of the caller's arrays, the inner state is
        # made from the copies. The caller's arrays are never donated; the
        # device backups hold them as they are.
        self._leaves = _device_copy(list(leaves))
        self.inner_opt_state = inner_tx.init(self.params)
        manager.register_state_dict_fn(
            "diloco_inner", self._load_inner, self._save_inner
        )

        from torchft_tpu.optim import make_jit_update

        # One fused dispatch per inner step; everything else in the inner
        # loop is pure python bookkeeping.
        self._jit_update = make_jit_update(inner_tx, donate_state=True)

        if fragment_fn is not None:
            partitions = fragment_fn(len(self._leaves))
        else:
            # Contiguous leaf chunks (the analogue of layer-group fragments).
            partitions = [
                [int(j) for j in part]
                for part in np.array_split(np.arange(len(self._leaves)), n_fragments)
            ]
        assert len(partitions) == n_fragments
        outer_txs = outer_tx if isinstance(outer_tx, list) else [outer_tx] * n_fragments
        assert len(outer_txs) == n_fragments
        self._fragments = [
            _Fragment(
                manager,
                i,
                part,
                outer_txs[i],
                [leaves[j] for j in part],
                should_quantize,
                fragment_update_alpha,
            )
            for i, part in enumerate(partitions)
        ]

    # -- state -------------------------------------------------------------

    @property
    def params(self) -> Any:
        """The live parameters. The arrays are valid until the next inner
        step, which deletes them (the update is in place): read them
        afresh after every step, and copy what must outlive one."""
        return jax.tree_util.tree_unflatten(self._treedef, self._leaves)

    def _save_inner(self) -> Dict[str, Any]:
        # Called under the state-dict read lock; the caller stages the
        # result after it has released the lock, while inner steps go on
        # and delete the arrays they replace. So the capture is a device
        # copy, dispatched here and therefore ahead of the next step.
        return _snapshot(
            "diloco_inner",
            {"leaves": list(self._leaves), "opt_state": self.inner_opt_state},
        )

    # tpuft: allow(lock-discipline): heal apply — runs under the state-dict writer taken by Manager._apply_pending_state_dict
    def _load_inner(self, state: Dict[str, Any]) -> None:
        # Restore onto the existing leaves' shardings (see
        # _restore_leaf_like): a healed joiner must end up with the same
        # partitioning the donor computes with, or their jitted programs
        # diverge by an ulp. Multi-host donor captures (ShardedLeaf)
        # reassemble against the current leaves' shardings. Every restored
        # leaf comes through the host into a buffer of its own, so the
        # leaves share none with the restored backups (_Fragment._load_state)
        # and the next inner step may delete them.
        old = self._leaves
        new = state["leaves"]
        if len(old) != len(new):
            raise ValueError(
                f"healed inner state has {len(new)} leaves, expected "
                f"{len(old)}: donor/joiner models must match"
            )
        self._leaves = [
            _restore_leaf_like(x, like, device=True) for x, like in zip(new, old)
        ]
        self.inner_opt_state = _restore_like(
            state["opt_state"], self.inner_opt_state, device=True
        )

    def _current_fragment(self) -> int:
        """All replicas must reduce the same fragment per round; keyed by the
        committed manager step (reference :739-744)."""
        return self._manager.current_step() % len(self._fragments)

    # -- step --------------------------------------------------------------

    def step(self, grads: Any) -> bool:
        """One inner step; drives the fragment prepare/sync schedule.
        Returns whether a fragment sync committed this step."""
        with self._step_span():
            # Write-lock the inner mutation (reference step pre/post hooks).
            self._manager.disallow_state_dict_read()
            try:
                with self._dispatch_span():
                    new_params, self.inner_opt_state = self._jit_update(
                        grads, self.inner_opt_state, self.params
                    )
                self._leaves = list(jax.tree_util.tree_flatten(new_params)[0])
            finally:
                self._manager.allow_state_dict_read()
            return self._after_inner_step()

    def _dispatch_span(self) -> Any:
        """The inner step's own dispatch, apart from the sync schedule behind
        it: where the runtime holds the host (a chip near full, a queue of
        programs), the device's gap lands here and not under a sync."""
        return tracing.phase("inner_dispatch", _trace_of(self._manager))

    def _step_span(self) -> Any:
        """The root span of one inner step (``tpuft::local_sgd::step``,
        numbered by the inner step): a fragment sync's spans open under it,
        and it takes every gap of the device that none of them covers."""
        self._inner_steps += 1
        return tracing.phase(
            "local_sgd_step", _trace_of(self._manager),
            inner_step=self._inner_steps - 1,
        )

    def make_step_fn(self, loss_fn: Callable[..., Any]) -> Callable[..., Any]:
        """Fuses loss/grad + inner update into ONE jitted dispatch.

        ``loss_fn(params, *batch) -> scalar loss``. Returns
        ``step(*batch) -> (loss, committed)``; the returned callable owns the
        same prepare/sync schedule as :meth:`step`. Halving the dispatch
        count matters on high-latency device links, and XLA fuses the
        backward with the optimizer update (no grad materialization in HBM
        between them)."""
        import optax

        inner_tx = self._inner_tx
        treedef = self._treedef

        def fused(leaves: List[Any], opt_state: Any, *batch: Any):
            params = jax.tree_util.tree_unflatten(treedef, leaves)
            loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
            updates, new_state = inner_tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            return jax.tree_util.tree_flatten(new_params)[0], new_state, loss

        # In place: the leaves and the inner state the step replaces are
        # given to it, as in the plain train step. An inner step is never
        # rolled back (a failed fragment sync goes back to the fragment's
        # backup, another tree), so nothing needs the old state.
        fused_jit = jax.jit(fused, donate_argnums=(0, 1))

        def step(*batch: Any):
            with self._step_span():
                self._manager.disallow_state_dict_read()
                try:
                    with self._dispatch_span():
                        new_leaves, self.inner_opt_state, loss = fused_jit(
                            self._leaves, self.inner_opt_state, *batch
                        )
                    self._leaves = list(new_leaves)
                finally:
                    self._manager.allow_state_dict_read()
                return loss, self._after_inner_step()

        return step

    def _after_inner_step(self) -> bool:
        """Shared fragment prepare/sync schedule (runs after every inner
        update); returns whether a fragment sync committed."""
        self._local_step += 1
        committed = False

        if self._local_step == self._sync_every - self._fragment_sync_delay:
            self._manager.start_quorum()
            fragment = self._current_fragment()
            logger.info("Preparing fragment=%d step=%d", fragment, self._local_step)
            self._fragments[fragment].prepare_sync(self._leaves)

        if self._local_step == self._sync_every:
            fragment = self._current_fragment()
            logger.info(
                "Syncing fragment=%d step=%d manager_step=%d",
                fragment,
                self._local_step,
                self._manager.current_step(),
            )
            committed = self._fragments[fragment].perform_sync(self._leaves)
            self._local_step = 0
        return committed
