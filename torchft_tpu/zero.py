"""ZeRO plane: FT-aware cross-replica sharding of the optimizer update.

Every replica in plain FT-DDP redundantly holds full params + full
optimizer state and applies the full update. This module shards the
*update* across the replica axis ("Automatic Cross-Replica Sharding of
Weight Update in Data-Parallel Training", PAPERS.md) without ever putting
that axis in the jax Mesh — membership changes must never recompile XLA
programs (the architecture invariant R5 enforces statically). Per step:

1. gradients pack into ONE flat f32 buffer (one jitted dispatch, one d2h
   fetch) and reduce across replica groups over the FT collectives —
   ``pg.reduce_scatter`` when the shard layout permits, allreduce+slice
   otherwise (bitwise-identical bytes either way on the TCP backend);
2. each live replica runs the jitted optax update (ONE
   ``make_jit_shard_update`` dispatch) on only the shards it owns — the
   owner holds the f32 *master* range plus that range's optax state;
3. the updated master ranges allgather and every replica unpacks the same
   flat buffer into model-dtype params — **bitwise identity across
   replica groups holds by construction** (each range's bytes are
   computed exactly once, by its owner, and broadcast).

Gradient math stays world-size independent: SUM + divide by the live
participant count; non-participants contribute zeros and own nothing.
With N participants each replica persists ~1/N of (masters + moments),
and the heal plane ships ~1/N (or, with the default skip-all heal
policy, none) of the optimizer bytes a full checkpoint would.

**Elasticity** is the hard part: shard ownership is a pure function of
(number of shards, live cohort size, step) — ``shard_assignment`` —
recomputed whenever the quorum's shape changes. Re-balance is lazy and
wire-lockstep: at the first step of a new assignment every PG member
exchanges tiny shard *manifests* (ids + the committed step each shard
state corresponds to), derives the same deterministic transfer plan, and
moves **only the shard states whose ownership changed** point-to-point.
A shard whose holder died is reconstructed deterministically: its master
range re-packs from the (replicated, committed) params — exact for f32
models — and its moments restart from ``tx.init`` (counted in
``tpuft_zero_shard_reinits_total``; the documented bounded-staleness
envelope). Stale holders (a joiner that kept shards across a death) are
fenced by the manifest step tag and never chosen as a source.

Heals are shard-addressable end to end: the optimizer registers each
shard's state under a ``heal_part:zero_shard_<s>`` key, the checkpoint
transport stages each part as its own CRC'd chunk, and the joiner skips
the parts it can re-balance from survivors (``TPUFT_ZERO_HEAL_SHARDS``;
the skipped bytes land in ``tpuft_zero_heal_bytes_saved_total``).

**Quantized shard wire** (``$TPUFT_ZERO_CODEC``, default fp32): the flat
f32 plane encodes to fp8/int8/int4 on both bulk legs — the grad reduce
rides the fused dequant-reduce-requant allreduce
(:func:`torchft_tpu.parallel.collectives.allreduce_quantized`) and the
master allgather ships packed ``[tag||scales||payload]`` ranges that
EVERY replica (owners included) dequantizes identically, so bitwise
replica identity survives by construction while the replica-axis bytes
drop ~4x (8-bit) / ~8x (int4). Masters stay f32 on their owners; the
env must agree fleet-wide (the wire tag turns disagreement into a hard
error). See docs/zero.md.

Composes with all three commit orderings (strict / overlapped /
pipelined — rollback snapshots are whole :class:`ZeroState` objects,
rebound never mutated), with DiLoCo/LocalSGD manager registration
(distinct state-dict keys), and with the lone-replica identity skip
(N=1 owns every shard and touches no wire). See docs/zero.md.
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from torchft_tpu import metrics, tracing, wire_codec
from torchft_tpu.checkpointing.transport import HEAL_PART_PREFIX
from torchft_tpu.ops import quantization as q
from torchft_tpu.parallel.collectives import allreduce_quantized
from torchft_tpu.manager import Manager
from torchft_tpu.optim import (
    Optimizer,
    _as_device_tree,
    _count_dispatch,
    _replica_labels,
    _sync_device,
    _trace_of,
    make_jit_shard_update,
)
from torchft_tpu.parallel.process_group import ReduceOp

logger = logging.getLogger(__name__)

__all__ = [
    "ShardSpec",
    "ZeroState",
    "ZeroOptimizer",
    "shard_assignment",
    "shard_part_name",
    "plan_shard_moves",
]

ENV_ZERO = "TPUFT_ZERO"
ENV_ZERO_SHARDS = "TPUFT_ZERO_SHARDS"
ENV_ZERO_REBALANCE = "TPUFT_ZERO_REBALANCE"
ENV_ZERO_HEAL_SHARDS = "TPUFT_ZERO_HEAL_SHARDS"

DEFAULT_NUM_SHARDS = 8


def shard_part_name(shard: int) -> str:
    """The heal-part key for one shard's state (the checkpoint transport
    stages each such part as its own independently-fetchable chunk)."""
    return f"{HEAL_PART_PREFIX}zero_shard_{shard}"


def shard_assignment(
    num_shards: int,
    num_participants: int,
    step: int = 0,
    policy: Optional[str] = None,
) -> np.ndarray:
    """Owner (participant rank) per shard: a pure function of the sorted
    quorum cohort's size and the step — every replica computes the same
    array with NO communication (the unit tests pin determinism).

    Policies (``$TPUFT_ZERO_REBALANCE``):

    - ``block`` (default): contiguous blocks of shards per rank
      (``np.array_split`` semantics) — block layouts make the
      ``pg.reduce_scatter`` fast path possible and minimize the number of
      ownership moves when the cohort shrinks or grows by one.
    - ``strided``: ``owner[s] = s % N`` — spreads hot shards when shard
      sizes are skewed.

    ``step`` is part of the signature so a step-keyed rotation policy
    stays a pure function of (cohort, step); the shipped policies are
    deliberately step-invariant (rotation would churn shard state every
    step for no FT benefit).
    """
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    n = max(1, int(num_participants))
    policy = policy or os.environ.get(ENV_ZERO_REBALANCE, "block")
    if policy == "block":
        owners = np.empty(num_shards, dtype=np.int64)
        for rank, block in enumerate(
            np.array_split(np.arange(num_shards), min(n, num_shards))
        ):
            owners[block] = rank
        return owners
    if policy == "strided":
        return np.arange(num_shards, dtype=np.int64) % n
    raise ValueError(
        f"{ENV_ZERO_REBALANCE} must be 'block' or 'strided', got {policy!r}"
    )


@dataclass(frozen=True)
class _LeafMeta:
    shape: Tuple[int, ...]
    dtype: Any
    size: int
    offset: int


class ShardSpec:
    """The flat-buffer shard geometry over one params pytree.

    Leaves concatenate (flatten order — deterministic across replicas for
    identical models, the frozen-bucket invariant) into one conceptual f32
    buffer of ``total`` elements, zero-padded to ``num_shards`` equal
    ranges of ``shard_len`` elements each. Equal ranges keep the
    re-balance wire format and the jitted shard update shape-stable no
    matter which shards a replica owns. The replica axis never appears in
    any jax Mesh: sharding is plain python range bookkeeping + host
    collectives, so membership changes recompile nothing.
    """

    def __init__(self, params: Any, num_shards: int) -> None:
        leaves, treedef = jax.tree_util.tree_flatten(params)
        if not leaves:
            raise ValueError("ShardSpec needs at least one parameter leaf")
        self.treedef = treedef
        metas: List[_LeafMeta] = []
        offset = 0
        for leaf in leaves:
            if not hasattr(leaf, "shape"):
                raise ValueError(
                    "ZeRO shards array leaves only; found a non-array param "
                    f"leaf of type {type(leaf).__name__}"
                )
            size = int(np.prod(leaf.shape)) if leaf.shape else 1
            metas.append(
                _LeafMeta(tuple(leaf.shape), np.dtype(leaf.dtype), size, offset)
            )
            offset += size
        self.leaf_metas = metas
        self.total = offset
        self.num_shards = int(num_shards)
        self.shard_len = -(-self.total // self.num_shards)  # ceil
        self.padded = self.shard_len * self.num_shards

        import jax.numpy as jnp

        def _pack(tree: Any) -> Any:
            flat_leaves = jax.tree_util.tree_leaves(tree)
            flat = jnp.concatenate(
                [leaf.astype(jnp.float32).reshape(-1) for leaf in flat_leaves]
            )
            return jnp.pad(flat, (0, self.padded - self.total))

        def _unpack(flat: Any) -> Any:
            outs = []
            for meta in metas:
                chunk = jax.lax.dynamic_slice_in_dim(flat, meta.offset, meta.size)
                outs.append(chunk.reshape(meta.shape).astype(meta.dtype))
            return jax.tree_util.tree_unflatten(treedef, outs)

        self.pack = jax.jit(_pack)
        self.unpack = jax.jit(_unpack)

    def shard_range(self, shard: int) -> Tuple[int, int]:
        start = shard * self.shard_len
        return start, start + self.shard_len

    def shard_view(self, flat: np.ndarray, shard: int) -> np.ndarray:
        start, stop = self.shard_range(shard)
        return flat[start:stop]

    def describe(self) -> Dict[str, Any]:
        return {
            "num_shards": self.num_shards,
            "total": self.total,
            "shard_len": self.shard_len,
            "num_leaves": len(self.leaf_metas),
        }


@dataclass(frozen=True)
class _ShardState:
    """One shard's persisted optimizer state: the f32 master range plus
    that range's optax state, tagged with the committed step it
    corresponds to (the re-balance manifest's freshness fence)."""

    step: int
    master: Any  # (shard_len,) f32
    opt: Any  # optax state pytree for this range


@dataclass(frozen=True)
class ZeroState:
    """The sharded optimizer state one replica persists. Immutable —
    updates build a new instance, so the commit pipeline's rollback
    snapshots and the donor's checkpoint captures are plain reference
    rebinds (never torn, never mutated in place)."""

    spec: ShardSpec
    held: Dict[int, _ShardState] = field(default_factory=dict)
    step: int = 0
    # The (quorum_id, pg_rank, pg_world, participating_rank,
    # num_participants) this state's ownership was balanced for; None
    # forces a re-balance at the next step (fresh construction, heal).
    balance_key: Optional[Tuple] = None
    ever_balanced: bool = False
    # Proven at the last manifest exchange: participant rank r sits at PG
    # rank r for every participant. This is the evidence gate for the
    # pg.reduce_scatter fast path — chunk-by-PG-rank only routes ranges
    # to their owners when the two rank spaces coincide, and assuming it
    # without proof would silently corrupt the update on a permuted
    # cohort.
    ranks_identical: bool = False

    def owned_bytes(self) -> int:
        total = 0
        for state in self.held.values():
            total += int(np.asarray(state.master).nbytes)
            for leaf in jax.tree_util.tree_leaves(state.opt):
                total += int(np.asarray(leaf).nbytes)
        return total


def plan_shard_moves(
    manifests: Sequence[Tuple[int, int, Sequence[Tuple[int, int]]]],
    owners: np.ndarray,
    participant_pg_ranks: Dict[int, int],
    current_step: int,
) -> Tuple[List[Tuple[int, int, int]], List[int]]:
    """The deterministic re-balance transfer plan every rank derives from
    the same manifest exchange (pure function — no further negotiation).

    ``manifests``: per PG member ``(pg_rank, state_step, [(shard_id,
    shard_step), ...])``. ``owners``: participant rank per shard
    (:func:`shard_assignment`). ``participant_pg_ranks``: participant
    rank -> PG rank (derived from the same manifests by the caller).

    Returns ``(moves, lost)``: ``moves`` is ``[(shard, src_pg_rank,
    dst_pg_rank), ...]`` sorted by shard id — ONLY shards whose
    current-step holder is not their new owner; ``lost`` is the shard ids
    no live member holds at ``current_step`` (reconstructed by their new
    owner, counted as reinits once the plane has balanced before).
    Holders whose shard tag is behind ``current_step`` are stale (a
    rejoined replica that kept state across a death) and are never chosen
    as a source.
    """
    holders: Dict[int, List[int]] = {}
    for pg_rank, _state_step, entries in manifests:
        for shard_id, shard_step in entries:
            if shard_step == current_step:
                holders.setdefault(int(shard_id), []).append(int(pg_rank))
    moves: List[Tuple[int, int, int]] = []
    lost: List[int] = []
    for shard in range(len(owners)):
        owner_pg = participant_pg_ranks.get(int(owners[shard]))
        if owner_pg is None:
            # The owner is not a live PG member this round (can only
            # happen transiently while the quorum settles); nobody
            # fetches the shard — its holder keeps it for the next plan.
            continue
        ranked = sorted(holders.get(shard, []))
        if not ranked:
            lost.append(shard)
            continue
        src = ranked[0]
        if src != owner_pg:
            moves.append((shard, src, owner_pg))
    return moves, lost


class ZeroOptimizer(Optimizer):
    """:class:`~torchft_tpu.optim.Optimizer` with the update sharded
    across the replica axis (see module docstring for the protocol).

    API differences from the base class, both deliberate:

    - :meth:`step` takes the **local** (unaveraged) gradient pytree — the
      cross-replica reduction IS the reduce-scatter half of the sharded
      update, so averaging first (``ft_allreduce_gradients``) would pay
      the wire twice. ``make_step_fn`` handles this transparently.
    - ``opt_state`` is a :class:`ZeroState` (opaque to the commit
      pipeline's snapshot/rollback machinery, which only rebinds refs).

    ``num_shards`` is fixed for the life of the job (and must match
    across replicas — it keys the shard-addressable heal format); choose
    a value divisible by the cohort sizes you expect so the
    ``pg.reduce_scatter`` fast path engages (``$TPUFT_ZERO_SHARDS``,
    default 8, covers 1/2/4/8). The sharded wire quantizes through
    ``$TPUFT_ZERO_CODEC`` (fp8/int8/int4; fleet-wide agreement like
    ``TPUFT_WIRE_DTYPE``) — NOT through the per-call ``should_quantize``
    flag, which remains a no-op warning here: the codec is a wire
    format, not a step flag, because every replica must decode the same
    allgather bytes for bitwise identity to survive.
    """

    def __init__(
        self,
        manager: Manager,
        tx: Any,
        params: Any,
        num_shards: Optional[int] = None,
        register_key: str = "zero",
    ) -> None:
        if num_shards is None:
            num_shards = int(
                os.environ.get(ENV_ZERO_SHARDS, str(DEFAULT_NUM_SHARDS))
            )
        self._num_shards = int(num_shards)
        self._spec: Optional[ShardSpec] = None  # built inside _init_state
        super().__init__(manager, tx, params, register_key=register_key)
        self._jit_shard_update = make_jit_shard_update(tx)
        import jax.numpy as jnp

        # Shared template for every shard's optax state: equal ranges mean
        # ONE structure (treedef + leaf shapes) describes all shards — the
        # re-balance recv templates and the heal payloads lean on this.
        self._opt_template = tx.init(
            jnp.zeros((self._spec.shard_len,), jnp.float32)
        )
        self._opt_treedef = jax.tree_util.tree_structure(self._opt_template)
        self._opt_leaf_templates = [
            np.zeros(np.shape(leaf), dtype=np.asarray(leaf).dtype)
            for leaf in jax.tree_util.tree_leaves(self._opt_template)
        ]
        heal_policy = os.environ.get(ENV_ZERO_HEAL_SHARDS, "skip")
        if heal_policy not in ("skip", "fetch"):
            raise ValueError(
                f"{ENV_ZERO_HEAL_SHARDS} must be 'skip' or 'fetch', "
                f"got {heal_policy!r}"
            )
        if heal_policy == "skip":
            # A joiner re-balances its shards from survivors over the PG,
            # so the heal stream need not carry the donor's shard states
            # at all: skip those parts (the transport pins the saved bytes
            # in tpuft_zero_heal_bytes_saved_total).
            manager.register_heal_parts_filter(
                lambda: {shard_part_name(s) for s in range(self._num_shards)}
            )
        metrics.set_gauge(
            "tpuft_zero_num_shards", self._num_shards, **_replica_labels(manager)
        )

    # ------------------------------------------------------------------
    # state construction / registration
    # ------------------------------------------------------------------

    def _init_state(self, tx: Any, params: Any) -> ZeroState:
        self._spec = ShardSpec(params, self._num_shards)
        # Held shards start EMPTY: ownership is unknown until the first
        # quorum, and bootstrapping an owned shard (master re-packed from
        # the replicated params, moments from tx.init) is deterministic —
        # identical on every replica at step 0 by the init_sync contract.
        return ZeroState(spec=self._spec, held={}, step=0, balance_key=None)

    def _state_dict(self) -> Any:
        state: ZeroState = self.opt_state
        shards: Dict[str, Any] = {}
        for s in range(self._num_shards):
            held = state.held.get(s)
            if held is None:
                shards[shard_part_name(s)] = None
            else:
                shards[shard_part_name(s)] = {
                    "step": held.step,
                    "master": held.master,
                    "opt": held.opt,
                }
        return {
            "params": self.params,
            "zero": {"num_shards": self._num_shards, "step": state.step},
            "shards": shards,
        }

    # tpuft: allow(lock-discipline): heal apply — the registered load fns run under the state-dict writer taken by Manager._apply_pending_state_dict
    def _load_state_dict(self, state: Any) -> None:
        import jax.numpy as jnp

        meta = state["zero"]
        if int(meta["num_shards"]) != self._num_shards:
            raise ValueError(
                f"donor runs {meta['num_shards']} ZeRO shards, this replica "
                f"runs {self._num_shards}: num_shards must match fleet-wide "
                f"(${ENV_ZERO_SHARDS})"
            )
        self.params = _as_device_tree(state["params"], like=self.params)
        held: Dict[int, _ShardState] = {}
        for s in range(self._num_shards):
            payload = state["shards"].get(shard_part_name(s))
            if payload is None or payload.get("master") is None:
                # Not held by the donor, or a skip_parts heal substituted
                # None for the part's leaves — either way the shard state
                # arrives through the re-balance exchange instead.
                continue
            held[s] = _ShardState(
                step=int(payload["step"]),
                master=jnp.asarray(np.asarray(payload["master"])),
                opt=jax.tree_util.tree_map(
                    lambda x: jnp.asarray(np.asarray(x)), payload["opt"]
                ),
            )
        self.opt_state = ZeroState(
            spec=self._spec,
            held=held,
            step=int(meta["step"]),
            balance_key=None,  # force a re-balance under the new quorum
            ever_balanced=self.opt_state.ever_balanced,
        )
        self._note_state_structure()
        self._heal_count += 1

    # ------------------------------------------------------------------
    # ownership / re-balance
    # ------------------------------------------------------------------

    def _participation(self) -> Tuple[int, int, Optional[int], int]:
        """(pg_rank, pg_world, participating_rank, num_participants) for
        the current quorum (None participating rank = healing/spare)."""
        manager = self.manager
        pg = manager._pg
        return (
            pg.rank(),
            max(1, pg.size()),
            manager.participating_rank() if manager.is_participating() else None,
            max(1, manager.num_participants()),
        )

    def _owned_shards(self) -> List[int]:
        _pg_rank, _pg_world, my_prank, nparts = self._participation()
        if my_prank is None:
            return []
        owners = shard_assignment(
            self._num_shards, nparts, self.manager.current_step()
        )
        return [s for s in range(self._num_shards) if owners[s] == my_prank]

    def _bootstrap_shard(self, shard: int, flat_params: Any) -> _ShardState:
        import jax.numpy as jnp

        start, _stop = self._spec.shard_range(shard)
        master = jax.lax.dynamic_slice_in_dim(
            flat_params, start, self._spec.shard_len
        )
        return _ShardState(
            step=self.opt_state.step,
            master=master,
            opt=self.tx.init(jnp.zeros((self._spec.shard_len,), jnp.float32)),
        )

    def _maybe_rebalance(self) -> None:
        """Re-balances shard ownership when the quorum's shape changed
        since the last step. Runs on the train-loop thread, in wire
        lockstep with every other PG member (all ranks observe the same
        quorum and reach this seam at the same step). Exchanges only the
        shard states whose ownership moved; lost shards (dead holder)
        reconstruct deterministically."""
        state: ZeroState = self.opt_state
        pg_rank, pg_world, my_prank, nparts = self._participation()
        key = (
            self.manager._quorum_id,
            pg_rank,
            pg_world,
            my_prank,
            nparts,
        )
        if state.balance_key == key:
            return
        owners = shard_assignment(
            self._num_shards, nparts, self.manager.current_step()
        )
        owned = (
            [s for s in range(self._num_shards) if owners[s] == my_prank]
            if my_prank is not None
            else []
        )
        labels = _replica_labels(self.manager)
        if pg_world <= 1:
            # Alone on the wire: no exchange partner. Keep fresh held
            # shards, bootstrap the rest from the replicated params.
            with tracing.phase(
                "zero_rebalance", _trace_of(self.manager),
                owned=len(owned), wire=False,
            ):
                self._adopt_rebalanced(
                    state, owned, {}, key, labels, ranks_identical=True
                )
            return
        try:
            with tracing.phase(
                "zero_rebalance", _trace_of(self.manager),
                owned=len(owned), wire=True,
            ):
                self._rebalance_over_wire(
                    state, owners, owned, pg_rank, key, labels
                )
        except Exception as e:  # noqa: BLE001 — poison the step, never raise
            # Comm-layer errors funnel into report_error: the step will
            # not commit and the next quorum reconfigures the wire; the
            # pre-balance state stays live (balance_key unchanged, so the
            # next healthy step retries the exchange).
            logger.exception("ZeRO re-balance failed: %s", e)
            self.manager.report_error(
                e if isinstance(e, Exception) else RuntimeError(str(e))
            )

    def _rebalance_over_wire(
        self,
        state: ZeroState,
        owners: np.ndarray,
        owned: List[int],
        pg_rank: int,
        key: Tuple,
        labels: Dict[str, Any],
    ) -> None:
        pg = self.manager._pg
        _pg_rank, _pg_world, my_prank, _nparts = self._participation()
        # Manifest: [pg_rank, participating_rank(-1), state_step,
        # (shard_id, shard_step) * held]. Tiny — the whole exchange is a
        # few int64s per member.
        entries = sorted(state.held.items())
        manifest = np.array(
            [pg_rank, -1 if my_prank is None else my_prank, state.step]
            + [v for s, sh in entries for v in (s, sh.step)],
            dtype=np.int64,
        )
        gathered = pg.allgather([manifest]).wait()
        manifests: List[Tuple[int, int, Sequence[Tuple[int, int]]]] = []
        participant_pg_ranks: Dict[int, int] = {}
        current_step = state.step
        for arrays in gathered:
            row = np.asarray(arrays[0], dtype=np.int64)
            member_pg, member_prank, member_step = (
                int(row[0]),
                int(row[1]),
                int(row[2]),
            )
            current_step = max(current_step, member_step)
            if member_prank >= 0:
                participant_pg_ranks[member_prank] = member_pg
            pairs = [
                (int(row[i]), int(row[i + 1])) for i in range(3, len(row), 2)
            ]
            manifests.append((member_pg, member_step, pairs))
        moves, _lost = plan_shard_moves(
            manifests, owners, participant_pg_ranks, current_step
        )
        nparts = self._participation()[3]
        ranks_identical = len(participant_pg_ranks) == nparts and all(
            prank == pgr for prank, pgr in participant_pg_ranks.items()
        )
        # Deterministic global order (sorted by shard id) so every rank
        # submits its role ops in the same sequence — the same pairwise
        # progress argument the alltoall ordering makes.
        moved_in: Dict[int, _ShardState] = {}
        for shard, src, dst in moves:
            if src == pg_rank:
                held = state.held[shard]
                arrays = [np.asarray(held.master)] + [
                    np.asarray(leaf)
                    for leaf in jax.tree_util.tree_leaves(held.opt)
                ]
                pg.send(arrays, dst=dst, tag=shard).wait()
            elif dst == pg_rank:
                templates = [
                    np.zeros((self._spec.shard_len,), np.float32)
                ] + [np.array(t) for t in self._opt_leaf_templates]
                received = pg.recv(templates, src=src, tag=shard).wait()
                import jax.numpy as jnp

                moved_in[shard] = _ShardState(
                    step=current_step,
                    master=jnp.asarray(received[0]),
                    opt=jax.tree_util.tree_unflatten(
                        self._opt_treedef,
                        [jnp.asarray(a) for a in received[1:]],
                    ),
                )
                nbytes = sum(int(a.nbytes) for a in received)
                metrics.inc("tpuft_zero_shards_moved_total", **labels)
                metrics.inc("tpuft_zero_rebalance_bytes_total", nbytes, **labels)
        self._adopt_rebalanced(
            state, owned, moved_in, key, labels, ranks_identical=ranks_identical
        )

    def _adopt_rebalanced(
        self,
        state: ZeroState,
        owned: List[int],
        moved_in: Dict[int, _ShardState],
        key: Tuple,
        labels: Dict[str, Any],
        ranks_identical: bool,
    ) -> None:
        flat_params = None
        held: Dict[int, _ShardState] = {}
        for s in owned:
            if s in moved_in:
                held[s] = moved_in[s]
            elif s in state.held and state.held[s].step >= state.step:
                held[s] = state.held[s]
            else:
                if flat_params is None:
                    flat_params = self._spec.pack(self.params)
                held[s] = self._bootstrap_shard(s, flat_params)
                if state.ever_balanced:
                    # The shard was live somewhere before this membership
                    # change and its holder died with it: masters re-pack
                    # exactly from the replicated committed params;
                    # moments restart (the bounded-staleness envelope
                    # docs/zero.md documents).
                    metrics.inc("tpuft_zero_shard_reinits_total", **labels)
                else:
                    metrics.inc("tpuft_zero_shard_bootstraps_total", **labels)
        self.manager.disallow_state_dict_read()
        try:
            self.opt_state = replace(
                state,
                held=held,
                balance_key=key,
                ever_balanced=True,
                ranks_identical=ranks_identical,
            )
        finally:
            self.manager.allow_state_dict_read()
        self._note_state_structure()  # the held shards changed
        metrics.inc("tpuft_zero_rebalance_total", **labels)
        metrics.set_gauge("tpuft_zero_owned_shards", len(held), **labels)

    # ------------------------------------------------------------------
    # the sharded step
    # ------------------------------------------------------------------

    def _reduce_grad_shards(
        self, grads: Any, pre_state: ZeroState
    ) -> Optional[Dict[int, np.ndarray]]:
        """Packs ``grads`` into the flat f32 plane and reduces it across
        participating replicas (SUM / live participant count — world-size
        independent; non-participants contribute zeros). Returns the
        averaged ranges for the shards this replica holds (what the
        update consumes), or None when the wire errored (the step is
        already poisoned and will not commit).

        Takes ``pg.reduce_scatter`` — each rank receives ONLY its owned
        block — when the layout provably permits: every PG member is a
        participant sitting at its participant rank (manifest-proven at
        the last re-balance), the block policy gives every rank the same
        number of contiguous shards, and this replica's held set is
        exactly that block. Anything else (healing members in the PG,
        unequal blocks, strided policy) falls back to allreduce + local
        slice — bitwise-identical bytes on the TCP backend, and still one
        collective."""
        manager = self.manager
        spec = self._spec
        flat = np.asarray(spec.pack(grads), dtype=np.float32)
        ids = sorted(pre_state.held)
        if manager.is_lone_replica():
            return {s: spec.shard_view(flat, s) for s in ids}
        nparts = max(1, manager.num_participants())
        if not manager.is_participating():
            flat = np.zeros_like(flat)
        pg = manager._pg
        metrics.inc(
            "tpuft_zero_reduce_scatter_bytes_total", flat.nbytes,
            **_replica_labels(manager),
        )
        # Quantized shard wire ($TPUFT_ZERO_CODEC, fleet-wide like
        # TPUFT_WIRE_DTYPE): the flat f32 grad plane rides the fused
        # dequant-reduce-requant allreduce at ~1/4 (fp8/int8) or ~1/8
        # (int4) of the f32 bytes. Reduced values feed only the OWNED
        # shards' updates, so cross-replica bitwise identity of the
        # reduction is not required here — it is re-established by the
        # allgather leg, where every replica dequantizes the same
        # encoded master payload.
        codec = wire_codec.zero_codec()
        if codec != "fp32":
            n_blocks = -(-flat.size // q.BLOCK)
            pad_blocks = (-n_blocks) % max(pg.size(), 1)
            post = (n_blocks + pad_blocks) * (4 + q.payload_cols(codec)) + (
                q.WIRE_HEADER_BYTES * pg.size()
            )
            metrics.inc(
                "tpuft_codec_bytes_pre_total", flat.nbytes,
                wire="zero", codec=codec,
            )
            metrics.inc(
                "tpuft_codec_bytes_post_total", int(post),
                wire="zero", codec=codec,
            )
            metrics.set_gauge(
                "tpuft_codec_wire", wire_codec.CODEC_GAUGE_CODES[codec],
                wire="zero",
            )
            tracing.record(
                "codec_wire",
                step=manager.current_step(),
                wire="zero",
                codec=codec,
                pre_bytes=int(flat.nbytes),
                post_bytes=int(post),
            )
            try:
                reduced = np.asarray(
                    allreduce_quantized([flat], ReduceOp.SUM, pg, wire_dtype=codec)
                    .wait()[0]
                )
                reduced = (reduced / nparts).astype(np.float32)
                return {s: spec.shard_view(reduced, s) for s in ids}
            except Exception as e:  # noqa: BLE001 — poison, never raise
                logger.exception("ZeRO quantized grad reduce failed: %s", e)
                manager.report_error(
                    e if isinstance(e, Exception) else RuntimeError(str(e))
                )
                return None
        # Every rank derives the branch from globally-agreed facts (PG
        # size vs participant count, shard divisibility, the proven rank
        # identity from the shared manifest round, the shared codec env)
        # so no rank can enter reduce_scatter while a peer enters
        # allreduce.
        fast = (
            pre_state.ranks_identical
            and pg.size() == nparts
            and self._num_shards % nparts == 0
            and os.environ.get(ENV_ZERO_REBALANCE, "block") == "block"
        )
        try:
            if fast:
                block = self._num_shards // nparts
                work = pg.reduce_scatter(
                    [flat.reshape(nparts, block * spec.shard_len)],
                    ReduceOp.SUM,
                )
                mine = np.asarray(work.wait()[0]).reshape(-1)
                mine = (mine / nparts).astype(np.float32)
                my_prank = manager.participating_rank()
                first = (my_prank or 0) * block
                out: Dict[int, np.ndarray] = {}
                for slot in range(block):
                    shard = first + slot
                    if shard in pre_state.held:
                        out[shard] = mine[
                            slot * spec.shard_len : (slot + 1) * spec.shard_len
                        ]
                return out
            reduced = np.asarray(pg.allreduce([flat], ReduceOp.SUM).wait()[0])
            reduced = (reduced / nparts).astype(np.float32)
            return {s: spec.shard_view(reduced, s) for s in ids}
        except Exception as e:  # noqa: BLE001 — poison, never raise
            logger.exception("ZeRO grad reduce failed: %s", e)
            manager.report_error(
                e if isinstance(e, Exception) else RuntimeError(str(e))
            )
            return None

    def _allgather_masters(
        self, updated: Dict[int, Any]
    ) -> Optional[np.ndarray]:
        """Allgathers the owned updated master ranges; returns the full
        new flat f32 buffer (identical bytes on every replica), or None on
        a wire error. Ranges no live owner covered — only possible
        transiently while a quorum settles — keep their previous values
        (lazily re-packed from the current params; the healthy path never
        pays that extra device fetch)."""
        manager = self.manager
        pg = manager._pg
        spec = self._spec
        ids = sorted(updated)
        # Quantized shard wire: owners encode their updated master ranges
        # and EVERY replica — owners included — dequantizes the same
        # encoded allgather payload through the same deterministic host
        # codec, so params stay bitwise identical across replicas BY
        # CONSTRUCTION (the wire bytes, not each owner's f32 local copy,
        # are the source of truth for params). Masters themselves stay
        # f32 on their owners; only the wire narrows.
        codec = wire_codec.zero_codec()
        shard_blocks = -(-spec.shard_len // q.BLOCK)
        if codec == "fp32":
            payload = [np.array(ids, dtype=np.int64)] + [
                np.asarray(updated[s], dtype=np.float32) for s in ids
            ]
        else:
            payload = [np.array(ids, dtype=np.int64)]
            pre = 0
            for s in ids:
                rng = np.asarray(updated[s], dtype=np.float32)
                pre += rng.nbytes
                payload.append(q.pack_arrays(*q.quantize_blocks(rng, wire=codec)))
            post = sum(int(a.nbytes) for a in payload[1:])
            metrics.inc(
                "tpuft_codec_bytes_pre_total", pre, wire="zero", codec=codec
            )
            metrics.inc(
                "tpuft_codec_bytes_post_total", post, wire="zero", codec=codec
            )
        sent = sum(int(a.nbytes) for a in payload[1:])
        metrics.inc(
            "tpuft_zero_allgather_bytes_total", sent,
            **_replica_labels(manager),
        )
        if manager.is_lone_replica():
            gathered = [payload]
        else:
            try:
                gathered = pg.allgather(payload).wait()
            except Exception as e:  # noqa: BLE001 — poison, never raise
                logger.exception("ZeRO param allgather failed: %s", e)
                manager.report_error(
                    e if isinstance(e, Exception) else RuntimeError(str(e))
                )
                return None
        flat = np.empty(spec.padded, dtype=np.float32)
        covered = np.zeros(spec.num_shards, dtype=bool)
        for arrays in gathered:
            row_ids = np.asarray(arrays[0], dtype=np.int64)
            for slot, shard in enumerate(row_ids):
                start, stop = spec.shard_range(int(shard))
                if codec == "fp32":
                    rng = np.asarray(arrays[1 + slot], np.float32)
                else:
                    # unpack_arrays' embedded format tag asserts the
                    # sender used OUR codec — a cross-rank
                    # TPUFT_ZERO_CODEC disagreement is a hard error,
                    # never a silent misdecode.
                    p, sc = q.unpack_arrays(
                        np.asarray(arrays[1 + slot], np.uint8).reshape(-1),
                        shard_blocks,
                        wire=codec,
                    )
                    rng = q.dequantize_blocks(
                        p, sc, (spec.shard_len,), np.float32
                    )
                flat[start:stop] = rng
                covered[int(shard)] = True
        if not covered.all():
            fallback = np.asarray(spec.pack(self.params), dtype=np.float32)
            for shard in np.flatnonzero(~covered):
                start, stop = spec.shard_range(int(shard))
                flat[start:stop] = fallback[start:stop]
        return flat

    def _zero_speculate(
        self, avg_blocks: Optional[Dict[int, np.ndarray]], pre_state: ZeroState
    ) -> Tuple[Any, Any]:
        """The sharded update + param allgather from the averaged
        gradient ranges of the held shards; returns ``(speculation,
        recompute)`` with the base-class contract (recompute re-derives
        against a state the commit barrier healed)."""
        import jax.numpy as jnp

        spec = self._spec
        if avg_blocks is None:
            # Wire already errored: the commit will fail and the
            # speculation is discarded; hand back the pre-step state so
            # the machinery has something well-formed to (not) adopt.
            return (self.params, pre_state), lambda: (self.params, self.opt_state)

        ids = sorted(avg_blocks)
        new_held: Dict[int, _ShardState] = dict(pre_state.held)
        updated_masters: Dict[int, Any] = {}
        if ids:
            with tracing.phase("update_dispatch", _trace_of(self.manager)):
                new_masters, new_opts = self._jit_shard_update(
                    [jnp.asarray(avg_blocks[s]) for s in ids],
                    [pre_state.held[s].opt for s in ids],
                    [pre_state.held[s].master for s in ids],
                )
            # A shard hands over its gradient range, its master and its
            # optax state, and takes the last two back.
            n_opt = len(self._opt_leaf_templates)
            _count_dispatch(
                len(ids) * (2 + n_opt), len(ids) * (1 + n_opt), donated=False
            )
            for slot, s in enumerate(ids):
                new_held[s] = _ShardState(
                    step=pre_state.step + 1,
                    master=new_masters[slot],
                    opt=new_opts[slot],
                )
                updated_masters[s] = new_masters[slot]
        new_flat = self._allgather_masters(updated_masters)
        if new_flat is None:
            return (self.params, pre_state), lambda: (self.params, self.opt_state)
        new_params = spec.unpack(jnp.asarray(new_flat))
        spec_state = replace(pre_state, held=new_held, step=pre_state.step + 1)

        def recompute() -> Tuple[Any, Any]:
            # The barrier healed this replica mid-step: the allgathered
            # flat buffer is the committed truth for params (owners
            # computed it from the same averaged gradients), and the
            # healed state supplies shard states for anything the heal
            # restored; my own owned shards keep the updates computed
            # above (derived from the pre-heal committed state — the
            # load_state_dict + optimizer.step() order).
            healed: ZeroState = self.opt_state
            merged = dict(healed.held)
            for s, sh in new_held.items():
                if s in updated_masters or s not in merged:
                    merged[s] = sh
            return (
                spec.unpack(jnp.asarray(new_flat)),
                replace(healed, held=merged, step=healed.step + 1,
                        balance_key=None),
            )

        return (new_params, spec_state), recompute

    # -- Optimizer seams ----------------------------------------------

    def _wire_speculate(self, grads: Any, pre_opt: Any, pre_params: Any,
                        should_quantize: bool):
        if should_quantize:
            _warn_quantize_once()
        self._maybe_rebalance()
        pre_state: ZeroState = self.opt_state  # re-read: rebalance rebinds
        avg_blocks = self._reduce_grad_shards(grads, pre_state)
        return self._zero_speculate(avg_blocks, pre_state)

    def _wire_step(self, grad_fn: Any, batch: Any, should_quantize: bool):
        if should_quantize:
            _warn_quantize_once()
        loss, grads = grad_fn(self.params, *batch)
        committed = self.step(grads)
        return loss, committed

    def _lone_step(
        self, fused: Any, fused_in_place: Any, grad_fn: Any, batch: Any
    ):
        # The lone step's programs here are a gradient program and a shard
        # update over held ranges that the re-balance exchange and the heal
        # address by reference: nothing is given away, the order stays
        # speculative.
        return self._speculative_lone_step(fused, grad_fn, batch)

    def _lone_dispatch(self, fused: Any, grad_fn: Any, batch: Any):
        self._maybe_rebalance()
        pre_params = self.params
        pre_state: ZeroState = self.opt_state
        with tracing.phase("update_dispatch", _trace_of(self.manager)):
            loss, grads = grad_fn(pre_params, *batch)
        n_params = self._state_leaves[0]
        _count_dispatch(n_params + self._batch_buffers(batch), 1 + n_params)
        avg_blocks = self._reduce_grad_shards(grads, pre_state)
        spec, recompute = self._zero_speculate(avg_blocks, pre_state)
        return loss, spec, recompute

    def step(self, grads: Any, timeout: Optional[float] = None) -> bool:
        """Commits one sharded step from the **local** gradient pytree
        (contrast :meth:`Optimizer.step`, which takes pre-averaged
        gradients): reduce-scatter, shard update, param allgather, then
        the commit barrier. The collectives complete before the vote
        launches — a rank whose sync failed must not vote commit."""
        grads = _sync_device(grads)
        heal_count = self._heal_count
        self._maybe_rebalance()
        pre_state: ZeroState = self.opt_state
        avg_blocks = self._reduce_grad_shards(grads, pre_state)
        spec, recompute = self._zero_speculate(avg_blocks, pre_state)
        return self._commit_and_adopt(heal_count, spec, recompute, timeout)


_WARNED_QUANTIZE = [False]


def _warn_quantize_once() -> None:
    if not _WARNED_QUANTIZE[0]:
        _WARNED_QUANTIZE[0] = True
        logger.warning(
            "should_quantize is a no-op on the ZeRO sharded wire; set "
            "TPUFT_ZERO_CODEC=fp8|int8|int4 instead (the codec is a wire "
            "format every replica must agree on, not a per-step flag — "
            "see docs/zero.md)"
        )
