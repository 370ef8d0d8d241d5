"""Threads-as-replicas integration harness.

Parity target: the reference's manager_integ_test.py Runner/EventInjector
(:83-249): each replica group is a thread (with an inner pool for its local
ranks), owns its own rendezvous store, and retries its train loop on
injected failures to simulate supervised restarts. Faults are scheduled
deterministically by (replica_group, step).
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from torchft_tpu.utils import lockcheck

# Every kill/heal drill doubles as a race/deadlock probe: the runtime
# lock-order detector is ON by default for threads-as-replicas tests
# (export TPUFT_LOCK_CHECK=0 to opt out). A detected cycle or a lock held
# across a commit barrier raises lockcheck.LockOrderError and fails the
# drill. See docs/static_analysis.md.
lockcheck.maybe_enable_from_env(default="1")

from torchft_tpu.coordination import LighthouseServer  # noqa: E402
from torchft_tpu.ddp import ft_allreduce_gradients
from torchft_tpu.health import DegradedReplicaError
from torchft_tpu.manager import Manager
from torchft_tpu.optim import Optimizer
from torchft_tpu.parallel.process_group import (
    FakeProcessGroupWrapper,
    ProcessGroupTCP,
)
from torchft_tpu.parallel.store import StoreClient, StoreServer

logger = logging.getLogger(__name__)


class InjectedFailure(Exception):
    pass


# ---------------------------------------------------------------------------
# Metrics-plane assertions (torchft_tpu.metrics counters across a drill)
# ---------------------------------------------------------------------------

FT_COUNTERS = (
    "commits",
    "commit_failures",
    "rollbacks",
    "heals_donor",
    "heals_joiner",
    "errors",
    "phantom_commits",
    "heal_retries",
    "donor_failovers",
    "checksum_failures",
    "chunk_refetches",
    "resumed_bytes",
    "stalled_fetches",
    "era_rejects",
    "zero_rebalances",
    "zero_shards_moved",
    "zero_shard_reinits",
    "zero_heal_bytes_saved",
    "ingress_paced_seconds",
    "ingress_bytes",
    "heal_exhausted_incidents",
)


def ft_counter_snapshot(replica_id: str = "") -> Dict[str, float]:
    """Current totals of the FT phase counters, optionally filtered to one
    STABLE replica id (the manager labels counters with the user prefix,
    before the per-process uuid suffix, so totals accumulate across
    simulated supervisor restarts — exactly what a drill wants to count).
    Counters are process-global and tests share one process: assert on
    DELTAS via :func:`ft_counter_delta`, never on absolute values.

    The heal-transport counters (checksum failures, chunk re-fetches,
    resumed bytes, stalled fetches, era rejects) are emitted below the
    manager and carry no replica labels — they are always process-global,
    regardless of ``replica_id``."""
    from torchft_tpu import metrics

    label = {"replica_id": replica_id} if replica_id else {}
    return {
        "commits": metrics.counter_total("tpuft_commits_total", **label),
        "commit_failures": metrics.counter_total(
            "tpuft_commit_failures_total", **label
        ),
        "rollbacks": metrics.counter_total("tpuft_rollbacks_total", **label),
        "phantom_commits": metrics.counter_total(
            "tpuft_phantom_commits_total", **label
        ),
        "heals_donor": metrics.counter_total(
            "tpuft_heals_total", role="donor", **label
        ),
        "heals_joiner": metrics.counter_total(
            "tpuft_heals_total", role="joiner", **label
        ),
        "errors": metrics.counter_total("tpuft_errors_total", **label),
        "heal_retries": metrics.counter_total(
            "tpuft_heal_retries_total", **label
        ),
        "donor_failovers": metrics.counter_total(
            "tpuft_heal_donor_failovers_total", **label
        ),
        "checksum_failures": metrics.counter_total(
            "tpuft_heal_checksum_failures_total"
        ),
        "chunk_refetches": metrics.counter_total(
            "tpuft_heal_chunk_refetches_total"
        ),
        "resumed_bytes": metrics.counter_total("tpuft_heal_resumed_bytes_total"),
        "stalled_fetches": metrics.counter_total(
            "tpuft_heal_stalled_fetches_total"
        ),
        "era_rejects": metrics.counter_total("tpuft_heal_era_rejects_total"),
        "zero_rebalances": metrics.counter_total(
            "tpuft_zero_rebalance_total", **label
        ),
        "zero_shards_moved": metrics.counter_total(
            "tpuft_zero_shards_moved_total", **label
        ),
        "zero_shard_reinits": metrics.counter_total(
            "tpuft_zero_shard_reinits_total", **label
        ),
        "zero_heal_bytes_saved": metrics.counter_total(
            "tpuft_zero_heal_bytes_saved_total"
        ),
        "stripe_chunks": metrics.counter_total("tpuft_heal_stripe_chunks_total"),
        "stripe_donor_failures": metrics.counter_total(
            "tpuft_heal_stripe_donor_failures_total"
        ),
        "stripe_reassigned_chunks": metrics.counter_total(
            "tpuft_heal_stripe_reassigned_chunks_total"
        ),
        "stripe_refetched_bytes": metrics.counter_total(
            "tpuft_heal_stripe_refetched_bytes_total"
        ),
        "delta_chunks_matched": metrics.counter_total(
            "tpuft_heal_delta_chunks_matched_total"
        ),
        "delta_bytes_saved": metrics.counter_total(
            "tpuft_heal_delta_bytes_saved_total"
        ),
        # Storm-plane accounting: the joiner ingress bound's injected
        # pacing, and heal exhaustions (a storm drill's hard zero).
        "ingress_paced_seconds": metrics.counter_total(
            "tpuft_heal_ingress_paced_seconds_total"
        ),
        "ingress_bytes": metrics.counter_total("tpuft_heal_ingress_bytes_total"),
        "heal_exhausted_incidents": metrics.counter_total(
            "tpuft_trace_incidents_total", kind="heal_exhausted"
        ),
    }


def ft_counter_delta(
    before: Dict[str, float], after: Dict[str, float]
) -> Dict[str, float]:
    """after - before, per counter (what one drill contributed)."""
    return {key: after[key] - before[key] for key in after}


class EventInjector:
    """Deterministic fault schedule keyed (replica_group, step)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._fail_at: Dict[tuple, bool] = {}
        self._fail_allreduce_at: Dict[tuple, bool] = {}
        self.count = 0

    def fail_at(self, group: int, step: int) -> "EventInjector":
        self._fail_at[(group, step)] = False
        return self

    def fail_allreduce_at(self, group: int, step: int) -> "EventInjector":
        self._fail_allreduce_at[(group, step)] = False
        return self

    def check(self, group: int, step: int, pg: FakeProcessGroupWrapper) -> None:
        with self._lock:
            key = (group, step)
            if key in self._fail_at and not self._fail_at[key]:
                self._fail_at[key] = True
                self.count += 1
                logger.info("injecting failure %s", key)
                raise InjectedFailure(f"injected failure at {key}")
            if key in self._fail_allreduce_at and not self._fail_allreduce_at[key]:
                self._fail_allreduce_at[key] = True
                self.count += 1
                logger.info("injecting allreduce failure %s", key)
                pg.report_future_error(InjectedFailure(f"injected allreduce at {key}"))


@dataclass
class Runner:
    """One replica group: runs ``train_loop`` on ``world_size`` rank threads,
    retrying up to ``attempts`` times on InjectedFailure (simulating
    torchelastic restarts)."""

    replica_group: int
    lighthouse_addr: str
    train_loop: Callable[..., Any]
    num_steps: int = 4
    world_size: int = 1
    attempts: int = 3
    use_async_quorum: bool = True
    injector: Optional[EventInjector] = None
    manager_args: Dict[str, Any] = field(default_factory=dict)
    train_loop_args: Dict[str, Any] = field(default_factory=dict)

    def run_replica(self) -> List[Any]:
        for attempt in range(self.attempts):
            store = StoreServer()
            try:
                return _run_on_daemon_threads(
                    [
                        (lambda rank=rank: self._run_rank(store, rank))
                        for rank in range(self.world_size)
                    ],
                    f"replica{self.replica_group}",
                )
            except (InjectedFailure, DegradedReplicaError) as e:
                # Both are "supervisor restarts the group" in production:
                # an injected process death, or the health plane's
                # self-ejection escalating out of start_quorum.
                logger.info(
                    "replica %d attempt %d died (%s); restarting",
                    self.replica_group,
                    attempt,
                    type(e).__name__,
                )
                time.sleep(0.2)
                continue
            finally:
                store.shutdown()
        raise RuntimeError(
            f"replica {self.replica_group} exhausted {self.attempts} attempts"
        )

    def _run_rank(self, store: StoreServer, rank: int) -> Any:
        client = StoreClient(store.address(), prefix=f"grp{self.replica_group}")
        return self.train_loop(
            runner=self,
            rank=rank,
            store_client=client,
            store_addr=store.address() + f"/grp{self.replica_group}",
            **self.train_loop_args,
        )


def _run_on_daemon_threads(
    fns: List[Callable[[], Any]], name: str, timeout: Optional[float] = None
) -> List[Any]:
    """Runs ``fns`` concurrently and returns their results in order, raising
    the first (in order) exception. A thread still running ``timeout``
    seconds in raises TimeoutError instead — and, being a daemon, holds up
    neither the caller nor interpreter exit. (A ThreadPoolExecutor joins its
    workers without bound, on leaving its with-block and again at exit: one
    stuck replica thread used to hang the whole tier-1 run.)"""
    results: List[Any] = [None] * len(fns)
    errors: List[Optional[BaseException]] = [None] * len(fns)

    def run(i: int) -> None:
        try:
            results[i] = fns[i]()
        except BaseException as e:  # re-raised on the caller's thread
            errors[i] = e

    threads = [
        threading.Thread(target=run, args=(i,), name=f"{name}_{i}", daemon=True)
        for i in range(len(fns))
    ]
    deadline = None if timeout is None else time.monotonic() + timeout
    for t in threads:
        t.start()
    for t in threads:
        t.join(None if deadline is None else max(0.0, deadline - time.monotonic()))
    for i, t in enumerate(threads):
        if errors[i] is not None:
            raise errors[i]
        if t.is_alive():
            raise TimeoutError(f"{name}_{i} still running after {timeout}s")
    return results


def run_replica_groups(runners: List[Runner], timeout: float = 120.0) -> List[List[Any]]:
    """Runs all replica groups concurrently; returns per-group results. A
    group that has not finished after ``timeout`` seconds fails the test."""
    return _run_on_daemon_threads(
        [r.run_replica for r in runners], "group", timeout=timeout
    )


# ---------------------------------------------------------------------------
# The v0 DDP train loop (reference train_ddp.py analogue, sized for tests)
# ---------------------------------------------------------------------------


def _init_model_params(seed: int = 0) -> Any:
    """Tiny deterministic 2-layer MLP, identical on every replica."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "w1": jax.random.normal(k1, (8, 16), dtype=jnp.float32) * 0.1,
        "b1": jnp.zeros((16,), dtype=jnp.float32),
        "w2": jax.random.normal(k2, (16, 4), dtype=jnp.float32) * 0.1,
        "b2": jnp.zeros((4,), dtype=jnp.float32),
    }


@jax.jit
def _loss_fn(params: Any, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    logits = h @ params["w2"] + params["b2"]
    return jnp.mean((logits - y) ** 2)


_grad_fn = jax.jit(jax.grad(_loss_fn))


def _batch_for(step: int, replica_group: int) -> tuple:
    """Deterministic per-(step, group) synthetic batch so gradients differ
    across groups and averaging is observable."""
    key = jax.random.PRNGKey(1000 * replica_group + step)
    kx, ky = jax.random.split(key)
    x = jax.random.normal(kx, (4, 8), dtype=jnp.float32)
    y = jax.random.normal(ky, (4, 4), dtype=jnp.float32)
    return x, y


def ddp_train_loop(
    runner: Runner,
    rank: int,
    store_client: StoreClient,
    store_addr: str,
    min_replica_size: int = 1,
    init_sync: bool = True,
    transport_factory: Optional[Callable[[Runner, int], Any]] = None,
) -> Dict[str, Any]:
    """Returns {"state_dict": final state, "history": {step: params}}.

    ``transport_factory(runner, rank)`` (via ``train_loop_args``) supplies
    a per-rank CheckpointTransport — heal-path drills use it to hand the
    donor side a fault-injecting transport (see HTTPTransport._fault_hook).
    """
    pg = FakeProcessGroupWrapper(ProcessGroupTCP(timeout=10.0))
    manager_args = dict(runner.manager_args)
    if transport_factory is not None:
        manager_args["checkpoint_transport"] = transport_factory(runner, rank)
    manager = Manager(
        pg=pg,
        min_replica_size=min_replica_size,
        store=store_client,
        store_addr=store_addr,
        use_async_quorum=runner.use_async_quorum,
        group_rank=rank,
        group_world_size=runner.world_size,
        lighthouse_addr=runner.lighthouse_addr,
        replica_id=f"ddp_{runner.replica_group}",
        heartbeat_interval=0.05,
        timeout=10.0,
        quorum_timeout=20.0,
        init_sync=init_sync,
        **manager_args,
    )
    opt = Optimizer(manager, optax.sgd(0.05), _init_model_params())

    history: Dict[int, Any] = {}
    quorum_times: List[float] = []
    failed_commits = 0
    try:
        while manager.current_step() < runner.num_steps:
            step = manager.current_step()
            if runner.injector is not None:
                runner.injector.check(runner.replica_group, step, pg)

            t0 = time.monotonic()
            opt.begin_step()
            manager.wait_quorum()
            quorum_times.append(time.monotonic() - t0)
            x, y = _batch_for(step, runner.replica_group)
            grads = _grad_fn(opt.params, x, y)
            avg_grads = ft_allreduce_gradients(manager, grads)
            committed = opt.step(avg_grads)
            if committed:
                history[manager.current_step()] = jax.tree_util.tree_map(
                    lambda a: jnp.array(a), opt.params
                )
            else:
                failed_commits += 1
        return {
            "state_dict": {"params": opt.params, "opt_state": opt.opt_state},
            "history": history,
            "manager_state": manager.state_dict(),
            "quorum_times": quorum_times,
            "failed_commits": failed_commits,
        }
    finally:
        manager.shutdown(wait=False)
        pg.shutdown()


def step_fn_ddp_train_loop(
    runner: Runner,
    rank: int,
    store_client: StoreClient,
    store_addr: str,
    rejoin_after_step: Optional[int] = None,
    lone_step_sleep: float = 0.0,
) -> Dict[str, Any]:
    """The DDP loop through ``Optimizer.make_step_fn`` at depth 0: the wire
    path while two groups train, the lone replica's vote-first step (the
    state updated in place) while one trains alone. With
    ``rejoin_after_step`` a group that was killed comes back only once the
    lighthouse shows a member past that step, so the survivor provably ran
    alone in between and the joiner heals from a donor whose state its step
    gives away; ``lone_step_sleep`` paces the lone steps so that the
    survivor is still training when the joiner is back."""
    if (
        rejoin_after_step is not None
        and runner.injector is not None
        and runner.injector.count
        and runner.replica_group == 1
    ):
        from torchft_tpu.coordination import LighthouseClient

        client = LighthouseClient(runner.lighthouse_addr)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            steps = [m.member.step for m in client.status().members if not m.joining]
            if steps and max(steps) > rejoin_after_step:
                break
            time.sleep(0.05)
        client.close()
    pg = FakeProcessGroupWrapper(ProcessGroupTCP(timeout=10.0))
    manager = Manager(
        pg=pg,
        min_replica_size=1,
        store=store_client,
        store_addr=store_addr,
        use_async_quorum=runner.use_async_quorum,
        group_rank=rank,
        group_world_size=runner.world_size,
        lighthouse_addr=runner.lighthouse_addr,
        replica_id=f"ddp_{runner.replica_group}",
        heartbeat_interval=0.05,
        timeout=10.0,
        quorum_timeout=20.0,
        **runner.manager_args,
    )
    opt = Optimizer(manager, optax.sgd(0.05), _init_model_params())
    step_fn = opt.make_step_fn(_loss_fn)
    failed_commits = 0
    try:
        while manager.current_step() < runner.num_steps:
            step = manager.current_step()
            if runner.injector is not None:
                runner.injector.check(runner.replica_group, step, pg)
            _, committed = step_fn(*_batch_for(step, runner.replica_group))
            if not committed:
                failed_commits += 1
            if lone_step_sleep and manager.is_lone_replica():
                time.sleep(lone_step_sleep)
        return {
            "state_dict": {"params": opt.params, "opt_state": opt.opt_state},
            "manager_state": manager.state_dict(),
            "failed_commits": failed_commits,
        }
    finally:
        manager.shutdown(wait=False)
        pg.shutdown()


def pipelined_ddp_train_loop(
    runner: Runner,
    rank: int,
    store_client: StoreClient,
    store_addr: str,
    min_replica_size: int = 1,
    depth: int = 1,
) -> Dict[str, Any]:
    """The DDP loop under the pipelined-commit schedule
    (``commit_pipeline_depth=depth``): up to ``depth`` steps' device syncs
    + votes resolve while younger steps are dispatched. Batches are keyed
    on ``opt.next_pipelined_step()`` — ``manager.current_step()`` advances
    while votes are in flight, so it cannot key a lockstep data stream
    (see Optimizer.next_pipelined_step). Returns the same shape as
    ddp_train_loop plus rollback accounting."""
    pg = FakeProcessGroupWrapper(ProcessGroupTCP(timeout=10.0))
    manager = Manager(
        pg=pg,
        min_replica_size=min_replica_size,
        store=store_client,
        store_addr=store_addr,
        use_async_quorum=runner.use_async_quorum,
        group_rank=rank,
        group_world_size=runner.world_size,
        lighthouse_addr=runner.lighthouse_addr,
        replica_id=f"ddp_{runner.replica_group}",
        heartbeat_interval=0.05,
        timeout=10.0,
        quorum_timeout=20.0,
        commit_pipeline_depth=depth,
        **runner.manager_args,
    )
    opt = Optimizer(manager, optax.sgd(0.05), _init_model_params())
    step_fn = opt.make_step_fn(_loss_fn)

    failed_commits = 0
    try:
        # Terminate on the dispatch prediction, not current_step(): with a
        # vote in flight the manager counter lags by one, and looping on
        # it would dispatch (and commit) one step past num_steps. The
        # prediction assumes the in-flight step commits, so after a flush
        # that refused the final step the outer loop resumes training.
        while manager.current_step() < runner.num_steps:
            while opt.next_pipelined_step() < runner.num_steps:
                step = opt.next_pipelined_step()
                if runner.injector is not None:
                    # The injected death lands with the PREVIOUS step's
                    # vote still in flight (launched at the end of the
                    # last step_fn call) — the kill-during-pipelined-vote
                    # case.
                    runner.injector.check(runner.replica_group, step, pg)
                x, y = _batch_for(step, runner.replica_group)
                _, prev_committed = step_fn(x, y)
                if prev_committed is False:
                    failed_commits += 1
            if opt.flush_pipeline() is False:
                failed_commits += 1
        return {
            "state_dict": {"params": opt.params, "opt_state": opt.opt_state},
            "manager_state": manager.state_dict(),
            "failed_commits": failed_commits,
            "rollbacks": opt.rollback_count,
        }
    finally:
        try:
            opt.flush_pipeline(raise_on_error=False)
        except Exception:
            pass
        manager.shutdown(wait=False)
        pg.shutdown()


def zero_ddp_train_loop(
    runner: Runner,
    rank: int,
    store_client: StoreClient,
    store_addr: str,
    min_replica_size: int = 1,
    num_shards: int = 4,
    pipelined: bool = False,
) -> Dict[str, Any]:
    """The DDP loop with the ZeRO plane (torchft_tpu.zero.ZeroOptimizer):
    reduce-scattered grads, sharded update, allgathered params. Returns
    ``{"state_dict", "history", "held_shards", ...}`` — the drills assert
    bitwise-identical params across groups at every committed step and
    that shard ownership re-balances across kill/rejoin. ``pipelined``
    runs the same loop under ``commit_pipeline_depth=1`` (batches keyed
    on ``opt.next_pipelined_step()``, see pipelined_ddp_train_loop)."""
    from torchft_tpu.zero import ZeroOptimizer

    pg = FakeProcessGroupWrapper(ProcessGroupTCP(timeout=10.0))
    manager = Manager(
        pg=pg,
        min_replica_size=min_replica_size,
        store=store_client,
        store_addr=store_addr,
        use_async_quorum=runner.use_async_quorum,
        group_rank=rank,
        group_world_size=runner.world_size,
        lighthouse_addr=runner.lighthouse_addr,
        replica_id=f"zero_{runner.replica_group}",
        heartbeat_interval=0.05,
        timeout=10.0,
        quorum_timeout=20.0,
        commit_pipeline_depth=1 if pipelined else 0,
        **runner.manager_args,
    )
    opt = ZeroOptimizer(
        manager, optax.adam(0.05), _init_model_params(), num_shards=num_shards
    )

    history: Dict[int, Any] = {}
    failed_commits = 0

    def record() -> None:
        history[manager.current_step()] = jax.tree_util.tree_map(
            lambda a: np.asarray(a), opt.params
        )

    try:
        if pipelined:
            step_fn = opt.make_step_fn(_loss_fn)
            while manager.current_step() < runner.num_steps:
                while opt.next_pipelined_step() < runner.num_steps:
                    step = opt.next_pipelined_step()
                    if runner.injector is not None:
                        runner.injector.check(runner.replica_group, step, pg)
                    x, y = _batch_for(step, runner.replica_group)
                    _, prev_committed = step_fn(x, y)
                    if prev_committed is False:
                        failed_commits += 1
                if opt.flush_pipeline() is False:
                    failed_commits += 1
        else:
            while manager.current_step() < runner.num_steps:
                step = manager.current_step()
                if runner.injector is not None:
                    runner.injector.check(runner.replica_group, step, pg)
                opt.begin_step()
                manager.wait_quorum()
                x, y = _batch_for(step, runner.replica_group)
                # ZeroOptimizer.step takes LOCAL grads: the cross-replica
                # reduction IS the sharded reduce-scatter inside.
                grads = _grad_fn(opt.params, x, y)
                if opt.step(grads):
                    record()
                else:
                    failed_commits += 1
        return {
            "state_dict": {
                "params": opt.params,
                "held_shards": sorted(opt.opt_state.held),
                "opt_bytes": opt.opt_state.owned_bytes(),
            },
            "history": history,
            "manager_state": manager.state_dict(),
            "failed_commits": failed_commits,
            "rollbacks": opt.rollback_count,
        }
    finally:
        try:
            opt.flush_pipeline(raise_on_error=False)
        except Exception:
            pass
        manager.shutdown(wait=False)
        pg.shutdown()


# ---------------------------------------------------------------------------
# DiLoCo train loop (reference train_diloco.py analogue, sized for tests)
# ---------------------------------------------------------------------------


def diloco_live_state(algo: Any) -> Dict[str, Any]:
    """DiLoCo's registered state read off the live attributes, keyed as it is
    registered with the manager: what a capture taken now must equal."""
    user = {
        "diloco_inner": {
            "leaves": list(algo._leaves),
            "opt_state": algo.inner_opt_state,
        }
    }
    for frag in algo._fragments:
        user[frag._key] = {
            "original_parameters": list(frag.backup),
            "outer_optimizer": frag.outer_opt_state,
        }
    return user


def diloco_train_loop(
    runner: Runner,
    rank: int,
    store_client: StoreClient,
    store_addr: str,
    num_syncs: int = 3,
    sync_every: int = 4,
    n_fragments: int = 2,
    fragment_sync_delay: int = 0,
    should_quantize: bool = False,
    on_algo: Optional[Callable[[Runner, Manager, Any], None]] = None,
) -> Dict[str, Any]:
    """Streaming DiLoCo across replica groups; returns the per-fragment
    global state for cross-group equality assertions. ``on_algo(runner,
    manager, algo)`` runs once per incarnation before the first step (a
    test's place to watch what a heal captures and what it applies)."""
    from torchft_tpu.local_sgd import DiLoCo

    pg = FakeProcessGroupWrapper(ProcessGroupTCP(timeout=10.0))
    manager = Manager(
        pg=pg,
        min_replica_size=1,
        store=store_client,
        store_addr=store_addr,
        use_async_quorum=False,
        group_rank=rank,
        group_world_size=runner.world_size,
        lighthouse_addr=runner.lighthouse_addr,
        replica_id=f"diloco_{runner.replica_group}",
        heartbeat_interval=0.05,
        timeout=10.0,
        quorum_timeout=20.0,
        **runner.manager_args,
    )
    try:
        algo = DiLoCo(
            manager,
            inner_tx=optax.sgd(0.05),
            outer_tx=optax.sgd(0.7, momentum=0.9, nesterov=True),
            params=_init_model_params(),
            sync_every=sync_every,
            n_fragments=n_fragments,
            fragment_sync_delay=fragment_sync_delay,
            should_quantize=should_quantize,
        )
        if on_algo is not None:
            on_algo(runner, manager, algo)
        inner_iter = 0
        failed_syncs = 0  # outer steps lost (north star: <= 1 per kill)
        while manager.current_step() < num_syncs:
            if runner.injector is not None:
                runner.injector.check(runner.replica_group, manager.current_step(), pg)
            x, y = _batch_for(1000 + inner_iter, runner.replica_group)
            grads = _grad_fn(algo.params, x, y)
            sync_due = algo._local_step + 1 == algo._sync_every
            committed = algo.step(grads)
            if sync_due and not committed:
                failed_syncs += 1
            inner_iter += 1
        return {
            "failed_syncs": failed_syncs,
            "global_state": [
                {
                    "backup": [np.array(b) for b in frag.backup],
                    "outer_opt": jax.tree_util.tree_map(
                        lambda v: np.asarray(v) if hasattr(v, "shape") else v,
                        frag.outer_opt_state,
                    ),
                }
                for frag in algo._fragments
            ],
            "manager_state": manager.state_dict(),
        }
    finally:
        manager.shutdown(wait=False)
        pg.shutdown()
