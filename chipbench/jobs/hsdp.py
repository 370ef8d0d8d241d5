"""hsdp: FT-HSDP across two replica groups, each one process holding two chips
on an fsdp=2 mesh under ``launch.supervise`` + ``chip_envs``. Every step's
gradients are averaged across the groups through ``ft_allreduce_sharded`` over
the native process group on loopback and committed by vote.

The parent stays off JAX (a chip belongs to one process). The fleet's window
runs from the LATER group's opening fetch to the EARLIER group's closing one,
on the monotonic clock the two processes share on one host, and counts the
committed tokens of both groups. Group 0 decides after each step, by that
clock, whether the window goes on; group 1 follows its decision, so both run
the same number of steps.
"""

from __future__ import annotations

import gc
import hashlib
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
from pathlib import Path
from typing import Any, Dict, List

from chipbench import harness

GROUPS = 2


def run(run) -> Dict[str, Any]:
    """The parent: lighthouse, supervised workers, their reports merged."""
    from torchft_tpu import _native
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.launch import local_chip_count, supervise

    if int(run.traffic["groups"]) != GROUPS:
        raise ValueError(f"the hsdp job runs {GROUPS} groups")
    if run.tail:
        raise SystemExit(
            "no result: --trace 2 measures and traces in ONE process, and this job's "
            "chips are held by worker processes of their own; it takes --trace 0 or 1"
        )
    extra_env = {"TPUFT_LOG": os.environ.get("TPUFT_LOG", "warn")}
    if run.rehearsal:
        extra_env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    elif local_chip_count() < run.chips:
        raise SystemExit(
            f"no result: this cell needs {run.chips} TPU chips; this host lets "
            f"us open {local_chip_count()}. The benchmark does not fall back."
        )
    harness.enable_compile_cache()  # exported: the workers take the same directory
    _native.ensure_built()  # once, before two workers would race to build it
    lighthouse = LighthouseServer(
        bind=f"{harness.LOOPBACK}:0", min_replicas=GROUPS, join_timeout_ms=3000,
        heartbeat_timeout_ms=5000,
    )
    out_dir = Path(tempfile.mkdtemp(prefix="chipbench_hsdp_"))
    worker = [
        sys.executable, str(harness.ROOT / "chipbench" / "run.py"),
        "--workload", run.cell["name"], "--seed", str(run.seed),
        "--seconds", str(run.seconds), "--trace", str(int(run.trace)),
        "--worker", str(out_dir), "--started", repr(run.started),
    ]
    if run.rehearsal:
        worker += ["--rehearse", str(run.rehearsal)]
    if run.out_dir is not None:
        worker += ["--out", str(run.out_dir)]
    try:
        rc = supervise(
            worker, num_replica_groups=GROUPS, lighthouse_addr=lighthouse.address(),
            relaunch_interval=1.0, max_restarts=0, extra_env=extra_env,
        )
    finally:
        lighthouse.shutdown()
        for log in sorted(out_dir.glob("group*.log")):
            for line in log.read_text().splitlines():
                if " INF tpuft] " not in line and "hugepage" not in line:
                    harness.say(f"  {log.stem} | {line}")
    try:
        if rc != 0:
            raise SystemExit(f"no result: a replica-group process failed (supervise returned {rc})")
        groups = [json.loads((out_dir / f"group{g}.json").read_text()) for g in range(GROUPS)]
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)  # nothing of a run stays in TMPDIR
    return merge(run, groups)


def merge(run, groups: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The fleet's outcome from the groups' reports."""
    opened = max(g["opened"] for g in groups)
    closed = min(g["closed"] for g in groups)
    problems = [f"group {g['group']}: {p}" for g in groups for p in g["problems"]]
    if len({g["digest"] for g in groups}) != 1:
        problems.append("the groups' parameter digests differ after the window")
    if len({g["obs"]["steps"] for g in groups}) != 1:
        problems.append("the groups ran different numbers of steps")
    steps = groups[0]["obs"]["steps"]
    failed = max(g["failed"] for g in groups)
    traces = [g["obs"]["trace"] for g in groups]
    peak = {
        key: max(g["device"][key] for g in groups)
        for key in ("memory_peak_bytes", "arrays_peak_bytes", "held_peak_bytes", "scratch_peak_bytes")
    }
    obs = {
        "chips": run.chips,
        "tokens": sum(g["obs"]["tokens"] for g in groups),
        "window_s": closed - opened,
        "steps": steps,
        "units": steps,
        "setup_s": opened - run.started,
        "arrays_peak_bytes": peak["arrays_peak_bytes"],
        "held_peak_bytes": peak["held_peak_bytes"],
        "scratch_peak_bytes": peak["scratch_peak_bytes"],
        "compile": max((g["obs"]["compile"] for g in groups), key=lambda c: c["seconds"]),
        "groups": [g["obs"] for g in groups],
        "flops_per_token": groups[0]["obs"]["flops_per_token"],
        "peaks": groups[0]["obs"]["peaks"],
        "trace": None if any(t is None for t in traces) else {
            "busy_s": sum(t["busy_s"] for t in traces) / len(traces),
            "window_s": sum(t["window_s"] for t in traces) / len(traces),
            "ops": _mean_lists([t["ops"] for t in traces]),
            "gaps": _mean_lists([t["gaps"] for t in traces]),
        },
    }
    device = {
        "platform": groups[0]["device"]["platform"],
        "kind": groups[0]["device"]["kind"],
        "count": sum(g["device"]["count"] for g in groups),
        "memory_peak_bytes": peak["memory_peak_bytes"],
    }
    return {
        "correct": not problems, "problems": problems, "attempted": steps,
        "failed": failed, "obs": obs, "device": device,
    }


def _mean_lists(lists: List[List[List[Any]]]) -> List[List[Any]]:
    """[[name, seconds], ...] of every group (each a mean over that group's
    chips) into one list, a mean over the groups."""
    total: Dict[str, float] = {}
    for pairs in lists:
        for name, seconds in pairs:
            total[name] = total.get(name, 0.0) + seconds / len(lists)
    return sorted(([k, v] for k, v in total.items()), key=lambda p: -p[1])


# -- one replica group ---------------------------------------------------------


def worker(run, out_dir: Path) -> None:
    """One replica-group process; a crash exits at once (an interpreter exit
    would wait out the manager's quorum thread while the chips are held) and
    tells the peer to do the same."""
    group = int(os.environ["REPLICA_GROUP_ID"])
    log = open(out_dir / f"group{group}.log", "w")
    os.dup2(log.fileno(), 1)
    os.dup2(log.fileno(), 2)

    def watch_peer() -> None:
        while not (out_dir / "failed").exists():
            time.sleep(0.5)
        print(f"[group {group}] the peer failed: exiting", flush=True)
        os._exit(3)

    threading.Thread(target=watch_peer, daemon=True).start()
    try:
        _group_main(run, out_dir, group)
    except BaseException:
        traceback.print_exc()
        sys.stdout.flush()
        (out_dir / "failed").touch()
        os._exit(1)


def _wait_for(path: Path, timeout: float = 300.0) -> str:
    deadline = time.monotonic() + timeout
    while not path.exists():
        if time.monotonic() > deadline:
            raise TimeoutError(f"{path.name} never appeared")
        time.sleep(0.001)
    return path.read_text()


def _publish(path: Path, text: str) -> None:
    tmp = path.with_suffix(".tmp")
    tmp.write_text(text)
    tmp.rename(path)  # appears whole or not at all


def _group_main(run, out_dir: Path, group: int) -> None:
    import jax
    import numpy as np

    from chipbench.model import System
    from chipbench.spans import SpanLog
    from torchft_tpu.bootstrap import init_manager
    from torchft_tpu.models.llama import apply_sharding_plan, sharding_plan
    from torchft_tpu.optim import Optimizer
    from torchft_tpu.parallel.mesh import ft_allreduce_sharded, ft_init_device_mesh
    from torchft_tpu.parallel.native_pg import ProcessGroupNative

    say = lambda msg: print(f"[group {group}] {msg}", flush=True)
    n_local = run.chips // GROUPS
    devices = harness.require_devices(n_local, run.rehearsal)
    harness.enable_compile_cache()
    ledger = harness.CompileLedger()
    spans = SpanLog()
    system = System(run.config, run.architecture, run.traffic, run.seed)
    say(f"{devices[0].platform} {devices[0].device_kind} x{len(devices)}, "
        f"TPU_VISIBLE_CHIPS={os.environ.get('TPU_VISIBLE_CHIPS')}")

    timeout = float(run.traffic["manager_timeout_s"])
    pg = ProcessGroupNative(timeout=timeout)
    manager, store = init_manager(
        pg, min_replica_size=GROUPS, replica_id=f"chipbench_hsdp_{group}",
        timeout=timeout, quorum_timeout=60.0, heartbeat_interval=0.1,
        hostname=harness.LOOPBACK, manager_bind=f"{harness.LOOPBACK}:0",
    )
    problems: List[str] = []
    try:
        ft_mesh = ft_init_device_mesh(manager, (n_local, 1), ("fsdp", "tp"), devices=devices)
        params = system.init_params()  # both groups: the same seed, the same weights
        system.reference = harness.reference_losses(system, params, group, GROUPS)
        gauge = harness.MemoryGauge(devices)
        params = apply_sharding_plan(params, ft_mesh.mesh, sharding_plan("fsdp", "tp"))
        opt = Optimizer(manager, system.tx, params)
        del params
        gc.collect()
        grad_fn = jax.jit(jax.value_and_grad(system.loss_fn))
        tokens_sharding = ft_mesh.sharding("fsdp", None)
        losses: List[Any] = []
        commits: List[bool] = []
        step_ends: List[float] = []
        wire_bytes: List[int] = []
        took_part: List[bool] = []  # step 0: did this group give its gradient

        def step() -> None:
            i = len(losses)
            with spans.span("chipbench/step"):
                tokens = jax.device_put(system.tokens(i, group), tokens_sharding)
                opt.begin_step()
                loss, grads = grad_fn(opt.params, tokens)
                if not wire_bytes:
                    wire_bytes.append(sum(
                        shard.data.nbytes
                        for leaf in jax.tree_util.tree_leaves(grads)
                        for shard in leaf.addressable_shards
                    ))
                with spans.span("chipbench/wire"):
                    averaged = ft_allreduce_sharded(manager, grads)
                if not took_part:
                    took_part.append(bool(manager.is_participating()))
                    _publish(out_dir / f"took_part_{group}", str(int(took_part[0])))
                with spans.span("chipbench/commit"):
                    commits.append(bool(opt.step(averaged)))
            losses.append(loss)
            step_ends.append(time.monotonic())

        def fetch() -> None:
            with spans.span("chipbench/fetch"):
                jax.block_until_ready(opt.params)
                if losses:
                    float(losses[-1])

        with jax.set_mesh(ft_mesh.mesh):
            for _ in range(int(run.traffic["warmup_units"])):
                step()
            fetch()
            warm_steps = len(losses)
            gc.collect()
            gc.freeze()
            compile_setup = ledger.snapshot()
            counters_before = harness.counter_sums()

            # The fleet's window opens at the later group's opening fetch.
            _publish(out_dir / f"open_{group}", repr(time.monotonic()))
            my_open = float((out_dir / f"open_{group}").read_text())
            peer_open = float(_wait_for(out_dir / f"open_{1 - group}"))
            fleet_open = max(my_open, peer_open)
            seconds, min_units = run.window_seconds(), int(run.traffic.get("min_units", 1))
            with harness.HostPulse(gauge.sample), harness.Tracer(run.trace) as tracer:
                while True:
                    step()
                    k = len(losses) - warm_steps
                    if group == 0:
                        done = time.monotonic() - fleet_open >= seconds and k >= min_units
                        _publish(out_dir / f"decide_{k}", "stop" if done else "go")
                    else:
                        done = _wait_for(out_dir / f"decide_{k}") == "stop"
                    if done:
                        break
                fetch()
                closed = time.monotonic()
        counters = harness.counter_deltas(counters_before, harness.counter_sums())
        compiled_inside = ledger.compiles - compile_setup["count"]
        trace = tracer.reduce(run.out_dir)
        steps = len(losses) - warm_steps
        values = np.asarray(jax.device_get(losses), dtype=np.float64)
        problems += harness.window_checks(values, compiled_inside)
        # The second loss is the window's first step (warm-up is one step).
        # A group that heals in step 0 (the start's sync of states) gives no
        # gradient to it; the program says which groups did, and AdamW's first
        # step does not depend on what the sum was divided by.
        participants = "+".join(
            str(g) for g in range(GROUPS)
            if int(_wait_for(out_dir / f"took_part_{g}", timeout=10.0))
        )
        problems += harness.reference_check(system, values[:2].tolist(), participants)
        failed = sum(1 for c in commits[warm_steps:] if not c)
        if failed or not all(commits):
            problems.append(f"{failed} step(s) of the window did not commit")
        digest = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(opt.params):
            digest.update(np.asarray(leaf).tobytes())
        device = gauge.report()
        problems += harness.memory_problems(device)
        say(f"{steps} steps, losses {values.tolist()}, commits {commits}, "
            f"window {closed - fleet_open:.2f}s, compile in set-up "
            f"{compile_setup['seconds']:.1f}s (hits {compile_setup['cache_hits']}, "
            f"misses {compile_setup['cache_misses']})")
        report = {
            "group": group, "opened": fleet_open, "closed": closed,
            "problems": problems, "failed": failed, "digest": digest.hexdigest(),
            "device": device,
            "obs": {
                "tokens": sum(commits[warm_steps:]) * system.tokens_per_step,
                "steps": steps,
                "window_s": closed - fleet_open,
                "compile": compile_setup,
                "counters": counters,
                "spans": spans.totals(fleet_open, closed),
                "step_ends": [t - fleet_open for t in step_ends[warm_steps:]],
                "wire_bytes_per_step": wire_bytes[0],
                "trace": trace,
                "flops_per_token": run.architecture.train_flops_per_token(run.config, system.seq),
                "peaks": None if run.rehearsal else harness.peaks_for(devices[0].device_kind),
            },
        }
        _publish(out_dir / f"group{group}.json", json.dumps(report))
    finally:
        manager.shutdown(wait=False)
        pg.shutdown()
        if store is not None:
            store.shutdown()
