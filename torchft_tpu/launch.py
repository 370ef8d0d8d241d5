"""Job launcher: replica-group supervision for one or many hosts.

Role-equivalent of the reference's launch tooling — ``torchft/torchx.py``
(per-replica-group roles with REPLICA_GROUP_ID / NUM_REPLICA_GROUPS /
lighthouse env wiring) and ``examples/slurm/runner.py`` (a supervision loop
that relaunches dead replica groups).

    python -m torchft_tpu.launch --num-replica-groups 4 -- \
        python examples/train_ddp.py --steps 100

Each replica group becomes a supervised subprocess with:
  REPLICA_GROUP_ID, NUM_REPLICA_GROUPS, TPUFT_LIGHTHOUSE
plus any TPUFT_* timeouts passed through, and — on a host with TPU chips —
its own disjoint set of them (:func:`chip_envs`): a chip belongs to one
process at a time, so the launcher itself never initializes a JAX backend.
Dead groups are relaunched with
**exponential backoff**: the delay doubles per recent rapid death (deaths
within ``_backoff_window`` seconds of each other — a genuinely
crash-looping group, not chaos kills minutes apart), capped at
``--relaunch-backoff-max``, so a hot-looping group cannot spin the host.
Restart exhaustion is **windowed**, not lifetime: ``--max-restarts``
restarts inside the sliding ``--restart-window`` seconds gives up on the
group (the torchelastic max_restarts contract, hardened for long-running
jobs where a lifetime counter eventually strands a healthy fleet over
unrelated faults spread across days).
"""

from __future__ import annotations

import argparse
import glob
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional

from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.utils.platform import cpu_by_name

__all__ = [
    "supervise",
    "main",
    "relaunch_delay",
    "prune_restart_window",
    "local_chip_count",
    "chip_envs",
]

# libtpu's process-bounds shape for an isolated process that owns N of a
# host's chips. Only what ran is listed (a v5e 2x2 host under libtpu 0.0.34:
# four one-chip and two two-chip processes side by side); any other share is
# refused, not guessed. A whole host needs no restriction at all.
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1"}
_GOOGLE_PCI_VENDOR = "0x1ae0"


def local_chip_count() -> int:
    """TPU chips this host lets us open, counted without a backend init.

    On v5e and newer a chip is a VFIO group file ``/dev/vfio/<n>``; any
    other device bound to vfio-pci looks the same there, so those files
    count only as far as sysfs lists Google PCI devices (sysfs alone
    over-counts: it lists the host's devices even where a sandbox passes
    only some of them through). Before v5e a chip is ``/dev/accel<n>``."""
    tpu_pci = 0
    for vendor in glob.glob("/sys/bus/pci/devices/*/vendor"):
        try:
            with open(vendor) as f:
                tpu_pci += f.read().strip() == _GOOGLE_PCI_VENDOR
        except OSError:
            pass
    vfio = len(glob.glob("/dev/vfio/[0-9]*"))
    return min(vfio, tpu_pci) + len(glob.glob("/dev/accel[0-9]*"))


def chip_envs(
    num_processes: int, env: Optional[Dict[str, str]] = None
) -> List[Dict[str, str]]:
    """One environment overlay per process, giving each its own equal,
    disjoint share of this host's chips (``TPU_VISIBLE_CHIPS`` plus the
    process-bounds pair libtpu wants with it). Every process is an
    ISOLATED slice (``TPU_PROCESS_BOUNDS=1,1,1``) that numbers its devices
    from 0: right for replica groups, which meet over host networking.

    Empty overlays — nothing to assign — where the caller asked for the
    CPU by name (:func:`~torchft_tpu.utils.platform.cpu_by_name` on
    ``env``, default this process's environment), where the host has no
    chips, or where one process owns the whole host. Raises when there are
    fewer chips than processes (two processes cannot share a chip, and the
    second would fail or hang inside TPU init rather than say so), and
    when the share is one nobody has run."""
    chips = local_chip_count()
    if cpu_by_name(env) or chips == 0 or num_processes == 1:
        return [{} for _ in range(num_processes)]
    per = chips // num_processes
    if per == 0:
        raise RuntimeError(
            f"{num_processes} processes cannot each own chips on a host "
            f"with {chips}: a chip belongs to one process at a time. Start "
            "fewer replica groups, or run on the CPU by name "
            "(JAX_PLATFORMS=cpu, with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N for a mesh)."
        )
    if per not in _CHIP_BOUNDS:
        raise RuntimeError(
            f"chip share {per} not verified for this host shape ({chips} "
            f"chips over {num_processes} processes): only shares of "
            f"{sorted(_CHIP_BOUNDS)} chips have run (v5e 2x2 host). Start "
            f"{chips // max(_CHIP_BOUNDS)} or more processes, or add the "
            "share to launch._CHIP_BOUNDS once it has run on such a host."
        )
    return [
        {
            "TPU_VISIBLE_CHIPS": ",".join(
                str(c) for c in range(i * per, (i + 1) * per)
            ),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": _CHIP_BOUNDS[per],
            "TPU_PROCESS_BOUNDS": "1,1,1",
        }
        for i in range(num_processes)
    ]


def relaunch_delay(
    base: float, recent_rapid_deaths: int, cap: float
) -> float:
    """The relaunch backoff schedule (pure function, unit-pinned):
    ``min(base * 2^n, cap)`` where ``n`` counts RECENT rapid deaths —
    deaths inside the short backoff window, i.e. evidence of a hot
    crash loop. Chaos kills minutes apart keep ``n`` at 0 and relaunch
    at the base interval; an instant-exit loop escalates geometrically
    to the cap."""
    return min(base * (2.0 ** max(recent_rapid_deaths, 0)), max(cap, base))


def prune_restart_window(
    restarts: List[float], now: float, window: float
) -> List[float]:
    """Sliding-window restart accounting (pure function, unit-pinned):
    keeps only restart timestamps within ``window`` seconds of ``now``.
    ``window <= 0`` disables pruning (lifetime semantics)."""
    if window <= 0:
        return list(restarts)
    return [t for t in restarts if now - t <= window]


def supervise(
    command: List[str],
    num_replica_groups: int,
    lighthouse_addr: Optional[str] = None,
    relaunch_interval: float = 10.0,
    max_restarts: int = 100,
    extra_env: Optional[Dict[str, str]] = None,
    group_world_size: int = 1,
    store_port_base: int = 29600,
    jax_coordinator_port_base: int = 0,
    restart_window: float = 600.0,
    relaunch_backoff_max: Optional[float] = None,
) -> int:
    """Runs ``command`` for each (group, rank) cell, relaunching dead
    groups. With ``group_world_size > 1`` every rank of a group shares
    GROUP_WORLD_SIZE/TPUFT_STORE_ADDR (group rank 0 binds the store on
    ``store_port_base + group``); a death of any rank restarts the whole
    group, matching the per-group restart unit of the reference's
    torchelastic deployment. Returns 0 when every group exits cleanly.

    Crash-loop hardening: the relaunch delay doubles per rapid death
    (:func:`relaunch_delay`, capped at ``relaunch_backoff_max``, default
    ``max(8 x relaunch_interval, relaunch_interval)``), and a group is
    given up only after ``max_restarts`` restarts inside the sliding
    ``restart_window`` seconds (:func:`prune_restart_window`;
    ``restart_window <= 0`` restores the legacy lifetime count)."""
    if group_world_size < 1:
        raise ValueError(f"group_world_size must be >= 1, got {group_world_size}")
    if jax_coordinator_port_base and group_world_size == 1:
        raise ValueError(
            "--jax-coordinator-port-base requires --group-world-size > 1 "
            "(a one-process group has nothing to cluster)"
        )
    chips = chip_envs(
        num_replica_groups * group_world_size,
        {**os.environ, **(extra_env or {})},
    )
    if jax_coordinator_port_base and any(chips):
        # chip_envs makes every process an isolated slice; the ranks of a
        # clustered group must instead form ONE slice over their chips
        # (real process bounds, addresses and task ids), which nobody has
        # run on a chip yet.
        raise RuntimeError(
            "--jax-coordinator-port-base on a host with TPU chips: forming "
            "one JAX cluster from several local processes over their own "
            "chips is not verified. Give the group one process that owns "
            "its chips (--group-world-size 1), or run on the CPU by name "
            "(JAX_PLATFORMS=cpu)."
        )
    own_lighthouse: Optional[LighthouseServer] = None
    if lighthouse_addr is None:
        own_lighthouse = LighthouseServer(
            min_replicas=1, join_timeout_ms=10000, heartbeat_timeout_ms=5000
        )
        lighthouse_addr = own_lighthouse.address()
        print(f"[launch] embedded lighthouse at {lighthouse_addr}", flush=True)

    import socket as _socket

    hostname = _socket.gethostname()

    def spawn_group(group: int) -> List[subprocess.Popen]:
        procs = []
        store_addr = f"{hostname}:{store_port_base + group}"
        for rank in range(group_world_size):
            env = {
                **os.environ,
                **chips[group * group_world_size + rank],
                **(extra_env or {}),
                "REPLICA_GROUP_ID": str(group),
                "NUM_REPLICA_GROUPS": str(num_replica_groups),
                "GROUP_RANK": str(rank),
                "GROUP_WORLD_SIZE": str(group_world_size),
                "TPUFT_LIGHTHOUSE": lighthouse_addr,
            }
            if group_world_size > 1:
                env["TPUFT_STORE_ADDR"] = store_addr
                if jax_coordinator_port_base:
                    env["TPUFT_JAX_COORDINATOR"] = (
                        f"{hostname}:{jax_coordinator_port_base + group}"
                    )
            print(
                f"[launch] starting group {group} rank {rank}: {' '.join(command)}",
                flush=True,
            )
            procs.append(subprocess.Popen(command, env=env))
        return procs

    groups = {g: spawn_group(g) for g in range(num_replica_groups)}
    # Restart timestamps per group (sliding-window exhaustion); the
    # short backoff window detects HOT loops (instant re-deaths) for the
    # exponential delay without punishing chaos kills minutes apart.
    restarts: Dict[int, List[float]] = {g: [] for g in range(num_replica_groups)}
    backoff_cap = (
        relaunch_backoff_max
        if relaunch_backoff_max is not None
        else max(8.0 * relaunch_interval, relaunch_interval)
    )
    backoff_window = max(4.0 * relaunch_interval + 5.0, 10.0)
    done: Dict[int, int] = {}
    try:
        while len(done) < num_replica_groups:
            time.sleep(min(relaunch_interval, 1.0))
            for group, procs in list(groups.items()):
                if group in done:
                    continue
                codes = [p.poll() for p in procs]
                if all(code == 0 for code in codes):
                    print(f"[launch] group {group} finished", flush=True)
                    done[group] = 0
                    continue
                failed = [code for code in codes if code not in (None, 0)]
                if not failed:
                    continue
                # Any dead rank restarts the whole group. Shared deadline so
                # a wedged multi-rank group can't stall supervision of the
                # others; after SIGKILL, reap each child so its sockets (the
                # fixed store port) are released before the respawn.
                for p in procs:
                    if p.poll() is None:
                        p.send_signal(signal.SIGTERM)
                term_deadline = time.monotonic() + 5
                for p in procs:
                    try:
                        p.wait(timeout=max(0.1, term_deadline - time.monotonic()))
                    except subprocess.TimeoutExpired:
                        p.kill()
                        p.wait()
                now = time.monotonic()
                restarts[group] = prune_restart_window(
                    restarts[group], now, restart_window
                )
                if len(restarts[group]) < max_restarts:
                    rapid = len(
                        prune_restart_window(restarts[group], now, backoff_window)
                    )
                    delay = relaunch_delay(relaunch_interval, rapid, backoff_cap)
                    restarts[group].append(now)
                    print(
                        f"[launch] group {group} died (exit {failed[0]}); "
                        f"relaunch {len(restarts[group])}/{max_restarts} "
                        f"(window {restart_window:g}s) in {delay:.1f}s",
                        flush=True,
                    )
                    time.sleep(delay)
                    groups[group] = spawn_group(group)
                else:
                    print(
                        f"[launch] group {group} exhausted restarts (exit {failed[0]})",
                        flush=True,
                    )
                    done[group] = failed[0]
        return 0 if all(code == 0 for code in done.values()) else 1
    finally:
        all_procs = [p for procs in groups.values() for p in procs]
        for proc in all_procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + 5
        for proc in all_procs:
            try:
                proc.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                proc.kill()
        if own_lighthouse is not None:
            own_lighthouse.shutdown()


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--num-replica-groups", type=int, required=True)
    parser.add_argument("--lighthouse", default=os.environ.get("TPUFT_LIGHTHOUSE"))
    parser.add_argument("--relaunch-interval", type=float, default=10.0)
    parser.add_argument("--max-restarts", type=int, default=100)
    parser.add_argument(
        "--restart-window",
        type=float,
        default=600.0,
        help="sliding window (seconds) for --max-restarts exhaustion; "
        "<= 0 restores the legacy lifetime count",
    )
    parser.add_argument(
        "--relaunch-backoff-max",
        type=float,
        default=None,
        help="cap on the exponential relaunch backoff (default "
        "8 x relaunch-interval)",
    )
    parser.add_argument("--group-world-size", type=int, default=1)
    parser.add_argument("--store-port-base", type=int, default=29600)
    parser.add_argument(
        "--jax-coordinator-port-base",
        type=int,
        default=0,
        help="when set, each group's ranks form one jax.distributed cluster "
        "(coordinator on this port + group id)",
    )
    parser.add_argument("command", nargs=argparse.REMAINDER, help="-- cmd args...")
    args = parser.parse_args()
    command = args.command
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        parser.error("missing command (after --)")
    sys.exit(
        supervise(
            command,
            num_replica_groups=args.num_replica_groups,
            lighthouse_addr=args.lighthouse,
            relaunch_interval=args.relaunch_interval,
            max_restarts=args.max_restarts,
            group_world_size=args.group_world_size,
            store_port_base=args.store_port_base,
            jax_coordinator_port_base=args.jax_coordinator_port_base,
            restart_window=args.restart_window,
            relaunch_backoff_max=args.relaunch_backoff_max,
        )
    )


if __name__ == "__main__":
    main()
