"""What the jobs share: device checks, the compile cache and its ledger, the
FT plane on loopback, the one-process run (set-up, warm-up, window, checks)
and the observations the metric readers read.

``CompileLedger``, ``Plane`` and ``balanced_fragments`` live here and
``chip_smoke.py`` imports them (since PR 31 it carries no copy): later PRs may
change the program, not the yardstick.
"""

from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

from chipbench.spans import SpanLog
from chipbench.window import run_window

ROOT = Path(__file__).resolve().parent.parent
LOOPBACK = "127.0.0.1"
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# The program's host-clock histograms at its layer boundaries (metrics.py);
# the readers take their deltas over the window.
COUNTERS = (
    "tpuft_quorum_seconds",
    "tpuft_commit_barrier_seconds",
    "tpuft_device_sync_seconds",
    "tpuft_update_dispatch_seconds",
)


def say(msg: str) -> None:
    """Progress goes to stderr: stdout's last line is the result."""
    print(msg, file=sys.stderr, flush=True)


def process_start() -> float:
    """This process's creation on the monotonic clock (falls back to now):
    set-up is counted from the process's start, imports included."""
    now = time.monotonic()
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
        return now - age if 0 <= age < 3600 else now
    except (OSError, ValueError, IndexError, AttributeError):
        return now


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` where that is
    set, else at a fixed path inside the checkout (the path is part of the
    key). Exported, so that worker processes and the program's own helper
    take the same directory. Every program is cached, however quick its
    compile: the second run of a cell must find them all."""
    import jax

    cache_dir = os.environ.get(COMPILE_CACHE_ENV)
    if not cache_dir:
        cache_dir = str(ROOT / ".jax_cache")
        os.environ[COMPILE_CACHE_ENV] = cache_dir
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class CompileLedger:
    """Counts backend compilations (cache retrievals included) and their
    seconds, and the persistent cache's hits and misses."""

    def __init__(self) -> None:
        import jax.monitoring

        self.compiles = 0
        self.compile_seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_: object) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_seconds += seconds

    def _on_event(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {
            "count": self.compiles, "seconds": self.compile_seconds,
            "cache_hits": self.cache_hits, "cache_misses": self.cache_misses,
        }


def peaks_for(device_kind: str) -> Dict[str, Any]:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    for name, row in table.items():
        if not name.startswith("_") and name.lower() in device_kind.lower():
            return row
    raise KeyError(
        f"device_kind {device_kind!r} is not in chipbench/peaks.json: add its "
        "published peaks, with the source, before measuring on it"
    )


def require_devices(chips: int, rehearsal: bool):
    """This process's devices when they are ``chips`` TPU chips or more;
    exits non-zero, with no result, otherwise. A rehearsal (asked for by
    name) takes whatever platform answered and says so."""
    import jax

    devices = jax.devices()
    if not rehearsal and (devices[0].platform != "tpu" or len(devices) < chips):
        raise SystemExit(
            f"no result: this cell needs {chips} TPU chip(s); jax sees "
            f"{len(devices)} x {devices[0].platform} ({devices[0].device_kind}). "
            "The benchmark measures on the chip and does not fall back."
        )
    return devices[:chips]


class MemoryGauge:
    """Two readings of what the chips hold, neither a sum of two peaks.

    ``arrays_peak_bytes`` is the runtime's own ``peak_bytes_in_use`` on the
    fullest device after the window: exact, transients included, the same in
    every run. On this runtime it counts live arrays only; a loaded program's
    scratch (XLA's temporaries: activations, gradients in flight) is "reserved
    at the bottom of memory" and reported apart (a program with 4 GiB of
    temporaries moved ``bytes_reserved`` by 4.0 GiB and ``peak_bytes_in_use``
    by nothing: my chip run, PR 24). It is a peak of the process's whole life,
    so set-up must leave no large array behind and hold fewer live bytes than
    the job: the float32 reference's update is one program to a number where
    that fits the chip, and where it is made a part at a time its live arrays
    are the system's weights, one stepped copy of them and the leaf being
    stacked (5.3 bytes a parameter at 1.36G under the job's 6.0: my chip run,
    PR 64), each part's float32 copies being its program's scratch.

    ``held_peak_bytes`` is the largest ``bytes_in_use + bytes_reserved`` of ONE
    instant, sampled every 5 ms through the window by the HostPulse thread
    (and ``scratch_peak_bytes`` the largest ``bytes_reserved``). Both peaks of
    the runtime added would count what never coincided (DiLoCo's codecs:
    16.38 GiB "held" on a 15.75 GiB chip). A transient between two samples is
    missed, so this reads under the true peak, never over it, and it is as
    steady as the thread is punctual: 9.1 and 9.5 GiB in two runs of the fleet
    cell, whose host is busy (my chip runs, PR 24). Hence a per-layer metric,
    not the end-to-end one."""

    def __init__(self, devices) -> None:
        self.devices = list(devices)
        self.held = self.scratch = 0
        self.samples = 0
        self.seconds = 0.0

    def sample(self) -> None:
        t0 = time.monotonic()
        for d in self.devices:
            stats = d.memory_stats() or {}
            arrays, scratch = int(stats.get("bytes_in_use", 0)), int(stats.get("bytes_reserved", 0))
            self.held, self.scratch = max(self.held, arrays + scratch), max(self.scratch, scratch)
        self.samples += 1
        self.seconds += time.monotonic() - t0

    def report(self) -> Dict[str, Any]:
        """Platform, kind, count and the readings above. ``memory_peak_bytes``,
        the result line's "peak on the fullest chip", is the larger of the
        two: each is a lower bound of the true peak."""
        self.sample()
        stats = [d.memory_stats() or {} for d in self.devices]
        arrays = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
        return {
            "platform": self.devices[0].platform,
            "kind": self.devices[0].device_kind,
            "count": len(self.devices),
            "memory_peak_bytes": max(arrays, self.held),
            "arrays_peak_bytes": arrays,
            "held_peak_bytes": self.held,
            "scratch_peak_bytes": self.scratch,
            "bytes_limit": max(int(s.get("bytes_limit", 0)) for s in stats),
            "samples": self.samples,
            "sample_seconds": self.seconds,
        }


def memory_problems(report: Dict[str, Any]) -> List[str]:
    """A chip cannot hold more than its limit: a reading above it is a wrong
    count, and the run is not correct (it is never clipped)."""
    limit = report["bytes_limit"]
    if limit and report["memory_peak_bytes"] > limit:
        return [f"{report['memory_peak_bytes']} bytes sampled on a chip of {limit}"]
    return []


def slowest_steps(step_ends: List[float], k: int = 3) -> str:
    """The k longest gaps between consecutive step ends, as "i:ms": where a
    run that reads far off lost its time."""
    gaps = sorted(
        ((b - a, i + 1) for i, (a, b) in enumerate(zip(step_ends[:-1], step_ends[1:]))),
        reverse=True,
    )[:k]
    return ", ".join(f"{i}:{1e3 * g:.0f}ms" for g, i in gaps)


class Plane:
    """One replica group's control plane: lighthouse, store, native process
    group, manager: every address that has a parameter is loopback."""

    def __init__(self, replica_id: str, timeout: float, **manager_kwargs: Any) -> None:
        from torchft_tpu import _native
        from torchft_tpu.coordination import LighthouseServer
        from torchft_tpu.manager import Manager
        from torchft_tpu.parallel.native_pg import ProcessGroupNative
        from torchft_tpu.parallel.store import StoreClient, StoreServer

        _native.ensure_built()  # into native/build inside the checkout
        self.lighthouse = LighthouseServer(
            bind=f"{LOOPBACK}:0", min_replicas=1, join_timeout_ms=100
        )
        self.store = StoreServer(f"{LOOPBACK}:0")
        self.pg = ProcessGroupNative(timeout=timeout)
        self.manager = Manager(
            pg=self.pg,
            store=StoreClient(self.store.address()),
            store_addr=self.store.address(),
            lighthouse_addr=self.lighthouse.address(),
            replica_id=replica_id,
            hostname=LOOPBACK,
            manager_bind=f"{LOOPBACK}:0",
            timeout=timeout,
            quorum_timeout=60.0,
            min_replica_size=1,
            **manager_kwargs,
        )

    def shutdown(self) -> None:
        self.manager.shutdown(wait=False)
        self.pg.shutdown()
        self.store.shutdown()
        self.lighthouse.shutdown()


def balanced_fragments(params, n_fragments: int):
    """``fragment_fn`` for DiLoCo: leaves spread over fragments by size
    (largest first) instead of contiguous chunks, which would put both
    vocabulary matrices and the whole MLP stack into one fragment whose
    outer step needs more temporaries than the chip has."""
    import jax

    sizes = [leaf.size for leaf in jax.tree_util.tree_leaves(params)]

    def fragment_fn(n_leaves: int):
        if n_leaves != len(sizes):
            raise ValueError(f"{n_leaves} leaves, sized for {len(sizes)}")
        bins: List[List[int]] = [[] for _ in range(n_fragments)]
        load = [0] * n_fragments
        for i in sorted(range(n_leaves), key=lambda i: -sizes[i]):
            b = load.index(min(load))
            bins[b].append(i)
            load[b] += sizes[i]
        return [sorted(b) for b in bins]

    return fragment_fn


def counter_sums() -> Dict[str, Dict[str, float]]:
    from torchft_tpu import metrics

    return {name: dict(metrics.histogram_stats(name)) for name in COUNTERS}


def counter_deltas(before, after) -> Dict[str, Dict[str, float]]:
    return {
        name: {
            "sum": after[name]["sum"] - before[name]["sum"],
            "count": after[name]["count"] - before[name]["count"],
        }
        for name in after
    }


def reference_check(system, losses: List[float], participants: str = "0") -> List[str]:
    """The system's first two losses against the float32 reference on the same
    seeded batches and weights: the first checks the forward pass, the second
    (the loss of batch 1 after ONE update from the gradient of the groups
    ``participants`` names) the gradient, the average and the optimizer."""
    tol = system.config["reference_tolerance"]
    want = system.reference
    problems = []
    for name, got, ref, limit in (
        ("first", losses[0], want["first"], tol["relative"]),
        ("second", losses[1] if len(losses) > 1 else math.nan,
         want["second"][participants], tol["update_relative"]),
    ):
        diff = abs(got - ref) / abs(ref)
        say(f"reference: {name} loss {got!r} vs float32 reference {ref!r}: "
            f"relative difference {diff:.3e} (tolerance {limit:.3e})")
        if not (math.isfinite(diff) and diff <= limit):
            problems.append(f"{name} loss differs from the float32 reference by {diff:.3e}")
    moved = abs(want["second"][participants] - want["second_without_update"]) / abs(want["first"])
    say(f"reference: the update of group(s) {participants} moved the second loss by "
        f"{moved:.3e} relative (without it {want['second_without_update']!r}; "
        f"by set of groups {want['second']})")
    if moved < 4 * tol["update_relative"]:
        problems.append(
            f"the reference's update moves the second loss by {moved:.3e} only: "
            "the second check could not tell an update from none"
        )
    return problems


def window_checks(losses, compiled_inside: int) -> List[str]:
    """What every job's window must satisfy, whatever the job."""
    import numpy as np

    problems = []
    if not np.all(np.isfinite(losses)):
        problems.append("a loss is not finite")
    if compiled_inside:
        problems.append(f"{compiled_inside} compilation(s) inside the window")
    return problems


def reference_losses(
    system, params, group: int = 0, groups: int = 1, by_parts: Optional[bool] = None
) -> Dict[str, Any]:
    """Float32 losses of ``group``'s first two batches (chipbench/reference.py
    around the architecture's ``sequence_loss``):
    ``first`` under ``params``; ``second_without_update``; and ``second``
    after one reference AdamW step on the mean gradient over the first batches
    of a set of groups, one value for every set that can have taken part in
    step 0 ("0", "1", "0+1": the program says afterwards which it was, and a
    group that heals in a step gives no gradient to it).

    The update is one program from the system's weights to a number where that
    program fits the device (``reference.whole_update_fits``: the tree's
    parameters against the ``bytes_limit`` of the device that holds them), so
    that its float32 copies are temporaries; where it would not fit, the update
    is made a part at a time and the second loss taken on the stepped tree,
    which is let go before the next set of groups. Either way no array of the
    reference outlives the call, and its live arrays stay under the job's own.
    ``by_parts`` is for the tests and the builder's comparison of the two ways;
    the benchmark never passes it."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference

    if groups not in (1, 2):
        raise ValueError("the reference's update is written for one or two groups")
    leaves = jax.tree_util.tree_leaves(params)
    n_parameters = sum(leaf.size for leaf in leaves)
    if by_parts is None:
        limit = max(int((d.memory_stats() or {}).get("bytes_limit", 0)) for d in leaves[0].devices())
        by_parts = not reference.whole_update_fits(n_parameters, limit)
    loss = reference.make_loss(system.architecture, system.config)
    if by_parts:
        update = reference.make_first_update_by_parts(system.architecture, system.config)
        say(f"reference: {n_parameters} parameters, the update in "
            f"{len(reference.parts_of(params))} parts")

        def loss_after(params, first, then):
            return loss(update(params, first), then)
    else:
        loss_after = reference.make_loss_after_first_update(system.architecture, system.config)
    then = system.tokens(1, group)
    out: Dict[str, Any] = {
        "first": float(loss(params, system.tokens(0, group))),
        "second_without_update": float(loss(params, then)),
        "second": {},
    }
    for members in ([[0]] if groups == 1 else [[0], [1], [0, 1]]):
        first = jnp.concatenate([system.tokens(0, g) for g in members])
        out["second"]["+".join(map(str, members))] = float(loss_after(params, first, then))
    del loss, loss_after
    gc.collect()
    return out


def host_clocks() -> Dict[str, float]:
    """The host's clocks and its CPU accounting at one instant: a run that
    reads far off says from these whether the machine stood still (steal, a
    monotonic clock that ran ahead of the raw one) or the program waited."""
    out = {
        "monotonic": time.monotonic(),
        "raw": time.clock_gettime(time.CLOCK_MONOTONIC_RAW),
        "boottime": time.clock_gettime(time.CLOCK_BOOTTIME),
        "realtime": time.time(),
    }
    try:
        fields = Path("/proc/stat").read_text().splitlines()[0].split()[1:]
        ticks = os.sysconf("SC_CLK_TCK")
        out["cpu_busy_s"] = sum(int(f) for f in fields[:3] + fields[5:7]) / ticks
        out["cpu_iowait_s"] = int(fields[4]) / ticks
        out["cpu_steal_s"] = int(fields[7]) / ticks
    except (OSError, ValueError, IndexError):
        pass
    return out


class HostPulse:
    """A thread that sleeps 5 ms at a time through the window and remembers
    its longest oversleep: if the whole host stood still (a paused VM, a
    starved core) it oversleeps with everything else; if only the step loop
    was blocked (an RPC, a lock) it does not. Read beside the slowest steps,
    it says which of the two a far-off run met. ``on_beat`` is called at every
    wake-up (the memory gauge's sample)."""

    def __init__(self, on_beat: Callable[[], None]) -> None:
        self.on_beat = on_beat
        self.longest = 0.0
        self.longest_at = 0.0  # monotonic clock, at the end of that sleep
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._beat, daemon=True)

    def _beat(self) -> None:
        last = time.monotonic()
        while not self._stop.wait(0.005):
            now = time.monotonic()
            if now - last - 0.005 > self.longest:
                self.longest, self.longest_at = now - last - 0.005, now
            self.on_beat()
            last = time.monotonic()

    def __enter__(self) -> "HostPulse":
        self._thread.start()
        return self

    def __exit__(self, *exc: object) -> None:
        self._stop.set()
        self._thread.join()


class Tracer:
    """The program's capture control (``tracing.start_capture`` /
    ``stop_capture``: the profiler, host spans and no Python frames) around a
    traced window; the trace is reduced to a small dictionary and its files
    are deleted. ``capture`` is what ``stop_capture()`` returned, less the
    directory: the journal's ``events`` and the registry's ``counters`` of
    the window, the ``clock`` anchors and ``dropped``."""

    def __init__(self, enabled: bool) -> None:
        self.enabled = enabled
        self.dir: Optional[str] = None
        self.capture: Optional[Dict[str, Any]] = None

    def __enter__(self) -> "Tracer":
        if self.enabled:
            from torchft_tpu import tracing

            self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            tracing.start_capture(self.dir)
        return self

    def __exit__(self, *exc: object) -> None:
        if self.enabled:
            from torchft_tpu import tracing

            self.capture = tracing.stop_capture()
            del self.capture["trace_dir"]

    def reduce(self, keep_in: Optional[Path] = None) -> Optional[Dict[str, Any]]:
        if not self.enabled or self.dir is None:
            return None
        from chipbench import trace_reduce

        try:
            files = sorted(Path(self.dir).glob("plugins/profile/*/*.xplane.pb"))
            if not files:
                return None
            space = trace_reduce.load_xplane(files[-1])
            if keep_in is not None:
                keep_in.mkdir(parents=True, exist_ok=True)
                n_events = sum(len(l["events"]) for p in space["planes"] for l in p["lines"])
                (keep_in / f"trace_sample_{os.getpid()}.json").write_text(
                    json.dumps(space if n_events < 8000 else trace_reduce.sample(space))
                )
            return trace_reduce.reduce(space)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class Run:
    """One run's arguments and shared state, handed to the job."""

    def __init__(
        self, cell: Dict[str, Any], config: Dict[str, Any], architecture: ModuleType,
        traffic: Dict[str, Any], seed: int, seconds: float, trace: int,
        started: float, rehearsal: bool, out_dir: Optional[Path],
    ) -> None:
        self.cell, self.config, self.traffic = cell, config, traffic
        self.architecture = architecture  # the file the config's model_type names
        self.seed, self.seconds = seed, seconds
        # ``--trace``: 1 traces the one window; 2 measures as 0 does and then
        # traces a tail of the same traffic in the same process.
        self.trace, self.tail = trace == 1, trace == 2
        self.started, self.rehearsal, self.out_dir = started, rehearsal, out_dir
        self.chips = int(cell["chips"])

    def traced_seconds(self) -> float:
        """A traced window is short (traces are large and the tracer slows
        the host): ``trace_seconds`` of the traffic mix."""
        return min(self.seconds, float(self.traffic.get("trace_seconds", self.seconds)))

    def window_seconds(self) -> float:
        """The window a run's end-to-end numbers come from: the traced one
        under ``--trace 1``, else the whole of ``--seconds``."""
        return self.traced_seconds() if self.trace else self.seconds


def run_one_process(run: Run, make_job: Callable[..., Any]) -> Dict[str, Any]:
    """Set-up, warm-up, window and checks of a job that lives in this one
    process on this one chip. Returns the outcome ``run.py`` prints from."""
    import jax
    import numpy as np

    from chipbench.model import System

    devices = require_devices(run.chips, run.rehearsal)
    cache_dir = enable_compile_cache()
    ledger = CompileLedger()
    spans = SpanLog()
    traffic = run.traffic
    system = System(run.config, run.architecture, traffic, run.seed)
    say(f"device: {devices[0].platform} {devices[0].device_kind} x{len(devices)}; cache {cache_dir}")

    params = system.init_params()
    # Sampled and said, never judged: the yardstick's own share of the chip.
    setup_gauge = MemoryGauge(devices)
    with HostPulse(setup_gauge.sample):
        system.reference = reference_losses(system, params)
    setup_memory = setup_gauge.report()
    say(f"reference: held at most {setup_gauge.held} bytes (arrays and scratch at one instant, "
        f"{setup_gauge.samples} samples), live arrays at most {setup_memory['arrays_peak_bytes']}, "
        f"on a chip of {setup_memory['bytes_limit']}")
    job = make_job(run, system, params, spans)
    del params
    problems: List[str] = []
    try:
        steps_per_unit = int(traffic["steps_per_unit"])
        # steps_in_flight counts the step that runs and those queued behind
        # it: after dispatching step i the loop waits for step i - lag.
        lag = int(traffic.get("steps_in_flight", 2)) - 1
        losses: List[Any] = []
        step_ends: List[float] = []

        def run_unit(_unit: int) -> None:
            for _ in range(steps_per_unit):
                i = len(losses)
                with spans.span("chipbench/step"):
                    losses.append(job.step(i))
                if i >= lag:
                    # At most `lag` steps in flight: wait for an older step
                    # to be READY (no value leaves the device).
                    with spans.span("chipbench/ready"):
                        losses[i - lag].block_until_ready()
                step_ends.append(time.monotonic())

        def fetch() -> None:
            with spans.span("chipbench/fetch"):
                jax.block_until_ready(job.live_state())
                if losses:
                    float(losses[-1])

        # Warm-up: every shape this cell's traffic uses, and no other.
        for unit in range(int(traffic["warmup_units"])):
            run_unit(unit)
        fetch()
        problems += reference_check(system, [float(x) for x in losses[:2]])
        gc.collect()
        gc.freeze()  # the warmed-up heap is not walked again inside the window

        compile_setup = ledger.snapshot()

        def measure(seconds: float, traced: bool, measured: Optional[Dict[str, Any]]):
            """One window of whole units and everything read from it: its
            observations, failed operations, problems and device report.
            Called once for the measured window and, under ``--trace 2``,
            once more for the traced tail (``measured``: what the first gave).
            The steps of a window pass the job's checks from its own offset."""
            first = len(losses)
            gauge = MemoryGauge(devices)
            compiled_before = ledger.compiles
            counters_before = counter_sums()
            clocks_before = host_clocks()
            with HostPulse(gauge.sample) as pulse, Tracer(traced) as tracer:
                window = run_window(
                    run_unit, fetch, seconds, int(traffic.get("min_units", 1))
                )
            clocks = {k: v - clocks_before[k] for k, v in host_clocks().items()}
            # Set-up ends where the measured window opens; a tail has none.
            setup_s = measured["setup_s"] if measured else window.opened - run.started
            counters = counter_deltas(counters_before, counter_sums())
            compiled_inside = ledger.compiles - compiled_before
            trace = tracer.reduce(run.out_dir)

            steps = len(losses) - first
            values = np.asarray(jax.device_get(losses), dtype=np.float64)
            faults = window_checks(values, compiled_inside)
            failed, job_problems = job.check(first, steps, window.units)
            faults += job_problems
            device = gauge.report()
            faults += memory_problems(device)
            obs: Dict[str, Any] = {
                "chips": run.chips,
                "tokens": steps * system.tokens_per_step,
                "window_s": window.seconds,
                "steps": steps,
                "units": window.units,
                "setup_s": setup_s,
                "arrays_peak_bytes": device.pop("arrays_peak_bytes"),
                "held_peak_bytes": device.pop("held_peak_bytes"),
                "scratch_peak_bytes": device.pop("scratch_peak_bytes"),
                "compile": compile_setup,
                "counters": counters,
                "spans": spans.totals(window.opened, window.closed),
                "step_ends": [t - window.opened for t in step_ends[first:]],
                "host_stall_s": pulse.longest,
                "host_stall_at_s": pulse.longest_at - window.opened,
                "host_clocks": clocks,
                "memory": {k: device.pop(k) for k in ("bytes_limit", "samples", "sample_seconds")},
                "steps_per_unit": steps_per_unit,
                "trace": trace,
                "flops_per_token": run.architecture.train_flops_per_token(run.config, system.seq),
                "peaks": None if run.rehearsal else peaks_for(devices[0].device_kind),
                # For the readers that count what a kernel needs from the shapes.
                "config": run.config,
                "batch": system.batch,
                "seq": system.seq,
            }
            if measured is not None:  # the traced tail of ``--trace 2``
                obs["capture"] = tracer.capture
                obs["measured"] = {k: measured[k] for k in ("tokens", "window_s", "steps", "units")}
            obs.update(job.observations())
            say(
                f"window: {steps} steps in {window.units} units, {window.seconds:.3f}s "
                f"between fetches, setup {setup_s:.1f}s, compile in set-up "
                f"{compile_setup['seconds']:.1f}s in {compile_setup['count']} "
                f"(cache hits {compile_setup['cache_hits']}, misses {compile_setup['cache_misses']}); "
                f"slowest steps {slowest_steps(obs['step_ends'])}; longest host "
                f"oversleep {1e3 * pulse.longest:.0f}ms at {obs['host_stall_at_s']:.1f}s; "
                f"clocks over the window {json.dumps({k: round(v, 3) for k, v in clocks.items()})}; "
                f"{obs['memory']['samples']} memory samples took {1e3 * obs['memory']['sample_seconds']:.1f}ms"
            )
            if run.out_dir is not None:
                run.out_dir.mkdir(parents=True, exist_ok=True)
                level = 1 if run.trace else 2 if traced else 0
                name = f"series_{run.cell['name']}_{run.seed}_{level}_{os.getpid()}.json"
                (run.out_dir / name).write_text(json.dumps({
                    "step_ends": obs["step_ends"], "losses": values.tolist()[first:],
                    "window_s": window.seconds, "steps": steps, "obs": {
                        k: v for k, v in obs.items() if k not in ("step_ends", "trace")
                    },
                }))
            return obs, failed, faults, device

        obs, failed, faults, device = measure(run.window_seconds(), run.trace, None)
        problems += faults
        tail = None
        if run.tail:
            # Every number of the measured window is taken. One capture is
            # thrown away, so that the profiler's first start falls into no
            # number; then the same traffic once more, traced.
            cold = Tracer(True)
            with cold:
                pass
            shutil.rmtree(cold.dir, ignore_errors=True)
            tail, _, faults, tail_device = measure(run.traced_seconds(), True, obs)
            # ``correct``, ``attempted`` and ``failed`` are the measured
            # window's; what the tail's own checks found is said beside them.
            for fault in faults:
                say(f"TRACED TAIL NOT CORRECT: {fault}")
            # The line's peak is the whole run's.
            device["memory_peak_bytes"] = max(
                device["memory_peak_bytes"], tail_device["memory_peak_bytes"]
            )
    finally:
        job.close()
    return {
        "correct": not problems, "problems": problems,
        "attempted": obs["steps"], "failed": failed, "obs": obs, "device": device,
        "tail": tail,
    }
