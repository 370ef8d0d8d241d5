"""Tests of the benchmark itself, on the CPU; tier-1 collects them (this
directory is one of BENCHMARK.json's ``paths``, so it holds nothing else and
later PRs add to it without editing it). Alone:

    JAX_PLATFORMS=cpu python -m pytest tests/chipbench_tests -q

They guard the yardstick, not the program: BENCHMARK.json against its
contract, the whole-unit window, the trace reduction against a recorded
trace, the float32 reference against models/llama.py, discovery of new cells
and metrics by files alone, and a rehearsal of every job at toy size.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import spec, trace_reduce  # noqa: E402
from chipbench.window import run_window  # noqa: E402

HERE = ROOT / "chipbench" / "fixtures"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def copy_benchmark(to: Path) -> None:
    """The directories of BENCHMARK.json's ``paths``, and nothing else."""
    for path in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / path, to / path, ignore=shutil.ignore_patterns("__pycache__"))


def copy_with_parked_cell(to: Path) -> Path:
    """A copy of the benchmark whose BENCHMARK.json also lists the parked
    four-chip cell (chipbench/fixtures/parked_hsdp.json: its entries as they
    stood before PR 24 took the cell out). The cell's files are rehearsed
    from this copy, so that the PR that lists it again finds them working."""
    copy_benchmark(to)
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    parked = json.loads((HERE / "parked_hsdp.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        data[key] += parked[key]
    (to / "BENCHMARK.json").write_text(json.dumps(data))
    return to


def test_benchmark_json_is_sound():
    bench = spec.Benchmark(ROOT)
    assert spec.problems(bench) == []
    assert len(json.dumps(bench.data)) < 64 * 1024
    for cell in bench.data["workloads"]:
        traffic = bench.traffic(cell["traffic"])
        assert hasattr(bench.job(traffic["job"]), "run")
        assert bench.config(cell["config"])["num_hidden_layers"] >= 1
        for group in ("end_to_end", "per_layer"):
            for metric in bench.metrics_of(cell["name"], group):
                assert callable(bench.reader(group, metric["name"]).read), metric["name"]


def test_problems_catches_a_moves_that_a_cell_does_not_report(tmp_path):
    copy = copy_with_parked_cell(tmp_path / "repo")
    assert spec.problems(spec.Benchmark(copy)) == []  # the parked entries still fit
    data = json.loads((copy / "BENCHMARK.json").read_text())
    for metric in data["per_layer"]:
        if metric["name"] == "wire_sync_ms":
            metric["moves"] = "tokens_per_s"  # which the hsdp cell does not report
    (copy / "BENCHMARK.json").write_text(json.dumps(data))
    found = spec.problems(spec.Benchmark(copy))
    assert any("wire_sync_ms moves tokens_per_s" in p for p in found), found


# -- the window ----------------------------------------------------------------


class FakeClock:
    def __init__(self) -> None:
        self.now = 100.0

    def __call__(self) -> float:
        return self.now


@pytest.mark.parametrize("step_seconds", [0.24, 0.2400001, 0.31, 1.0])
def test_window_is_whole_rounds_between_two_fetches(step_seconds):
    """A DiLoCo unit is a round of 32 steps. However the clock falls, the
    window ends on a round boundary and the time is fetch to fetch."""
    clock, log = FakeClock(), []

    def run_unit(unit: int) -> None:
        for step in range(32):
            clock.now += step_seconds
            log.append(("step", unit, step))

    def fetch() -> None:
        clock.now += 0.05  # a fetch takes time too, and it is inside the measure
        log.append(("fetch", clock.now))

    window = run_window(run_unit, fetch, seconds=30.0, clock=clock)
    steps = [e for e in log if e[0] == "step"]
    assert len(steps) == 32 * window.units, "cut mid-round"
    assert steps[-1][2] == 31
    assert log[0][0] == "fetch" and log[-1][0] == "fetch"
    assert sum(1 for e in log if e[0] == "fetch") == 2, "a fetch inside the window"
    # Opened right after the first fetch returned, closed after the last.
    assert window.opened == pytest.approx(log[0][1])
    assert window.closed == pytest.approx(log[-1][1])
    assert window.seconds == pytest.approx(32 * window.units * step_seconds + 0.05)
    # No more rounds than needed to pass --seconds, and never fewer.
    assert (window.units - 1) * 32 * step_seconds < 30.0 <= window.units * 32 * step_seconds + 1e-9
    tokens_per_s = 32 * window.units * 8192 / window.seconds
    assert tokens_per_s != pytest.approx(32 * window.units * 8192 / 30.0), "divided by --seconds"


@pytest.mark.parametrize("unit_seconds,min_units,want", [(36.0, 2, 2), (36.0, 1, 1), (7.0, 2, 5)])
def test_window_runs_at_least_min_units(unit_seconds, min_units, want):
    """A fleet step longer than the window: the traffic file's ``min_units``
    keeps the window open for a second one; where units are short the clock
    decides as before."""
    clock = FakeClock()

    def run_unit(_unit: int) -> None:
        clock.now += unit_seconds

    window = run_window(run_unit, lambda: None, 30.0, min_units, clock)
    assert window.units == want
    assert window.seconds == pytest.approx(want * unit_seconds)


# -- the trace reduction -------------------------------------------------------


def brute_force(space):
    """The same quantities as trace_reduce.reduce by another method: cut the
    window at every event boundary and ask of each elementary segment whether
    any op covers it (no sorting-and-merging of intervals)."""
    import numpy as np

    spans = [
        (e[0], e[1], e[1] + e[2]) for p in space["planes"] if p["name"].startswith("/host:")
        for l in p["lines"] for e in l["events"]
    ]
    fetch_ends = sorted(end for name, _, end in spans if name == "chipbench/fetch")
    lo, hi = fetch_ends[0], fetch_ends[-1]
    out = []
    for plane in space["planes"]:
        if not plane["name"].startswith("/device:TPU:"):
            continue
        events = [e for l in plane["lines"] if l["name"] == "XLA Ops" for e in l["events"]]
        starts = np.clip(np.array([e[1] for e in events]), lo, hi)
        ends = np.clip(np.array([e[1] + e[2] for e in events]), lo, hi)
        cuts = np.unique(np.concatenate([starts, ends, [lo, hi]]))
        mids = (cuts[:-1] + cuts[1:]) / 2
        covered = np.zeros(len(mids), dtype=bool)
        for a, b in zip(starts, ends):
            covered |= (mids >= a) & (mids < b)
        busy = float(np.sum((cuts[1:] - cuts[:-1])[covered]))
        sums = {}
        for e, a, b in zip(events, starts, ends):
            if e[4] != "container" and b > a:
                sums[e[0]] = sums.get(e[0], 0.0) + float(b - a)
        kernels = sum(float(b - a) for e, a, b in zip(events, starts, ends) if e[4] == "kernel")
        out.append((busy, sums, kernels))
    return lo, hi, out


def test_trace_reduce_against_the_recorded_trace():
    space = json.loads((HERE / "small_trace.json").read_text())
    expect = json.loads((HERE / "small_trace.expect.json").read_text())
    got = trace_reduce.reduce(space)
    lo, hi, devices = brute_force(space)
    n = len(devices)
    assert got["devices"] == n >= 1
    assert got["window_s"] == pytest.approx((hi - lo) * 1e-9)
    want_busy = sum(d[0] for d in devices) / n * 1e-9
    assert got["busy_s"] == pytest.approx(want_busy, rel=1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    want_ops = {}
    for _, sums, _ in devices:
        for name, ns in sums.items():
            want_ops[name] = want_ops.get(name, 0.0) + ns * 1e-9 / n
    assert dict(got["ops"]) == pytest.approx(want_ops, rel=1e-9)
    # The recording nests ops in containers (the scanned layers' `while`):
    # containers are not listed, and the union counts the overlap once.
    assert any(e[4] == "container" for p in space["planes"] for l in p["lines"] for e in l["events"])
    assert not any(name.startswith("while") for name, _ in got["ops"])
    # Per-kernel sums: the Pallas calls, by program.
    want_kernels = sum(d[2] for d in devices) / n * 1e-9
    assert sum(s for rows in got["kernels"].values() for _, s in rows) == pytest.approx(want_kernels)
    assert want_kernels > 0 and set(got["kernels"]) == {"jit_plain"}
    assert sum(s for _, s in got["modules"]) == pytest.approx(sum(want_ops.values()))
    # Every idle second is attributed, and to the host span open at the time.
    idle = got["window_s"] - got["busy_s"]
    assert sum(s for _, s in got["gaps"]) == pytest.approx(idle, rel=1e-6)
    assert got["gaps"][0][0] == expect["longest_gap_owner"]


def test_gap_goes_to_the_innermost_open_host_span():
    ops = [["a f32[2]", 100, 300, "jit_f", "op"], ["k bf16[2]", 900, 100, "jit_f", "kernel"]]
    spans = [
        ["chipbench/fetch", 0, 50, "", "span"], ["chipbench/step", 60, 1000, "", "span"],
        ["tpuft::manager::should_commit", 500, 300, "", "span"],
        ["chipbench/fetch", 1100, 100, "", "span"],
    ]
    got = trace_reduce.reduce({"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": spans}]},
    ]})
    # Window 50..1200; busy 100..400 and 900..1000; the long gap 400..900 has
    # its middle (650) inside the program's span, which is inside the step's.
    assert got["window_s"] == pytest.approx(1150e-9) and got["busy_s"] == pytest.approx(400e-9)
    assert got["gaps"][0] == ["tpuft::manager::should_commit", pytest.approx(500e-9)]
    assert dict(got["gaps"])["chipbench/fetch"] == pytest.approx(200e-9)  # 1000..1200


def test_union_counts_overlap_once():
    total, merged = trace_reduce.union_seconds([(0, 10), (5, 12), (20, 21), (21, 30)])
    assert total == 22 and merged == [(0, 12), (20, 30)]


# -- the reference -------------------------------------------------------------


def tiny_system(dtype: str):
    from chipbench.model import System

    bench = spec.Benchmark(ROOT)
    overlay = json.loads((HERE / "rehearsal.json").read_text())
    config = {**bench.config("mistral-7b-v0.3-1chip"), **overlay["config"]}
    config["run"] = {**config["run"], **overlay["run"], "dtype": dtype}
    traffic = {**bench.traffic("plain"), **overlay["traffic"]["plain"]}
    return System(config, traffic, seed=2**31 + 12345)


def test_reference_agrees_with_the_program_in_float32():
    """Forward loss of models/llama.py (flash in interpret mode, scanned,
    remat, chunked loss) against the plain float32 reference on the same
    seeded weights and tokens. Tolerance 1e-5 relative: both sides compute in
    float32 and differ only in the order of their sums (a mean of 128 token
    losses near 6.8); a dropped term (a missing norm, a wrong rotary pairing,
    no causal mask) moves the loss by 1e-3 or more."""
    from chipbench import reference

    system = tiny_system("float32")
    params = system.init_params()
    tokens = system.tokens(0)
    got = float(system.loss_fn(params, tokens))
    want = float(reference.make_loss(system.config)(params, tokens))
    assert abs(got - want) / want < 1e-5, (got, want)
    # And it is a function of the inputs: other tokens, another loss.
    other = float(reference.make_loss(system.config)(params, system.tokens(1)))
    assert abs(other - want) > 1e-4


def test_reference_catches_a_lower_precision():
    """The same comparison with the program in bf16 differs by rounding alone
    (under 2^-8 at this toy size, where a mean of 128 token losses averages
    little; on the chip 8192 tokens bring it under 2e-5 and the tolerance is
    2^-12); were the rotary pairing or the mask wrong it would not."""
    from chipbench import reference

    system = tiny_system("bfloat16")
    params = system.init_params()
    tokens = system.tokens(0)
    got = float(system.loss_fn(params, tokens))
    want = float(reference.make_loss(system.config)(params, tokens))
    relative = abs(got - want) / want
    assert 0 < relative < 2**-8, (got, want)
    broken = {**system.config, "rope_theta": 10.0}
    wrong = float(reference.make_loss(broken)(params, tokens))
    assert abs(wrong - want) / want > 1e-5


def test_reference_update_is_the_first_adamw_step():
    """The reference's hand-written first step against optax.adamw on the
    program's own gradient, float32 at toy size: the same parameters to 1e-5,
    and the second loss tells that update from none, from one of twice the
    size and from one that lost the weight decay."""
    import jax
    import optax

    from chipbench import harness, reference

    system = tiny_system("float32")
    params = system.init_params()
    want = system.reference = harness.reference_losses(system, params)
    tx = system.tx
    grads = jax.grad(system.loss_fn)(params, system.tokens(0))
    updates, _ = tx.update(grads, tx.init(params), params)
    stepped = optax.apply_updates(params, updates)
    got = float(system.loss_fn(stepped, system.tokens(1)))
    tol = system.config["reference_tolerance"]["update_relative"]
    assert abs(got - want["second"]["0"]) / want["second"]["0"] < tol
    assert harness.reference_check(system, [want["first"], got]) == []
    assert harness.reference_check(system, [want["first"], want["second_without_update"]]) != []
    count = float(system.batch * system.seq)
    total = reference.grad_sum(params, system.tokens(0), system.config)
    mine = reference.first_adamw_step(params, total, count, system.config)
    worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(abs(a - b).max()), mine, stepped)))
    assert worst < 1e-5, worst
    for wrong in ({"learning_rate": 2 * system.config["optimizer"]["learning_rate"]}, {"weight_decay": 0.0}):
        config = {**system.config, "optimizer": {**system.config["optimizer"], **wrong}}
        loss = float(reference.make_loss_after_first_update(config)(params, system.tokens(0), system.tokens(1)))
        assert abs(loss - got) / got > tol, wrong


class FakeDevice:
    platform, device_kind = "tpu", "fake"

    def __init__(self, readings) -> None:
        self.readings = iter(readings)
        self.last = None

    def memory_stats(self):
        self.last = next(self.readings, self.last)
        return self.last


def test_memory_gauge_takes_one_instant_and_never_clips():
    """Arrays peak at one sample and scratch at another: what the chip held is
    the largest SUM of one sample (8), not the sum of two peaks (6 + 5, or the
    runtime's 7 + 9); the end-to-end figure is the runtime's own peak of
    arrays; and a reading above the chip's limit is a problem, not a number
    to clip."""
    from chipbench import harness

    stats = lambda a, r: {"bytes_in_use": a, "bytes_reserved": r, "bytes_limit": 10,
                          "peak_bytes_in_use": 7, "peak_bytes_reserved": 9}
    gauge = harness.MemoryGauge([FakeDevice([stats(6, 1), stats(3, 5), stats(2, 2)])])
    gauge.sample(); gauge.sample()
    report = gauge.report()
    assert (report["held_peak_bytes"], report["arrays_peak_bytes"], report["scratch_peak_bytes"]) == (8, 7, 5)
    assert report["memory_peak_bytes"] == 8 and harness.memory_problems(report) == []
    over = harness.MemoryGauge([FakeDevice([stats(6, 5)])])
    assert harness.memory_problems(over.report()) != []


def test_seed_decides_weights_and_tokens():
    import numpy as np

    a, b = tiny_system("float32"), tiny_system("float32")
    assert np.array_equal(np.asarray(a.tokens(3)), np.asarray(b.tokens(3)))
    assert not np.array_equal(np.asarray(a.tokens(3)), np.asarray(a.tokens(4)))
    assert not np.array_equal(np.asarray(a.tokens(3, 0)), np.asarray(a.tokens(3, 1)))


# -- discovery: new cells and metrics are new files ----------------------------


def test_a_cell_a_traffic_mix_a_job_and_a_metric_are_added_as_files(tmp_path):
    copy = tmp_path / "repo"
    copy_benchmark(copy)
    before = {
        p: p.read_bytes() for p in (copy / "chipbench").rglob("*") if p.is_file()
    }
    extra = copy / "morebench"  # a later PR's own directory
    for sub in ("configs", "traffic", "jobs", "layer_metrics"):
        (extra / sub).mkdir(parents=True)
    config = json.loads((copy / "chipbench/configs/mistral-7b-v0.3-1chip.json").read_text())
    config["name"] = "throwaway-1chip"
    (extra / "configs/throwaway-1chip.json").write_text(json.dumps(config))
    (extra / "traffic/plain-seq8k.json").write_text(json.dumps(
        {**json.loads((copy / "chipbench/traffic/plain.json").read_text()), "job": "echo", "batch": 1, "seq": 8192}
    ))
    (extra / "jobs/echo.py").write_text("def run(run):\n    return {'traffic': run.traffic}\n")
    (extra / "layer_metrics/steps_in_window.py").write_text("def read(obs):\n    return obs['steps']\n")
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    data["paths"].append("morebench")
    data["configs"].append({
        "name": "throwaway-1chip", "source": "https://example.org/x", "why": "test",
        "file": "morebench/configs/throwaway-1chip.json", "reduced": ["num_hidden_layers"],
    })
    data["workloads"].append({
        "name": "throwaway.seq8k", "config": "throwaway-1chip", "traffic": "plain-seq8k",
        "chips": 1, "why": "test",
    })
    for metric in data["end_to_end"]:
        if metric["name"] == "tokens_per_s":
            metric["workloads"].append("throwaway.seq8k")
    data["per_layer"].append({
        "name": "steps_in_window", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "entry points", "moves": "tokens_per_s",
        "workloads": ["throwaway.seq8k"],
    })
    (copy / "BENCHMARK.json").write_text(json.dumps(data))

    bench = spec.Benchmark(copy)
    assert spec.problems(bench) == []
    cell = bench.cell("throwaway.seq8k")
    traffic = bench.traffic(cell["traffic"])
    assert traffic["seq"] == 8192
    assert bench.job(traffic["job"]).run(type("R", (), {"traffic": traffic})) == {"traffic": traffic}
    assert bench.config(cell["config"])["name"] == "throwaway-1chip"
    names = [m["name"] for m in bench.metrics_of("throwaway.seq8k", "per_layer")]
    assert names == ["compile_s", "hbm_held_gib", "hbm_scratch_gib", "steps_in_window"]
    assert bench.reader("per_layer", "steps_in_window").read({"steps": 7}) == 7
    # The old cells still see their own metrics only, and no file was edited.
    assert "steps_in_window" not in [
        m["name"] for m in bench.metrics_of("mistral7b-1chip.plain", "per_layer")
    ]
    after = {p: p.read_bytes() for p in before}
    assert after == before


# -- the jobs, rehearsed -------------------------------------------------------


def run_cell(workload: str, *extra: str, rehearse: bool = True, root: Path = ROOT):
    """One run of ``root``'s benchmark (a copy finds the program through
    PYTHONPATH and keeps its compile cache in itself)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TPUFT_LOG": "warn", "PYTHONPATH": str(ROOT)}
    env.pop("XLA_FLAGS", None)
    if root != ROOT:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    command = [
        sys.executable, str(root / "chipbench/run.py"), "--workload", workload,
        "--seed", str(2**31 + 7), "--seconds", "1", *extra,
    ]
    if rehearse:
        command += ["--rehearse", str(HERE / "rehearsal.json")]
    return subprocess.run(command, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("workload,trace", [
    ("mistral7b-1chip.plain", "0"),
    ("mistral7b-1chip.ftddp", "0"),
    ("mistral7b-1chip.diloco-fp8", "0"),
    ("mistral7b-1chip.diloco-fp8", "1"),
    ("mistral7b-2x2.hsdp", "0"),  # parked: rehearsed from a copy that lists it
])
def test_rehearsal_prints_the_contract_line(workload, trace, tmp_path):
    root = ROOT
    if workload not in [w["name"] for w in spec.Benchmark(ROOT).data["workloads"]]:
        root = copy_with_parked_cell(tmp_path / "repo")
    done = run_cell(workload, "--trace", trace, root=root)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == RESULT_KEYS | {"rehearsal"}
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert set(line["device"]) == DEVICE_KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    bench = spec.Benchmark(root)
    group = "per_layer" if trace == "1" else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in bench.metrics_of(workload, group)}
    assert line["metrics"], "no metric on the line"
    for name, entry in line["metrics"].items():
        assert set(entry) == {"value", "unit"} and entry["unit"] == allowed[name]
        assert isinstance(entry["value"], float)
    if trace == "0":
        assert set(line["metrics"]) == set(allowed)
    if workload.endswith("diloco-fp8"):
        assert line["attempted"] % 8 == 0, "a window of whole rounds"


def test_off_chip_there_is_no_result():
    done = run_cell("mistral7b-1chip.plain", "--trace", "0", rehearse=False)
    assert done.returncode != 0
    assert "no result" in done.stderr
    assert not any(l.startswith("{") for l in done.stdout.splitlines())


def test_four_chip_cell_refuses_a_host_without_chips(tmp_path):
    root = copy_with_parked_cell(tmp_path / "repo")
    done = run_cell("mistral7b-2x2.hsdp", "--trace", "0", rehearse=False, root=root)
    assert done.returncode != 0
    assert not any(l.startswith("{") for l in done.stdout.splitlines())
