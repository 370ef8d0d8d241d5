"""Tests of the benchmark itself, on the CPU; tier-1 collects them (this
directory is one of BENCHMARK.json's ``paths``, so it holds nothing else and
later PRs add to it without editing it). Alone:

    JAX_PLATFORMS=cpu python -m pytest tests/chipbench_tests -q

They guard the yardstick, not the program: BENCHMARK.json against its
contract, the whole-unit window, the trace reduction against a recorded
trace, the float32 reference against models/llama.py (and its blocked form
against its unblocked one), discovery of new cells, metrics and architectures
by files alone, and a rehearsal of every job at toy size.
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


@pytest.fixture
def bench_root() -> Path:
    """The checkout whose BENCHMARK.json a test reads: this one. Every test of
    this directory that reads it and starts no process takes it from here, so
    that test_another_architecture.py can call the same test on a copy that
    lists one more cell: a test that pins what the lists hold TODAY, where it
    means a rule, fails there and not in the next PR's hands."""
    return ROOT


def copy_benchmark(to: Path, root: Path = ROOT) -> None:
    """The directories of BENCHMARK.json's ``paths``, and nothing else."""
    for path in json.loads((root / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(root / path, to / path, ignore=shutil.ignore_patterns("__pycache__"))


def copy_with_parked_cell(to: Path, root: Path = ROOT) -> Path:
    """A copy of the benchmark whose BENCHMARK.json also lists the parked
    four-chip cell (chipbench/fixtures/parked_hsdp.json: its entries as they
    stood before PR 24 took the cell out). The cell's files are rehearsed
    from this copy, so that the PR that lists it again finds them working."""
    copy_benchmark(to, root)
    data = json.loads((root / "BENCHMARK.json").read_text())
    parked = json.loads((HERE / "parked_hsdp.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        data[key] += parked[key]
    (to / "BENCHMARK.json").write_text(json.dumps(data))
    return to


def list_cell(
    copy: Path, name: str, config: str, traffic: str, configs=(), metrics=("tokens_per_s",),
    root: Path = ROOT,
) -> None:
    """Writes the copy's BENCHMARK.json (``root``'s) with a directory
    ``morebench``, one more cell (and ``configs`` entries), listed under
    ``metrics``."""
    data = json.loads((root / "BENCHMARK.json").read_text())
    data["paths"].append("morebench")
    data["configs"] += list(configs)
    data["workloads"].append(
        {"name": name, "config": config, "traffic": traffic, "chips": 1, "why": "test"})
    for metric in data["end_to_end"] + data["per_layer"]:
        if metric["name"] in metrics:
            metric["workloads"].append(name)
    (copy / "BENCHMARK.json").write_text(json.dumps(data))


def test_benchmark_json_is_sound(bench_root):
    bench = spec.Benchmark(bench_root)
    assert spec.problems(bench) == []
    assert len(json.dumps(bench.data)) < 64 * 1024
    for cell in bench.data["workloads"]:
        traffic = bench.traffic(cell["traffic"])
        assert hasattr(bench.job(traffic["job"]), "run")
        assert bench.config(cell["config"])["num_hidden_layers"] >= 1
        for group in ("end_to_end", "per_layer"):
            for metric in bench.metrics_of(cell["name"], group):
                assert callable(bench.reader(group, metric["name"]).read), metric["name"]


@pytest.mark.parametrize("edit,want", [
    ({"trace_in_run": True}, None),
    ({"trace_in_run": False}, None),
    ({"trace_in_run": "yes"}, "trace_in_run must be true or false"),
    ({"trace_in_run": True, "stray": 1}, "top-level keys"),
])
def test_trace_in_run_is_an_optional_boolean_and_no_other_key_is_taken(edit, want, tmp_path, bench_root):
    copy = tmp_path / "repo"
    copy_benchmark(copy, bench_root)
    data = json.loads((bench_root / "BENCHMARK.json").read_text())
    data.pop("trace_in_run", None)
    (copy / "BENCHMARK.json").write_text(json.dumps({**data, **edit}))
    found = spec.problems(spec.Benchmark(copy))
    if want is None:
        assert found == []
    else:
        assert len(found) == 1 and want in found[0], found


def test_problems_catches_a_moves_that_a_cell_does_not_report(tmp_path, bench_root):
    copy = copy_with_parked_cell(tmp_path / "repo", bench_root)
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


def tiny_system(dtype: str, root: Path):
    from chipbench.model import System

    bench = spec.Benchmark(root)
    overlay = json.loads((HERE / "rehearsal.json").read_text())
    config = {**bench.config("mistral-7b-v0.3-1chip"), **overlay["config"]}
    config["run"] = {**config["run"], **overlay["run"], "dtype": dtype}
    traffic = {**bench.traffic("plain"), **overlay["traffic"]["plain"]}
    return System(config, bench.architecture(config["model_type"]), traffic, seed=2**31 + 12345)


def test_reference_agrees_with_the_program_in_float32(bench_root):
    """Forward loss of models/llama.py (flash in interpret mode, scanned,
    remat, chunked loss) against the plain float32 reference on the same
    seeded weights and tokens. Tolerance 1e-5 relative: both sides compute in
    float32 and differ only in the order of their sums (a mean of 128 token
    losses near 6.8); a dropped term (a missing norm, a wrong rotary pairing,
    no causal mask) moves the loss by 1e-3 or more."""
    from chipbench import reference

    system = tiny_system("float32", bench_root)
    params = system.init_params()
    tokens = system.tokens(0)
    got = float(system.loss_fn(params, tokens))
    want = float(reference.make_loss(system.architecture, system.config)(params, tokens))
    assert abs(got - want) / want < 1e-5, (got, want)
    # And it is a function of the inputs: other tokens, another loss.
    other = float(reference.make_loss(system.architecture, system.config)(params, system.tokens(1)))
    assert abs(other - want) > 1e-4


def test_reference_catches_a_lower_precision(bench_root):
    """The same comparison with the program in bf16 differs by rounding alone
    (under 2^-8 at this toy size, where a mean of 128 token losses averages
    little; on the chip 8192 tokens bring it under 2e-5 and the tolerance is
    2^-12); were the rotary pairing or the mask wrong it would not."""
    from chipbench import reference

    system = tiny_system("bfloat16", bench_root)
    params = system.init_params()
    tokens = system.tokens(0)
    got = float(system.loss_fn(params, tokens))
    want = float(reference.make_loss(system.architecture, system.config)(params, tokens))
    relative = abs(got - want) / want
    assert 0 < relative < 2**-8, (got, want)
    broken = {**system.config, "rope_theta": 10.0}
    wrong = float(reference.make_loss(system.architecture, broken)(params, tokens))
    assert abs(wrong - want) / want > 1e-5


def test_reference_update_is_the_first_adamw_step(bench_root):
    """The reference's hand-written first step against optax.adamw on the
    program's own gradient, float32 at toy size: the same parameters to 1e-5,
    and the second loss tells that update from none, from one of twice the
    size and from one that lost the weight decay."""
    import jax
    import optax

    from chipbench import harness, reference

    system = tiny_system("float32", bench_root)
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
    total = reference.grad_sum(system.architecture, params, system.tokens(0), system.config)
    mine = reference.first_adamw_step(params, total, count, system.config)
    worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(abs(a - b).max()), mine, stepped)))
    assert worst < 1e-5, worst
    for wrong in ({"learning_rate": 2 * system.config["optimizer"]["learning_rate"]}, {"weight_decay": 0.0}):
        config = {**system.config, "optimizer": {**system.config["optimizer"], **wrong}}
        loss = float(reference.make_loss_after_first_update(system.architecture, config)(params, system.tokens(0), system.tokens(1)))
        assert abs(loss - got) / got > tol, wrong


@pytest.mark.parametrize("query_block,head_block", [(32, 128), (128, 32), (32, 32)])
def test_blocked_reference_equals_the_unblocked_one(query_block, head_block, monkeypatch, bench_root):
    """One sequence of 128 positions in four blocks of 32 (attention, the loss
    head, both) against the same sequence in one block: softmax is by row, so
    only the order of the sums differs. Loss to 1e-6 relative, gradient leaf
    by leaf to 1e-5 of the leaf's largest entry (float32, CPU)."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference

    system = tiny_system("float32", bench_root)
    params = system.init_params()
    tokens = jax.random.randint(jax.random.PRNGKey(3), (129,), 0, system.config["vocab_size"])

    def loss_and_grad():
        fn = lambda p: system.architecture.sequence_loss(p, tokens, system.config, recompute=True)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(fn))(params)

    monkeypatch.setattr(reference, "QUERY_BLOCK", 128)
    monkeypatch.setattr(reference, "HEAD_BLOCK", 128)
    want, want_grad = loss_and_grad()
    monkeypatch.setattr(reference, "QUERY_BLOCK", query_block)
    monkeypatch.setattr(reference, "HEAD_BLOCK", head_block)
    got, got_grad = loss_and_grad()
    assert abs(float(got) - float(want)) / float(want) < 1e-6
    worst = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))), got_grad, want_grad
    )
    assert max(jax.tree_util.tree_leaves(worst)) < 1e-5, worst
    # A sequence that is not whole blocks is refused, not padded.
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    with pytest.raises(ValueError, match="whole blocks"):
        reference.causal_attention(*(jnp.zeros((48, 2, 8)),) * 3)


def test_flash_attention_flops_against_a_hand_count():
    """2 layers, 3 heads of 8, batch 5, 16 positions: one matmul of the
    scores' shape is 2 * 16 * 16 * 8 * 3 = 12288 operations, 6144 under the
    causal mask; two forward and five backward: 43008 a layer and sequence."""
    from chipbench import flops

    config = {"num_hidden_layers": 2, "num_attention_heads": 3, "head_dim": 8}
    assert flops.flash_attention_flops(config, 5, 16) == 7 * 6144 * 2 * 5 == 430080
    # Four times the positions in a quarter of the sequences: four times the work.
    assert flops.flash_attention_flops(config, 1, 64) == 4 * flops.flash_attention_flops(config, 4, 16)


def test_flash_mxu_pct_reads_the_recorded_trace(bench_root):
    """The recorded plain run (three steps of batch 4 x seq 2048 at the cell's
    widths): the kernels' seconds are flash_time_pct's, the operations
    flops.flash_attention_flops', the peak peaks.json's."""
    from chipbench import flops, harness

    bench = spec.Benchmark(bench_root)
    trace = trace_reduce.reduce(json.loads((HERE / "small_trace.json").read_text()))
    config = bench.config("mistral-7b-v0.3-1chip")
    obs = {"trace": trace, "steps": 3, "config": config, "batch": 4, "seq": 2048,
           "peaks": harness.peaks_for("TPU v5 lite")}
    read = bench.reader("per_layer", "flash_mxu_pct").read
    seconds = sum(s for _, s in trace["kernels"]["jit_plain"])
    want = 100 * 3 * flops.flash_attention_flops(config, 4, 2048) / seconds / 197e12
    assert read(obs) == pytest.approx(want, rel=1e-12)
    assert read(obs) == pytest.approx(25.0086, rel=1e-4) and 0 < read(obs) < 100
    share = bench.reader("per_layer", "flash_time_pct").read(obs)
    assert read(obs) * share == pytest.approx(  # the same seconds under both
        100 * 100 * 3 * flops.flash_attention_flops(config, 4, 2048) / trace["busy_s"] / 197e12)
    # Nothing to read: no trace, no peak (a rehearsal), no step, no kernel.
    for hole in ({"trace": None}, {"peaks": None}, {"steps": 0},
                 {"trace": {**trace, "kernels": {"jit_quantize_pseudograd": [["k", 1.0]]}}}):
        assert read({**obs, **hole}) is None


def test_control_the_reference_in_the_precision_below_is_not_correct(bench_root):
    """The control of the comparison that decides ``correct``: the reference
    itself, put in the program's place with its weights in the nearest
    precision below the one the (toy, float32) configuration states, bf16.
    Both of its losses must fail the tolerances the sound program passes
    (test_reference_update_is_the_first_adamw_step). At the cells' own size
    the precision below bf16 is fp8: PERF.md section 2 has those readings."""
    import jax
    import jax.numpy as jnp

    from chipbench import harness, reference

    system = tiny_system("float32", bench_root)
    params = system.init_params()
    system.reference = harness.reference_losses(system, params)
    lower = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16).astype(a.dtype), params)
    first = reference.make_loss(system.architecture, system.config)(lower, system.tokens(0))
    second = reference.make_loss_after_first_update(system.architecture, system.config)(
        lower, system.tokens(0), system.tokens(1))
    found = harness.reference_check(system, [float(first), float(second)])
    assert len(found) == 2 and "first loss" in found[0] and "second loss" in found[1], found


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


def test_seed_decides_weights_and_tokens(bench_root):
    import numpy as np

    a, b = tiny_system("float32", bench_root), tiny_system("float32", bench_root)
    assert np.array_equal(np.asarray(a.tokens(3)), np.asarray(b.tokens(3)))
    assert not np.array_equal(np.asarray(a.tokens(3)), np.asarray(a.tokens(4)))
    assert not np.array_equal(np.asarray(a.tokens(3, 0)), np.asarray(a.tokens(3, 1)))


# -- discovery: new cells and metrics are new files ----------------------------


def test_a_cell_a_traffic_mix_a_job_and_a_metric_are_added_as_files(tmp_path, bench_root):
    copy = tmp_path / "repo"
    copy_benchmark(copy, bench_root)
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
    data = json.loads((bench_root / "BENCHMARK.json").read_text())
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
    # Its per-layer metrics: those that name no cells, which every cell
    # reports (today's three among them), and its own, appended last.
    names = [m["name"] for m in bench.metrics_of("throwaway.seq8k", "per_layer")]
    assert names[:-1] == [m["name"] for m in data["per_layer"][:-1] if "workloads" not in m]
    assert {"compile_s", "hbm_held_gib", "hbm_scratch_gib"} <= set(names[:-1])
    assert names[-1] == "steps_in_window"
    assert bench.reader("per_layer", "steps_in_window").read({"steps": 7}) == 7
    # The old cells still see their own metrics only, and no file was edited.
    assert "steps_in_window" not in [
        m["name"] for m in bench.metrics_of("mistral7b-1chip.plain", "per_layer")
    ]
    after = {p: p.read_bytes() for p in before}
    assert after == before


THROWAWAY_ARCHITECTURE = '''
"""bagofwords: a block the benchmark has never seen (no attention: token
embedding, RMSNorm, one gated residual layer, an untied head), program's
model and float32 reference in one file."""
import jax
import jax.numpy as jnp

from chipbench import reference


class Model:
    def __init__(self, config):
        self.d, self.vocab, self.eps = config["hidden_size"], config["vocab_size"], config["rms_norm_eps"]
        self.dtype = jnp.dtype(config["run"]["dtype"])

    def init(self, key, tokens):
        e, w, h = jax.random.split(key, 3)
        normal = lambda k, shape: (jax.random.normal(k, shape) * shape[0] ** -0.5).astype(self.dtype)
        return {"params": {"embed": normal(e, (self.vocab, self.d)) * self.d ** 0.5,
                           "scale": jnp.ones((self.d,), self.dtype),
                           "w": normal(w, (self.d, self.d)), "head": normal(h, (self.d, self.vocab))}}

    def apply(self, params, inputs, targets=None):
        p = params["params"]
        x = p["embed"][inputs]
        x = x + jax.nn.silu(reference.rms_norm(x, p["scale"], self.eps) @ p["w"])
        logp = jax.nn.log_softmax((x @ p["head"]).astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def build(config, seq):
    return Model(config)


def sequence_loss(params, tokens, config, recompute=False):
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params["params"])
    x = p["embed"][tokens[:-1]]
    x = x + jax.nn.silu(reference.rms_norm(x, p["scale"], float(config["rms_norm_eps"])) @ p["w"])
    return reference.next_token_loss_sum(x, p["head"], tokens[1:])


def parameter_counts(config):
    d, vocab = config["hidden_size"], config["vocab_size"]
    return {"total": 2 * vocab * d + d * d + d, "matmul": d * d + vocab * d}


def train_flops_per_token(config, seq):
    return 6.0 * parameter_counts(config)["matmul"]
'''


def test_an_architecture_is_added_as_files(tmp_path):
    """What a model_config PR brings: an architecture file in a directory of
    its own, a configuration that names it, a traffic mix and a cell. The
    benchmark finds them by name, runs the cell end to end through the plain
    job (the program's model from ``build``, the float32 reference from
    ``sequence_loss``, ``mfu_pct``'s count from ``train_flops_per_token``),
    and no file of chipbench/ was edited."""
    copy = tmp_path / "repo"
    copy_benchmark(copy)
    before = {p: p.read_bytes() for p in (copy / "chipbench").rglob("*") if p.is_file()}
    extra = copy / "morebench"
    for sub in ("configs", "traffic", "architectures"):
        (extra / sub).mkdir(parents=True)
    (extra / "architectures/bagofwords.py").write_text(THROWAWAY_ARCHITECTURE)
    config = json.loads((copy / "chipbench/configs/mistral-7b-v0.3-1chip.json").read_text())
    config.update(name="bagofwords-1chip", model_type="bagofwords")
    (extra / "configs/bagofwords-1chip.json").write_text(json.dumps(config))
    (extra / "traffic/plain-toy.json").write_text(json.dumps(
        {**json.loads((copy / "chipbench/traffic/plain.json").read_text()), "batch": 2, "seq": 64}
    ))
    list_cell(copy, "bagofwords.plain", "bagofwords-1chip", "plain-toy", configs=[{
        "name": "bagofwords-1chip", "source": "https://example.org/x", "why": "test",
        "file": "morebench/configs/bagofwords-1chip.json", "reduced": [],
    }], metrics=("tokens_per_s", "mfu_pct"))

    bench = spec.Benchmark(copy)
    assert spec.problems(bench) == []
    architecture = bench.architecture(bench.config("bagofwords-1chip")["model_type"])
    assert architecture.__file__ == str(extra / "architectures/bagofwords.py")
    assert architecture.train_flops_per_token({"hidden_size": 4, "vocab_size": 10}, 64) == 6.0 * 56
    done = run_cell("bagofwords.plain", "--trace", "0", root=copy)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"tokens_per_s", "peak_hbm_gib", "setup_s"}
    assert "reference: first loss" in done.stderr and "reference: second loss" in done.stderr
    assert {p: p.read_bytes() for p in before} == before


def test_a_model_type_without_a_file_is_a_named_problem(tmp_path):
    """Seen by ``spec.problems`` before anything runs: the run ends with "no
    result" and the names, not with a traceback inside a job."""
    copy = tmp_path / "repo"
    copy_benchmark(copy)
    shutil.copy(ROOT / "BENCHMARK.json", copy / "BENCHMARK.json")
    path = copy / "chipbench/configs/mistral-7b-v0.3-1chip.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), "model_type": "olmoe"}))
    bench = spec.Benchmark(copy)
    found = spec.problems(bench)
    assert len(found) == 1 and "mistral-7b-v0.3-1chip" in found[0], found
    assert "model_type 'olmoe'" in found[0] and "architectures/olmoe.py" in found[0]
    with pytest.raises(spec.SpecError, match="architectures/olmoe.py"):
        bench.architecture("olmoe")
    done = run_cell("mistral7b-1chip.plain", "--trace", "0", root=copy)
    assert done.returncode != 0 and "Traceback" not in done.stderr
    assert "no result: BENCHMARK.json is unsound" in done.stderr and "olmoe" in done.stderr
    assert not any(l.startswith("{") for l in done.stdout.splitlines())


def test_only_the_architecture_file_names_the_programs_model():
    """Nothing of the benchmark outside architectures/ names the program's
    model class, a parameter path of it or the dense block's width (the parked
    hsdp job imports the program's sharding plan, which is the program's name
    for a layout, not for a block). What each pattern guards: ``[Ll]lama`` the
    dense block's class and module (``models/llama.py``), ``w_gate`` a path of
    its parameter tree, ``intermediate_size`` its feed-forward width, and with
    it every key that holds those letters (``moe_intermediate_size`` too). So
    a reader, a job or the harness is written for any block: a reader that
    needs a width or a count takes it from ``obs["config"]`` by a key that its
    architecture file names (a constant or a function of that file, found by
    ``spec.load_module`` on the file of ``obs["config"]["model_type"]``), and
    never spells the key itself."""
    import re

    names = re.compile(r"[Ll]lama|w_gate|intermediate_size")
    hits = [
        f"{p.relative_to(ROOT)}:{n}" for p in sorted((ROOT / "chipbench").rglob("*.py"))
        if p.parent.name != "architectures"
        for n, text in enumerate(p.read_text().splitlines(), 1) if names.search(text)
    ]
    assert [h for h in hits if not h.startswith("chipbench/jobs/hsdp.py")] == []
    assert len([h for h in hits if h.startswith("chipbench/jobs/hsdp.py")]) == 1


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
    ("mistral7b-1chip.ftddp-seq8k", "0"),  # one sequence of four reference blocks
    ("mistral7b-1chip.ftddp-seq8k", "1"),
    ("mistral7b-2x2.hsdp", "0"),  # parked: rehearsed from a copy that lists it
    # One process measures and then traces: both kinds of metric on one line.
    ("mistral7b-1chip.plain", "2"),
    ("mistral7b-1chip.ftddp", "2"),
    ("mistral7b-1chip.diloco-fp8", "2"),
    ("mistral7b-1chip.ftddp-seq8k", "2"),
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
    groups = {"0": ["end_to_end"], "1": ["per_layer"], "2": ["end_to_end", "per_layer"]}[trace]
    allowed = {m["name"]: m["unit"] for g in groups for m in bench.metrics_of(workload, g)}
    assert line["metrics"], "no metric on the line"
    for name, entry in line["metrics"].items():
        assert set(entry) == {"value", "unit"} and entry["unit"] == allowed[name]
        assert isinstance(entry["value"], float)
    if trace in ("0", "2"):
        assert {m["name"] for m in bench.metrics_of(workload, "end_to_end")} <= set(line["metrics"])
    if trace == "0":
        assert set(line["metrics"]) == set(allowed)
    if trace == "1":  # as before PR 43: the capture's readers find nothing to read
        assert not {"ft_step_host_ms", "outer_sync_host_ms", "trace_overhead_pct"} & set(line["metrics"])
    if trace == "2":
        # What needs no chip is there: the capture's readers, and the counters
        # and clocks of the measured window. (A ``breakdown``, ``busy_s`` and
        # the device-trace metrics need a TPU plane in the trace: none here.)
        by_source = {m["name"]: m["source"] for m in bench.metrics_of(workload, "per_layer")}
        want = {n for n, source in by_source.items() if source != "device_trace"} - {"mfu_pct"}
        assert want <= set(line["metrics"]), sorted(want - set(line["metrics"]))
        assert {"trace_overhead_pct", "host_stall_ms", "compile_s"} <= want
    if workload.endswith("diloco-fp8"):
        assert line["attempted"] % 8 == 0, "a window of whole rounds"


STUCK_JOB = '''
"""stuck: the plain job with its timed path broken underneath: the step
computes the loss and drops the update, so the state never changes."""
from chipbench import harness, spec

plain = spec.load_module(harness.ROOT / "chipbench/jobs/plain.py")


class Job(plain.Job):
    def __init__(self, run, system, params, spans):
        import jax

        super().__init__(run, system, params, spans)
        self._loss = jax.jit(system.loss_fn)

    def step(self, i):
        return self._loss(self.params, self.system.tokens(i))


def run(run):
    return harness.run_one_process(run, Job)
'''


def test_trace_2_measures_as_trace_0_does_and_only_then_starts_a_capture(monkeypatch, capsys):
    """The measured part of a ``--trace 2`` run makes the same calls in the
    same order as a ``--trace 0`` run, up to and including the taking of its
    numbers (``gauge.report()`` last); no capture starts before that. Then one
    capture is thrown away, and one more wraps a second window."""
    import jax

    from chipbench import harness
    from torchft_tpu import tracing

    run_py = spec.load_module(ROOT / "chipbench/run.py")

    calls = []

    def logged(owner, name):
        inner = getattr(owner, name)

        def wrapper(*args, **kwargs):
            calls.append(name)
            return inner(*args, **kwargs)

        monkeypatch.setattr(owner, name, wrapper)

    for owner, name in (
        (harness, "run_window"), (harness, "counter_sums"), (harness, "host_clocks"),
        (harness, "window_checks"), (harness, "memory_problems"),
        (harness.MemoryGauge, "report"), (harness.HostPulse, "__enter__"),
        (harness.HostPulse, "__exit__"), (jax, "device_get"),
        (tracing, "start_capture"), (tracing, "stop_capture"),
    ):
        logged(owner, name)
    monkeypatch.setenv("TPUFT_LOG", "warn")

    def run(trace: str):
        del calls[:]
        argv = ["--workload", "mistral7b-1chip.plain", "--seed", "11", "--seconds", "0.2",
                "--trace", trace, "--rehearse", str(HERE / "rehearsal.json")]
        assert run_py.main(argv) == 0
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert line["correct"] is True
        return list(calls), line

    plain, line0 = run("0")
    both, line2 = run("2")
    assert "start_capture" not in plain and plain[-2:] == ["report", "memory_problems"]
    assert plain.count("run_window") == 1
    assert both[: len(plain)] == plain
    tail = both[len(plain):]
    assert tail[:2] == ["start_capture", "stop_capture"]  # thrown away
    again = tail[2:]
    assert again.count("start_capture") == again.count("stop_capture") == again.count("run_window") == 1
    assert again.index("start_capture") < again.index("run_window") < again.index("stop_capture")
    assert again[-2:] == ["report", "memory_problems"]
    assert set(line0["metrics"]) < set(line2["metrics"])


def test_trace_2_on_the_parked_job_is_no_result(tmp_path):
    root = copy_with_parked_cell(tmp_path / "repo")
    done = run_cell("mistral7b-2x2.hsdp", "--trace", "2", root=root)
    assert done.returncode != 0 and "no result: --trace 2" in done.stderr
    assert not any(l.startswith("{") for l in done.stdout.splitlines())


def test_a_step_that_returns_its_state_unchanged_is_not_correct(tmp_path):
    """The rest of a run driven past the harness's look for a chip (a
    rehearsal), with the timed path broken where the state is produced: every
    loss is finite, nothing compiles in the window, the first loss agrees with
    the reference, and ``correct`` is false because the second does not."""
    copy = tmp_path / "repo"
    copy_benchmark(copy)
    for sub in ("traffic", "jobs"):
        (copy / "morebench" / sub).mkdir(parents=True)
    (copy / "morebench/jobs/stuck.py").write_text(STUCK_JOB)
    (copy / "morebench/traffic/stuck-toy.json").write_text(json.dumps(
        {**json.loads((copy / "chipbench/traffic/plain.json").read_text()), "job": "stuck", "batch": 2, "seq": 64}
    ))
    list_cell(copy, "mistral7b-1chip.stuck", "mistral-7b-v0.3-1chip", "stuck-toy")
    assert spec.problems(spec.Benchmark(copy)) == []
    done = run_cell("mistral7b-1chip.stuck", "--trace", "0", root=copy)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["attempted"] >= 1
    wrong = [l for l in done.stderr.splitlines() if l.startswith("NOT CORRECT")]
    assert len(wrong) == 1 and "second loss differs from the float32 reference" in wrong[0], wrong


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
