"""Tests of the per-layer readers that read the program's own spans out of a
traced run's idle gaps (PR 25). On the CPU; tier-1 collects them.

    JAX_PLATFORMS=cpu python -m pytest tests/chipbench_tests -q
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import spec, trace_reduce  # noqa: E402
from test_chipbench import bench_root  # noqa: E402,F401

PLAIN, FTDDP = "mistral7b-1chip.plain", "mistral7b-1chip.ftddp"
DILOCO, SEQ8K = "mistral7b-1chip.diloco-fp8", "mistral7b-1chip.ftddp-seq8k"


def reader(name: str, root: Path):
    return spec.Benchmark(root).reader("per_layer", name).read


GAPS = [
    ["tpuft::manager::_client::_quorum", 0.090], ["tpuft::optim::device_sync", 0.040],
    ["tpuft::optim::step", 0.010], ["chipbench/step", 0.004], ["chipbench/fetch", 0.002],
    ["tpuft::local_sgd::step", 0.020], ["tpuft::local_sgd::apply_outer", 0.050],
    ["tpuft::local_sgd::inner_dispatch", 0.070],
    ["tpuft::local_sgd::perform_sync", 0.006], ["unattributed", 0.001],
]


def event(name, start, dur, thread="MainThread"):
    return {"name": name, "ph": "X", "t_mono": start, "dur": dur, "thread": thread, "step": 0}


# Two FT-DDP steps as the capture's journal holds them: the roots, their
# children on their thread, the quorum thread's RPC and the executor's vote.
FT_EVENTS = [
    event("start_quorum", 10.000, 0.0001), event("quorum", 10.0001, 0.001, "tpuft_quorum_0"),
    event("wait_quorum", 10.0002, 0.0012), event("update_dispatch", 10.0015, 0.002),
    event("commit_barrier", 10.004, 0.0003, "tpuft_quorum_0"),
    event("device_sync", 10.004, 0.230), event("adopt", 10.2345, 0.0003),
    event("step", 10.000, 0.235),
    event("device_sync", 10.300, 0.231), event("step", 10.297, 0.237),
    # A device sync of another thread's step, and an instant, are not the root's.
    event("device_sync", 10.310, 0.100, "other"), {"name": "commit", "ph": "i", "t_mono": 10.5, "thread": "MainThread"},
]
SYNC_EVENTS = [
    event("prepare_sync", 20.0, 0.0006), event("sync_quantize", 20.0, 0.0003),
    event("perform_sync", 20.3, 0.0015), event("sync_apply_outer", 20.3, 0.0008),
    event("prepare_sync", 22.0, 0.0005), event("perform_sync", 22.3, 0.0013),
    event("step", 20.0, 0.24), event("inner_dispatch", 20.0, 0.23),
]
MODULES = [
    ["jit_fused", 5.0], ["jit_quantize_pseudograd", 0.020], ["jit_apply_outer", 0.036],
    ["jit_tokens", 0.001],
]
MEASURED = {"tokens": 1_000_000, "window_s": 30.0, "steps": 122, "units": 122}


@pytest.mark.parametrize("name,obs,want", [
    # The root less the device syncs inside it on its thread, a step.
    ("ft_step_host_ms", {"capture": {"events": FT_EVENTS}}, 1e3 * ((0.235 - 0.230) + (0.237 - 0.231)) / 2),
    ("ft_step_host_ms", {"capture": {"events": SYNC_EVENTS[:6]}}, None),  # no root
    ("ft_step_host_ms", {"capture": None}, None),
    ("ft_step_host_ms", {"trace": {"gaps": []}, "steps": 20}, None),  # --trace 1: no capture
    # prepare_sync + perform_sync over the perform_sync events.
    ("outer_sync_host_ms", {"capture": {"events": SYNC_EVENTS}}, 1e3 * (0.0006 + 0.0015 + 0.0005 + 0.0013) / 2),
    ("outer_sync_host_ms", {"capture": {"events": FT_EVENTS}}, None),
    ("outer_sync_host_ms", {"fragments": 4, "units": 1}, None),
    # Device seconds of the two sync programs, every op, over fragments x rounds.
    ("outer_sync_device_ms", {"trace": {"modules": MODULES}, "fragments": 4, "units": 2}, 1e3 * 0.056 / 8),
    ("outer_sync_device_ms", {"trace": {"modules": MODULES[:1]}, "fragments": 4, "units": 2}, None),
    ("outer_sync_device_ms", {"trace": None, "fragments": 4, "units": 2}, None),
    ("outer_sync_device_ms", {"trace": {"modules": MODULES}, "units": 2}, None),
    # 100 x (1 - tail rate / measured rate); nothing outside --trace 2.
    ("trace_overhead_pct", {"tokens": 200_000, "window_s": 6.06, "measured": MEASURED},
     100 * (1 - (200_000 / 6.06) / (1_000_000 / 30.0))),
    ("trace_overhead_pct", {"tokens": 200_000, "window_s": 5.9, "measured": MEASURED},
     100 * (1 - (200_000 / 5.9) / (1_000_000 / 30.0))),  # the tail ran faster: negative
    ("trace_overhead_pct", {"tokens": 200_000, "window_s": 6.0}, None),
    ("trace_overhead_pct", {"tokens": 200_000, "window_s": 6.0, "measured": {}}, None),
    # Idle under any tpuft:: span, the root included, per step.
    ("ft_idle_ms", {"trace": {"gaps": GAPS}, "steps": 20},
     1e3 * (0.090 + 0.040 + 0.010 + 0.020 + 0.050 + 0.070 + 0.006) / 20),
    ("ft_idle_ms", {"trace": {"gaps": [["chipbench/step", 0.05]]}, "steps": 20}, 0.0),
    ("ft_idle_ms", {"trace": None, "steps": 20}, None),
    ("ft_idle_ms", {"steps": 20}, None),
    ("ft_idle_ms", {"trace": {"gaps": GAPS}, "steps": 0}, None),
    # Idle under a sync's spans and the manager's; the inner step's root and
    # its dispatch, which every inner step has, are left out.
    ("outer_sync_idle_ms", {"trace": {"gaps": GAPS}, "fragments": 4, "units": 1},
     1e3 * (0.090 + 0.050 + 0.006) / 4),
    ("outer_sync_idle_ms", {"trace": {"gaps": GAPS}, "fragments": 4, "units": 2},
     1e3 * (0.090 + 0.050 + 0.006) / 8),
    ("outer_sync_idle_ms", {"trace": {"gaps": GAPS}, "units": 1}, None),
    ("outer_sync_idle_ms", {"trace": {"gaps": GAPS}, "fragments": 4, "units": 0}, None),
    ("outer_sync_idle_ms", {"trace": None, "fragments": 4, "units": 1}, None),
])
def test_span_reader(name, obs, want, bench_root):
    got = reader(name, bench_root)(obs)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9, abs=1e-12)


def test_span_readers_read_what_trace_reduce_gives(bench_root):
    """End to end on a hand-made trace: one FT-DDP step whose device gap has
    its middle under the program's dispatch span, inside the root, inside the
    harness's step."""
    ops = [["a f32[2]", 100, 300, "jit_f", "op"], ["b f32[2]", 900, 100, "jit_f", "op"]]
    spans = [
        ["chipbench/fetch", 0, 50, "", "span"], ["chipbench/step", 60, 1000, "", "span"],
        ["tpuft::optim::step", 70, 980, "", "span"],
        ["tpuft::optim::update_dispatch", 500, 300, "", "span"],
        ["chipbench/fetch", 1100, 100, "", "span"],
    ]
    trace = trace_reduce.reduce({"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": spans}]},
    ]})
    owners = dict(trace["gaps"])
    assert owners["tpuft::optim::update_dispatch"] == pytest.approx(500e-9)  # 400..900
    assert owners["tpuft::optim::step"] == pytest.approx(50e-9)  # 50..100: the root, no child open
    got = reader("ft_idle_ms", bench_root)({"trace": trace, "steps": 1})
    assert got == pytest.approx(1e3 * 550e-9)
    assert reader("outer_sync_idle_ms", bench_root)({"trace": trace, "fragments": 4, "units": 1}) == 0.0


def test_span_metrics_are_listed_with_their_cells_and_nothing_else_moved(bench_root):
    """What the lists MEAN, not what they hold today (PR 40): a later PR lists
    its cell and appends its metrics without editing this file, and the same
    assertions hold on its BENCHMARK.json (test_another_architecture.py calls
    this test on such a copy)."""
    bench = spec.Benchmark(bench_root)
    assert spec.problems(bench) == []
    # The per-layer numbers are taken by ``--trace 2``, in the run that measures
    # (PR 43; PERF.md section 6).
    assert bench.data["trace_in_run"] is True
    by_name = {m["name"]: m for m in bench.data["per_layer"]}
    cells = [w["name"] for w in bench.data["workloads"]]
    job_of = {w["name"]: bench.traffic(w["traffic"])["job"] for w in bench.data["workloads"]}
    # A span metric is read in cells of the one job that opens its spans: the
    # FT step's idle under ``ftddp`` (PR 27: the long-sequence cell too), a
    # fragment sync's under ``diloco``. Today's cells stay listed.
    for name, job, todays in (
        ("ft_idle_ms", "ftddp", [FTDDP, SEQ8K]), ("outer_sync_idle_ms", "diloco", [DILOCO]),
    ):
        listed = by_name[name]["workloads"]
        assert [c for c in listed if job_of[c] != job] == [], name
        assert [c for c in todays if c not in listed] == [], name
    # The kernels' share of the device's time and of its peak are of the SAME
    # calls, so they list the same cells: the four dense all-attention ones and
    # any other that meets flash_time_pct.py's rule, never every cell there is.
    flash = by_name["flash_mxu_pct"]["workloads"]
    assert sorted(flash) == sorted(by_name["flash_time_pct"]["workloads"])
    assert set(flash) <= set(cells) and {PLAIN, FTDDP, DILOCO, SEQ8K} <= set(flash)
    for name in ("ft_idle_ms", "outer_sync_idle_ms"):
        entry = by_name[name]
        assert entry["source"] == "device_trace" and entry["moves"] == "tokens_per_s"
        assert entry["layer"] == "step protocol" and entry["better"] == "lower"
    # The capture's readers (PR 43) list the cells of the job whose events they
    # read; what the tracing costs is read wherever a rate is.
    for name, job, todays, source in (
        ("ft_step_host_ms", "ftddp", [FTDDP, SEQ8K], "program_span"),
        ("outer_sync_host_ms", "diloco", [DILOCO], "program_span"),
        ("outer_sync_device_ms", "diloco", [DILOCO], "device_trace"),
    ):
        entry = by_name[name]
        assert [c for c in entry["workloads"] if job_of[c] != job] == [], name
        assert [c for c in todays if c not in entry["workloads"]] == [], name
        assert (entry["source"], entry["layer"], entry["moves"]) == (source, "step protocol", "tokens_per_s")
    overhead = by_name["trace_overhead_pct"]
    assert {PLAIN, FTDDP, DILOCO, SEQ8K} <= set(overhead["workloads"])
    assert (overhead["source"], overhead["layer"]) == ("program_span", "entry points")


def test_the_captures_clock_lays_the_harness_spans_onto_the_profilers_timeline(tmp_path, monkeypatch):
    """``SpanLog.span`` and the program's ``_Span`` both read
    ``time.monotonic``, and the capture's ``clock`` anchors carry that clock
    onto the profiler's: a ``chipbench/fetch`` span's start, mapped through the
    ``tpuft::capture_begin`` anchor, is its annotation's start in a real xplane
    read back here, to a millisecond. Through the harness's own path, twice in
    one process (``--trace 2`` starts a capture it throws away first)."""
    import time

    from chipbench import harness
    from chipbench.spans import SpanLog
    from torchft_tpu import tracing

    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    spans, journal = SpanLog(), tracing.current()
    for round_ in range(2):
        mine = len(spans.spans)
        with harness.Tracer(True) as tracer:
            for _ in range(2):
                with spans.span("chipbench/fetch"):
                    with tracing.phase("optim_step", journal, step=round_):
                        time.sleep(0.003)
                time.sleep(0.001)
        capture = tracer.capture
        # Not the directory; ``runtime`` is the fifth key since PR 59, and a later one may follow.
        assert {"events", "counters", "clock", "dropped"} <= set(capture) and "trace_dir" not in capture
        roots = [e for e in capture["events"] if e["name"] == "step"]
        assert [e["step"] for e in roots] == [round_, round_]
        (path,) = Path(tracer.dir).glob("plugins/profile/*/*.xplane.pb")
        space = trace_reduce.load_xplane(path)
        found = {}
        for plane in space["planes"]:
            for line in plane["lines"]:
                for name, start, dur, *_ in line["events"]:
                    found.setdefault(name, []).append((start, dur))
        (begin_ns, _), = found["tpuft::capture_begin"]
        (end_ns, _), = found["tpuft::capture_end"]
        clock = capture["clock"]
        assert end_ns - begin_ns == pytest.approx(clock["end_mono_ns"] - clock["begin_mono_ns"], abs=1e6)

        def on_profiler(t_mono: float) -> float:
            return begin_ns + (t_mono * 1e9 - clock["begin_mono_ns"])

        fetches = sorted(found["chipbench/fetch"])
        assert len(fetches) == 2
        for (start, dur), (_, t0, t1) in zip(fetches, spans.spans[mine:]):
            assert on_profiler(t0) == pytest.approx(start, abs=1e6)
            assert on_profiler(t1) == pytest.approx(start + dur, abs=1e6)
        # The journal's instants too: the root's start and end.
        for (start, dur), root in zip(sorted(found["tpuft::optim::step"]), roots):
            assert on_profiler(root["t_mono"]) == pytest.approx(start, abs=1e6)
            assert on_profiler(root["t_mono"] + root["dur"]) == pytest.approx(start + dur, abs=1e6)
        assert tracer.reduce() is None  # no device plane on the CPU
        assert not Path(tracer.dir).exists()  # the trace is deleted once reduced
