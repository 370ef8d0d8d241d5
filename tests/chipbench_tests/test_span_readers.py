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


@pytest.mark.parametrize("name,obs,want", [
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
    assert "trace_in_run" not in bench.data  # PERF.md section 7 says why
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
