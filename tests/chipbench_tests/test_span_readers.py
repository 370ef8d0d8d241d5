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

FTDDP, DILOCO = "mistral7b-1chip.ftddp", "mistral7b-1chip.diloco-fp8"
SEQ8K = "mistral7b-1chip.ftddp-seq8k"


def reader(name: str):
    return spec.Benchmark(ROOT).reader("per_layer", name).read


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
def test_span_reader(name, obs, want):
    got = reader(name)(obs)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9, abs=1e-12)


def test_span_readers_read_what_trace_reduce_gives():
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
    got = reader("ft_idle_ms")({"trace": trace, "steps": 1})
    assert got == pytest.approx(1e3 * 550e-9)
    assert reader("outer_sync_idle_ms")({"trace": trace, "fragments": 4, "units": 1}) == 0.0


def test_span_metrics_are_listed_with_their_cells_and_nothing_else_moved():
    bench = spec.Benchmark(ROOT)
    assert spec.problems(bench) == []
    assert "trace_in_run" not in bench.data  # PERF.md section 7 says why
    by_name = {m["name"]: m for m in bench.data["per_layer"]}
    # PR 27: the long-sequence FT-DDP cell reads the FT step's idle too, and
    # the kernels' share of the peak comes after the two span metrics.
    assert by_name["ft_idle_ms"]["workloads"] == [FTDDP, SEQ8K]
    assert by_name["outer_sync_idle_ms"]["workloads"] == [DILOCO]
    assert [m["name"] for m in bench.data["per_layer"]][-3:] == [
        "ft_idle_ms", "outer_sync_idle_ms", "flash_mxu_pct"]
    assert by_name["flash_mxu_pct"]["workloads"] == [w["name"] for w in bench.data["workloads"]]
    for name in ("ft_idle_ms", "outer_sync_idle_ms"):
        entry = by_name[name]
        assert entry["source"] == "device_trace" and entry["moves"] == "tokens_per_s"
        assert entry["layer"] == "step protocol" and entry["better"] == "lower"
