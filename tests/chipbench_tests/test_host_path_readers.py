"""Tests of the four per-layer readers of PR 59, which open the FT step's
serial host path from the capture's own record: the journal's events by part
(``ft_dispatch_host_ms``, ``ft_adopt_host_ms``), the runtime's events under
the ``update_dispatch`` span (``ft_dispatch_execute_ms``) and the counters of
the buffers a dispatch hands over (``ft_host_us_per_buffer``). On the CPU;
tier-1 collects them.

    JAX_PLATFORMS=cpu python -m pytest tests/chipbench_tests -q
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import spec  # noqa: E402
from test_chipbench import bench_root, run_cell  # noqa: E402,F401
from test_span_readers import FT_EVENTS, SYNC_EVENTS, event  # noqa: E402

NEW = {
    "ft_dispatch_host_ms": "ms/step", "ft_adopt_host_ms": "ms/step",
    "ft_dispatch_execute_ms": "ms/step", "ft_host_us_per_buffer": "us",
}
FTDDP_CELLS = [
    "mistral7b-1chip.ftddp", "mistral7b-1chip.ftddp-seq8k",
    "keye-vl2-30b-a3b-1chip.ftddp-seq8k", "smallthinker-21b-a3b-1chip.ftddp-seq16k",
    "granite-4.0-h-micro-1chip.ftddp-seq8k",
]


def reader(name: str, root: Path):
    return spec.Benchmark(root).reader("per_layer", name).read


def slot(count, seconds, self_seconds=None, first_at_s=0.0):
    return {
        "count": count, "seconds": seconds, "first_at_s": first_at_s,
        "self_seconds": seconds if self_seconds is None else self_seconds,
    }


# The runtime under two dispatches of 2.0 ms, as ``stop_capture()["runtime"]``
# gives it: the outermost call, the execute call and its helper inside it.
RUNTIME = {
    "tpuft::optim::update_dispatch": {"count": 2, "seconds": 0.004, "under": {
        "PjitFunction": slot(4, 0.0038, 0.0006, 0.00001),
        "PjRtCpuExecutable::Execute": slot(2, 0.0030, 0.0001, 0.0003),
        "PjRtCpuExecutable::ExecuteHelper": slot(2, 0.0029, 0.0020, 0.00031),
        "Wait for usage holds": slot(148, 0.0009),
        "other": {"count": 9, "seconds": 0.0061, "self_seconds": 0.0002},
    }},
    "tpuft::optim::adopt": {"count": 2, "seconds": 0.0006, "under": {}},
}
# 37 leaves of state and one of batch in, the loss and the state out, a step.
COUNTERS = {
    "tpuft_step_dispatch_total": [{"labels": {}, "value": 2.0}],
    "tpuft_step_dispatch_buffers_total": [
        {"labels": {"direction": "in"}, "value": 76.0},
        {"labels": {"direction": "out"}, "value": 72.0},
    ],
}
# Events of the second root of FT_EVENTS that the older readers did not need,
# and an update_dispatch outside every root and on another thread.
MORE = [
    event("update_dispatch", 10.2975, 0.0024), event("adopt", 10.5335, 0.0004),
    event("state_swap", 10.5335, 0.0001), event("history_promote", 10.5337, 0.0002),
    event("update_dispatch", 11.0, 0.5), event("update_dispatch", 10.1, 0.05, "other"),
    event("adopt", 10.1, 0.05, "other"),
]
STEP_HOST_MS = 1e3 * ((0.235 - 0.230) + (0.237 - 0.231)) / 2


def capture(**parts):
    return {"capture": {"events": FT_EVENTS + MORE, "counters": COUNTERS, "runtime": RUNTIME, **parts}}


@pytest.mark.parametrize("name,obs,want", [
    # The events inside a root on its thread, over the roots.
    ("ft_dispatch_host_ms", capture(), 1e3 * (0.002 + 0.0024) / 2),
    ("ft_dispatch_host_ms", {"capture": {"events": FT_EVENTS}}, 1e3 * 0.002 / 2),  # a root without one counts
    ("ft_dispatch_host_ms", {"capture": {"events": SYNC_EVENTS}}, None),  # DiLoCo's root holds none
    ("ft_dispatch_host_ms", {"capture": {"events": MORE}}, None),  # no root
    ("ft_dispatch_host_ms", {"capture": None}, None),
    ("ft_dispatch_host_ms", {"trace": {"gaps": []}, "steps": 20}, None),  # --trace 1: no capture
    ("ft_adopt_host_ms", capture(), 1e3 * (0.0003 + 0.0004) / 2),
    ("ft_adopt_host_ms", {"capture": {"events": SYNC_EVENTS}}, None),
    ("ft_adopt_host_ms", {"capture": None}, None),
    ("ft_adopt_host_ms", {"trace": {"gaps": []}, "steps": 20}, None),
    # The name that holds Execute with most seconds, over the span's count.
    ("ft_dispatch_execute_ms", capture(), 1e3 * 0.0030 / 2),
    ("ft_dispatch_execute_ms", capture(runtime={
        "tpuft::optim::update_dispatch": {"count": 4, "seconds": 0.1, "under": {
            "TpuExecutable::ExecuteHelper": slot(4, 0.07), "TpuClient::Execute": slot(4, 0.08),
            "other": {"count": 1, "seconds": 9.0, "self_seconds": 9.0},
        }},
    }), 1e3 * 0.08 / 4),
    ("ft_dispatch_execute_ms", capture(runtime={  # no execute call under the span
        "tpuft::optim::update_dispatch": {"count": 2, "seconds": 0.004, "under": {
            "PjitFunction": slot(2, 0.003),
        }},
    }), None),
    ("ft_dispatch_execute_ms", capture(runtime={"tpuft::local_sgd::inner_dispatch": RUNTIME[
        "tpuft::optim::update_dispatch"]}), None),  # another span's
    ("ft_dispatch_execute_ms", capture(runtime={}), None),  # the trace was not read
    ("ft_dispatch_execute_ms", {"capture": {"events": FT_EVENTS, "counters": {}}}, None),  # a program of before PR 59
    ("ft_dispatch_execute_ms", {"capture": None}, None),
    ("ft_dispatch_execute_ms", {"trace": {"gaps": []}, "steps": 20}, None),
    # ft_step_host_ms over the buffers a dispatch: (76 + 72) / 2.
    ("ft_host_us_per_buffer", capture(), 1e3 * STEP_HOST_MS / 74),
    ("ft_host_us_per_buffer", capture(counters={}), None),  # the counters did not grow
    ("ft_host_us_per_buffer", capture(counters={
        "tpuft_step_dispatch_total": COUNTERS["tpuft_step_dispatch_total"]}), None),
    ("ft_host_us_per_buffer", capture(counters={
        "tpuft_step_dispatch_buffers_total": COUNTERS["tpuft_step_dispatch_buffers_total"]}), None),
    ("ft_host_us_per_buffer", capture(events=MORE), None),  # no root to divide
    ("ft_host_us_per_buffer", {"capture": None, "counters": COUNTERS}, None),  # the window's, not the capture's
    ("ft_host_us_per_buffer", {"trace": {"gaps": []}, "steps": 20}, None),
])
def test_host_path_readers_on_hand_made_observations(name, obs, want, bench_root):
    got = reader(name, bench_root)(obs)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_the_parts_lie_inside_the_whole(bench_root):
    """On one capture: dispatch + adopt <= the step's host path, and the
    execute call <= the dispatch."""
    obs = capture()
    read = {name: reader(name, bench_root)(obs) for name in (*NEW, "ft_step_host_ms")}
    assert read["ft_dispatch_host_ms"] + read["ft_adopt_host_ms"] <= read["ft_step_host_ms"]
    assert read["ft_dispatch_execute_ms"] <= read["ft_dispatch_host_ms"]


def test_benchmark_json_lists_the_four_at_the_end_and_is_sound(bench_root):
    bench = spec.Benchmark(bench_root)
    assert spec.problems(bench) == []
    by_name = {m["name"]: m for m in bench.data["per_layer"]}
    for name, unit in NEW.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": "lower", "source": "program_span",
            "layer": "step protocol", "moves": "tokens_per_s",
            "workloads": by_name[name]["workloads"],
        }
        # The five FT-DDP cells (a later PR's cell may join them), and no other job's.
        assert set(FTDDP_CELLS) <= set(by_name[name]["workloads"])
        assert by_name[name]["workloads"] == by_name["ft_step_host_ms"]["workloads"]
        assert bench.reader_path("per_layer", name).is_file()
    names = [m["name"] for m in bench.data["per_layer"]]  # in this order, wherever a later PR appends
    assert [n for n in names if n in NEW] == list(NEW)
    for cell in ("mistral7b-1chip.plain", "mistral7b-1chip.diloco-fp8"):
        assert not set(NEW) & {m["name"] for m in bench.metrics_of(cell, "per_layer")}


@pytest.mark.parametrize("workload,expected", [
    ("mistral7b-1chip.ftddp", True),
    ("mistral7b-1chip.diloco-fp8", False),
])
def test_trace_2_rehearsal_carries_the_four_in_an_ftddp_cell_only(workload, expected):
    done = run_cell(workload, "--trace", "2")
    assert done.returncode == 0, done.stderr[-3000:]
    metrics = json.loads(done.stdout.strip().splitlines()[-1])["metrics"]
    if not expected:
        assert not set(NEW) & set(metrics)
        return
    for name, unit in NEW.items():
        assert metrics[name]["unit"] == unit and metrics[name]["value"] > 0, name
    value = {name: entry["value"] for name, entry in metrics.items()}
    assert value["ft_dispatch_host_ms"] + value["ft_adopt_host_ms"] <= value["ft_step_host_ms"]
    assert value["ft_dispatch_execute_ms"] <= value["ft_dispatch_host_ms"]


def test_trace_1_rehearsal_carries_none_of_the_four():
    done = run_cell("mistral7b-1chip.ftddp", "--trace", "1")
    assert done.returncode == 0, done.stderr[-3000:]
    metrics = json.loads(done.stdout.strip().splitlines()[-1])["metrics"]
    assert metrics and not set(NEW) & set(metrics)
