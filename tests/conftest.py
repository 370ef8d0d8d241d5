"""Test configuration: run JAX on a virtual 8-device CPU mesh.

The whole multi-replica cluster is simulated in one process with threads
(reference test strategy: SURVEY.md §4) — replica groups are threads, devices
are virtual CPU devices, and the native coordination plane runs embedded on
ephemeral ports.
"""

import os
import sys
from pathlib import Path

# The suite runs on the CPU with 8 virtual devices. JAX_PLATFORMS alone
# selects the platform (the tier-1 command sets it; set here too so a bare
# `pytest` and every subprocess a test starts get the CPU as well), and
# jax_num_cpu_devices sizes the virtual mesh before the backend initializes.
os.environ["JAX_PLATFORMS"] = "cpu"
# The native plane logs to fd 2 from its own threads. pytest hands fd 2 back
# to the terminal for a moment between a test's phases, and an INFO line
# landing there splits the run's progress line — which the tier-1 command
# counts dot by dot. No test reads those lines; keep warnings and errors
# (TPUFT_LOG=info in the environment brings the chatter back).
os.environ.setdefault("TPUFT_LOG", "warn")
import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

import pytest  # noqa: E402


@pytest.fixture
def golden_param_tree():
    """``check(name, params)``: every leaf's path, shape, dtype and values
    (sha256 of their bytes) are ``name``'s in
    tests/fixtures/model_param_trees.json, written at the parent of PR 52.
    The benchmark's float32 references read a model's tree by path and its
    seeds make the weights, so a change of the model layer holds them all."""
    import hashlib
    import json

    import numpy as np

    golden = json.loads((REPO_ROOT / "tests/fixtures/model_param_trees.json").read_text())

    def check(name: str, params) -> None:
        got = {
            "/".join(str(k.key) for k in path): [
                list(leaf.shape), str(leaf.dtype),
                hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest(),
            ]
            for path, leaf in jax.tree_util.tree_leaves_with_path(params)
        }
        assert got == golden[name]

    return check


# Turn NativeToolchainMissing (no cmake/ninja, no prebuilt libtpuft.so)
# into a skip with a clear reason, wherever it surfaces — fixture setup or
# the test body. Everything else passes through untouched.


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    from torchft_tpu._native import NativeToolchainMissing

    try:
        return (yield)
    except NativeToolchainMissing as e:
        pytest.skip(f"native toolchain absent: {e}")


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    from torchft_tpu._native import NativeToolchainMissing

    try:
        return (yield)
    except NativeToolchainMissing as e:
        pytest.skip(f"native toolchain absent: {e}")


# Suite-budget ledger: full runs write SUITE_PERF.json (total wall
# seconds + the 10 slowest tests) so the CLAUDE.md suite-budget line and
# CHANGES.md cite a measured artifact instead of a remembered number.
# Gated to runs that collected a real chunk of the suite — a `-k`/single-
# file iteration must not overwrite the full-run ledger.
_SUITE_PERF_MIN_TESTS = 50


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    import json
    import time

    stats = terminalreporter.stats
    reports = [
        rep
        for key in ("passed", "failed", "skipped", "xfailed", "xpassed")
        for rep in stats.get(key, [])
        if hasattr(rep, "duration")
    ]
    tests = {rep.nodeid for rep in reports}
    if len(tests) < _SUITE_PERF_MIN_TESTS:
        return
    by_test = {}
    for rep in reports:  # sum setup/call/teardown phases per nodeid
        by_test[rep.nodeid] = by_test.get(rep.nodeid, 0.0) + rep.duration
    slowest = sorted(by_test.items(), key=lambda kv: -kv[1])[:10]
    session_start = getattr(terminalreporter, "_sessionstarttime", None)
    total = (
        time.time() - session_start
        if session_start is not None
        else sum(by_test.values())
    )
    payload = {
        "total_seconds": round(total, 1),
        "tests": len(tests),
        "exitstatus": int(getattr(exitstatus, "value", exitstatus)),
        "slowest": [
            {"test": nodeid, "seconds": round(dur, 2)} for nodeid, dur in slowest
        ],
    }
    out = REPO_ROOT / "SUITE_PERF.json"
    try:
        out.write_text(json.dumps(payload, indent=1) + "\n")
        terminalreporter.write_line(f"suite perf ledger -> {out}")
    except OSError:  # read-only checkout: the suite result still stands
        pass
