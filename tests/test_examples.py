"""Smoke-run every example as a real subprocess — the files users copy
first must never rot. Single replica group, tiny workloads, CPU platform.

The --demo chaos variants (multi-process kill/restart/heal) are NOT run
here — that behavior is covered by the heavier harnesses
(tests/test_multiprocess_e2e.py, tests/test_chaos_soak.py under
TPUFT_SOAK=1); this file keeps per-example cost to one process + one jit.

The whole module is marked ``slow`` (~100 s of subprocess smoke runs):
the tier-1 gate runs ``-m 'not slow'`` so new per-round tests fit its
budget; the full suite (plain ``pytest tests/``) still runs these.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parent.parent
EXAMPLES = REPO / "examples"


@pytest.fixture(scope="module")
def lighthouse():
    from torchft_tpu.coordination import LighthouseServer

    server = LighthouseServer(min_replicas=1, join_timeout_ms=500)
    yield server
    server.shutdown()


def _virtual_devices(n: int) -> dict:
    """The caller asks for a CPU mesh by name; no example forces one."""
    return {"XLA_FLAGS": f"--xla_force_host_platform_device_count={n}"}


def _run(script: str, args: list, lighthouse, timeout: int = 180, env=None):
    full_env = {
        **os.environ,
        "TPUFT_LIGHTHOUSE": lighthouse.address(),
        "REPLICA_GROUP_ID": "0",
        "JAX_PLATFORMS": "cpu",
        "TPUFT_LOG": "warn",
        **(env or {}),
    }
    proc = subprocess.run(
        [sys.executable, str(EXAMPLES / script), *map(str, args)],
        env=full_env,
        timeout=timeout,
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 0, (
        f"{script} rc={proc.returncode}\nstdout:\n{proc.stdout[-3000:]}\n"
        f"stderr:\n{proc.stderr[-3000:]}"
    )
    return proc.stdout


def test_train_ddp(lighthouse):
    out = _run(
        "train_ddp.py",
        ["--num-replica-groups", 1, "--steps", 2, "--batch-size", 4],
        lighthouse,
    )
    assert "param_digest=" in out


def test_train_ddp_microbatched(lighthouse):
    out = _run(
        "train_ddp.py",
        [
            "--num-replica-groups", 1, "--steps", 2, "--batch-size", 4,
            "--microbatches", 2,
        ],
        lighthouse,
    )
    assert "param_digest=" in out


def test_train_diloco(lighthouse):
    out = _run(
        "train_diloco.py",
        [
            "--num-replica-groups", 1, "--syncs", 1, "--sync-every", 2,
            "--batch-size", 4, "--hidden", 32,
        ],
        lighthouse,
    )
    assert "global_digest=" in out


def test_train_hsdp(lighthouse):
    out = _run(
        "train_hsdp.py",
        [
            "--num-replica-groups", 1, "--steps", 2, "--batch-size", 4,
            "--seq-len", 32,
        ],
        lighthouse,
        env=_virtual_devices(2),
    )
    assert "param_digest=" in out


def test_train_hsdp_fit_levers(lighthouse):
    """scan-layers + dots-remat + fused CE compose with the HSDP sharding."""
    out = _run(
        "train_hsdp.py",
        [
            "--num-replica-groups", 1, "--steps", 2, "--batch-size", 4,
            "--seq-len", 32,
            "--scan-layers", "--remat", "--fused-ce",
        ],
        lighthouse,
        env=_virtual_devices(2),
    )
    assert "param_digest=" in out


def test_train_longcontext(lighthouse):
    out = _run(
        "train_longcontext.py",
        [
            "--num-replica-groups", 1, "--steps", 1, "--batch-size", 2,
            "--seq-len", 128, "--sp", 2,
        ],
        lighthouse,
        env=_virtual_devices(2),
    )
    assert "param_digest=" in out


def test_orchestrate(lighthouse):
    # Self-contained: embeds its own lighthouse; mtbf=0 disables chaos.
    proc = subprocess.run(
        [
            sys.executable, str(EXAMPLES / "orchestrate.py"),
            "--groups", "1", "--steps", "3", "--mtbf", "0",
        ],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "TPUFT_LOG": "warn"},
        timeout=180,
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "digest=" in proc.stdout
