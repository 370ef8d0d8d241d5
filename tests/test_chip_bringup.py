"""The seams the chip bring-up added: chip_smoke.py's rehearsal and its
refusal without a chip, the placeable compile cache, the native library's
source stamp, the launcher's chip assignment, and the probes that moved
in-process. Everything here runs on the CPU; what it guards is that nothing
slides onto the CPU when a chip was asked for.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(argv, **env):
    return subprocess.run(
        [sys.executable, *argv],
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "TPUFT_LOG": "warn", **env},
        capture_output=True,
        text=True,
        timeout=300,
    )


# ---------------------------------------------------------------------------
# chip_smoke.py: a rehearsal says so; no chip, no result
# ---------------------------------------------------------------------------


def test_chip_smoke_rehearsal_names_the_cpu_and_passes() -> None:
    proc = _run(["chip_smoke.py", "--rehearse"])
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert "REHEARSAL at tiny size on platform cpu" in proc.stdout
    summary = json.loads(
        next(l for l in lines if l.startswith("summary: "))[len("summary: "):]
    )
    assert summary["claim"] is None and list(summary)[-1] == "claim"
    assert summary["phases"]["ft-ddp"]["all_committed"] is True
    assert summary["phases"]["kill-heal"] == {
        **summary["phases"]["kill-heal"],
        "survivor_steps_lost": 0,
        "bitwise_identical": True,
    }


def test_chip_smoke_refuses_to_run_without_a_chip() -> None:
    """The default invocation needs a TPU: on the CPU it exits non-zero,
    says why, and prints no result line under any device's name."""
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "cpu" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_imports_the_harness_and_carries_no_copy() -> None:
    """``chip_smoke.py -> chipbench.harness -> torchft_tpu``: the smoke's
    ledger and fragment split ARE the benchmark's, and importing the smoke
    (the harness with it) initializes no backend — the parent of
    ``--chips 4`` hands the chips to its workers."""
    import chip_smoke
    import chipbench.harness

    assert chip_smoke.CompileLedger is chipbench.harness.CompileLedger
    assert chip_smoke.balanced_fragments is chipbench.harness.balanced_fragments
    proc = _run(
        [
            "-c",
            "import chip_smoke;"
            "from jax._src import xla_bridge;"
            "assert not xla_bridge.backends_are_initialized()",
        ]
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_require_tpu_exits_on_the_cpu() -> None:
    from torchft_tpu.utils.platform import require_tpu

    with pytest.raises(SystemExit, match="no TPU"):
        require_tpu()


# ---------------------------------------------------------------------------
# the compile cache can be placed from outside, and never moves by itself
# ---------------------------------------------------------------------------


@pytest.fixture
def _restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def test_compile_cache_env_var_wins(monkeypatch, _restore_cache_config) -> None:
    from torchft_tpu.utils import platform

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(platform.COMPILE_CACHE_ENV, "/somewhere/placed/from/outside")
    assert platform.enable_compile_cache() == "/somewhere/placed/from/outside"
    # No other directory is set in code: jax reads the variable itself.
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_path_in_checkout(
    monkeypatch, _restore_cache_config
) -> None:
    from torchft_tpu.utils import platform

    monkeypatch.delenv(platform.COMPILE_CACHE_ENV, raising=False)
    first = platform.enable_compile_cache()
    assert first == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    # Exported, so children inherit the same directory — and a second call
    # (now finding the variable) lands on the same path.
    assert os.environ[platform.COMPILE_CACHE_ENV] == first
    assert platform.enable_compile_cache() == first
    monkeypatch.delenv(platform.COMPILE_CACHE_ENV)


def test_compile_cache_path_is_never_temp_pid_or_time(monkeypatch) -> None:
    from torchft_tpu.utils import platform

    path = str(platform._CHECKOUT_CACHE_DIR)
    assert not path.startswith(tempfile.gettempdir())
    assert str(os.getpid()) not in Path(path).name
    assert ".jax_cache/" in (REPO / ".gitignore").read_text()
    # One place in the code names the config option: the helper.
    setters = [
        str(py.relative_to(REPO))
        for top in ("torchft_tpu", "examples", "benchmarks", "scripts")
        for py in (REPO / top).rglob("*.py")
        if '"jax_compilation_cache_dir"' in py.read_text()
    ] + [
        name
        for name in ("chip_smoke.py", "__graft_entry__.py")
        if '"jax_compilation_cache_dir"' in (REPO / name).read_text()
    ]
    assert setters == ["torchft_tpu/utils/platform.py"]


# ---------------------------------------------------------------------------
# libtpuft.so is built from the files git would commit
# ---------------------------------------------------------------------------


def test_ensure_built_rebuilds_on_changed_source_digest(tmp_path, monkeypatch) -> None:
    """A library whose stamp does not match today's sources is rebuilt, not
    loaded. The build itself is faked (it is cmake + ninja, minutes on one
    core): what is pinned is when it runs."""
    from torchft_tpu import _native

    native = tmp_path / "native"
    (native / "src").mkdir(parents=True)
    (native / "proto").mkdir()
    (native / "CMakeLists.txt").write_text("project(x)\n")
    (native / "src" / "a.cc").write_text("int a;\n")
    (native / "proto" / "p.proto").write_text("syntax = 'proto3';\n")
    build = native / "build"
    monkeypatch.setattr(_native, "_NATIVE_DIR", native)
    monkeypatch.setattr(_native, "_BUILD_DIR", build)
    monkeypatch.setattr(_native, "_STAMP", build / "libtpuft.so.digest")
    monkeypatch.setattr(_native, "_packaged_paths", lambda: [])
    builds = []

    def fake_run(argv, **_):
        if argv[0] == "ninja":
            builds.append(_native.source_digest())
            (build / "libtpuft.so").write_text("built from " + builds[-1])
        return subprocess.CompletedProcess(argv, 0, "", "")

    monkeypatch.setattr(_native.subprocess, "run", fake_run)

    lib = _native.ensure_built()
    assert lib == build / "libtpuft.so" and len(builds) == 1
    assert _native.ensure_built() == lib and len(builds) == 1  # fresh: loaded as is

    (native / "src" / "a.cc").write_text("int a = 1;\n")  # touch a source
    _native.ensure_built()
    assert len(builds) == 2 and builds[0] != builds[1]
    assert lib.read_text() == "built from " + builds[1]  # old library gone

    (build / "libtpuft.so.digest").unlink()  # a library nobody stamped
    _native.ensure_built()
    assert len(builds) == 3

    (native / "proto" / "p.proto").write_text("syntax = 'proto3'; // v2\n")
    (native / "CMakeLists.txt").write_text("project(y)\n")
    _native.ensure_built()
    assert len(builds) == 4


# ---------------------------------------------------------------------------
# one process for each chip
# ---------------------------------------------------------------------------

_PRINT_ENV = (
    "import json, os; open(os.path.join(os.environ['OUT'], "
    "os.environ['REPLICA_GROUP_ID'] + '_' + os.environ['GROUP_RANK']), 'w')"
    ".write(json.dumps({k: v for k, v in os.environ.items() if k.startswith('TPU_')}))"
)


@pytest.mark.parametrize("groups,ranks,per", [(2, 1, 2), (4, 1, 1), (2, 2, 1)])
def test_supervise_hands_children_disjoint_chips(
    tmp_path, monkeypatch, groups, ranks, per
) -> None:
    """Two two-chip and four one-chip processes are the shapes that ran on
    the chip; (2, 2, 1) is the second again as far as libtpu can tell: the
    ranks of a multi-rank group are isolated one-chip processes too."""
    from torchft_tpu import launch

    monkeypatch.setattr(launch, "local_chip_count", lambda: 4)
    monkeypatch.delenv("JAX_PLATFORMS")  # the caller did not ask for the CPU
    rc = launch.supervise(
        [sys.executable, "-c", _PRINT_ENV],
        num_replica_groups=groups,
        group_world_size=ranks,
        lighthouse_addr="127.0.0.1:1",  # stub children never dial it
        relaunch_interval=0.1,
        extra_env={"OUT": str(tmp_path)},
    )
    assert rc == 0
    seen = []
    for g in range(groups):
        for r in range(ranks):
            env = json.loads((tmp_path / f"{g}_{r}").read_text())
            chips = env["TPU_VISIBLE_CHIPS"].split(",")
            assert len(chips) == per
            assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
            assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == launch._CHIP_BOUNDS[per]
            seen += chips
    assert sorted(seen) == [str(c) for c in range(groups * ranks * per)]  # disjoint


def test_chip_envs_refuses_more_processes_than_chips_unless_cpu_by_name(
    monkeypatch,
) -> None:
    from torchft_tpu import launch

    monkeypatch.setattr(launch, "local_chip_count", lambda: 1)
    with pytest.raises(RuntimeError, match="a chip belongs to one process"):
        launch.chip_envs(2, {})
    assert launch.chip_envs(2, {"JAX_PLATFORMS": "cpu"}) == [{}, {}]
    assert launch.chip_envs(2, {"JAX_PLATFORMS": " CPU "}) == [{}, {}]  # cpu_by_name's rule
    with pytest.raises(RuntimeError, match="a chip belongs to one process"):
        launch.chip_envs(2, {"JAX_PLATFORMS": "cpu,tpu"})  # a list is not "by name"
    assert launch.chip_envs(1, {}) == [{}]  # owns the whole host: nothing to set
    monkeypatch.setattr(launch, "local_chip_count", lambda: 0)
    assert launch.chip_envs(3, {}) == [{}, {}, {}]  # no chips: jax picks the CPU


def test_chip_envs_refuses_a_share_nobody_has_run(monkeypatch) -> None:
    """An 8-chip host has enough chips for two groups, but a four-chip share
    has no verified process bounds: that is its own error, not the
    'fewer chips than processes' one."""
    from torchft_tpu import launch

    monkeypatch.setattr(launch, "local_chip_count", lambda: 8)
    with pytest.raises(RuntimeError, match="chip share 4 not verified") as err:
        launch.chip_envs(2, {})
    assert "belongs to one process" not in str(err.value)
    assert [e["TPU_VISIBLE_CHIPS"] for e in launch.chip_envs(4, {})] == [
        "0,1", "2,3", "4,5", "6,7",
    ]


def test_supervise_refuses_a_clustered_group_over_local_chips(monkeypatch) -> None:
    """chip_envs isolates every process; ranks that are to form one JAX
    cluster need real process bounds and addresses, which have not run."""
    from torchft_tpu import launch

    monkeypatch.setattr(launch, "local_chip_count", lambda: 4)
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="not verified"):
        launch.supervise(
            [sys.executable, "-c", "pass"],
            num_replica_groups=1,
            group_world_size=2,
            jax_coordinator_port_base=29700,
            lighthouse_addr="127.0.0.1:1",
        )


@pytest.mark.parametrize(
    "vfio,vendors,want",
    [
        ([2], ["0x1ae0"] * 4, 1),  # a sandbox passing one of four chips through
        ([0, 1, 2, 3], ["0x1ae0"] * 4 + ["0x8086"], 4),
        ([5, 6], ["0x10de", "0x8086"], 0),  # VFIO devices, none of them a TPU
    ],
)
def test_local_chip_count_takes_vfio_files_only_where_sysfs_lists_tpus(
    tmp_path, monkeypatch, vfio, vendors, want
) -> None:
    from torchft_tpu import launch

    files = {
        "/dev/vfio/[0-9]*": [f"/dev/vfio/{n}" for n in vfio],
        "/dev/accel[0-9]*": [],
        "/sys/bus/pci/devices/*/vendor": [],
    }
    for i, vendor in enumerate(vendors):
        (tmp_path / f"vendor{i}").write_text(vendor + "\n")
        files["/sys/bus/pci/devices/*/vendor"].append(str(tmp_path / f"vendor{i}"))
    monkeypatch.setattr(launch.glob, "glob", files.__getitem__)
    assert launch.local_chip_count() == want


def test_launcher_import_leaves_jax_backends_alone() -> None:
    """The launcher parents processes that need the chips: importing it (and
    the lighthouse it embeds) must not initialize a backend."""
    proc = _run(
        [
            "-c",
            "import torchft_tpu.launch, torchft_tpu.coordination;"
            "from jax._src import xla_bridge;"
            "assert not xla_bridge.backends_are_initialized()",
        ]
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


# ---------------------------------------------------------------------------
# probes that used to run in a child now run where the device is held
# ---------------------------------------------------------------------------


def test_quarantine_probe_runs_in_process(monkeypatch) -> None:
    from torchft_tpu import health

    spawned = []
    monkeypatch.setattr(subprocess, "Popen", lambda *a, **k: spawned.append(a))
    monkeypatch.delenv(health.ENV_PROBE, raising=False)
    assert health._default_probe() is True  # a real round trip, on this backend
    assert spawned == []
    # A device that never answers fails the probe at its deadline.
    answered = threading.Event()
    monkeypatch.setenv(health.ENV_PROBE_TIMEOUT, "0.05")
    monkeypatch.setattr(
        "torchft_tpu.utils.platform.device_round_trip", lambda: answered.wait(30)
    )
    try:
        assert health._default_probe() is False
    finally:
        answered.set()  # let the abandoned probe thread finish
    monkeypatch.setenv(health.ENV_PROBE, "0")
    assert health._default_probe() is True


def test_doctor_reports_the_platform_that_answered() -> None:
    from torchft_tpu import doctor

    state, detail = doctor._check_device()
    assert state == "WARN" and "jax answered on cpu" in detail


# ---------------------------------------------------------------------------
# the flash dispatcher's backward under a sharded step (CPU fallback path)
# ---------------------------------------------------------------------------


def test_flash_gradients_under_fsdp_tp_mesh_match_unsharded() -> None:
    """Under ``jax.set_mesh`` the flash path shard_maps itself over fsdp/tp;
    off-chip its backward is the blockwise scan, whose carry must be varying
    over the manual axes (jax 0.9 checks it)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchft_tpu.ops.attention import flash_under_mesh

    b, s_, h, kv, d = 4, 32, 4, 2, 16
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, s_, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s_, kv, d), jnp.float32)
    v = jax.random.normal(kv_, (b, s_, kv, d), jnp.float32)

    def loss(q, k, v):
        out = flash_under_mesh(q, k, v, scale=d**-0.5, block_q=16, block_k=128)
        return jnp.sum(out**2)

    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    want_loss, want = grad(q, k, v)  # no mesh bound: the plain kernel call
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("fsdp", "tp"))
    spec = NamedSharding(mesh, P("fsdp", None, "tp", None))
    with jax.set_mesh(mesh):
        got_loss, got = grad(*(jax.device_put(x, spec) for x in (q, k, v)))
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4)
