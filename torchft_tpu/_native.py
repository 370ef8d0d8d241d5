"""ctypes loader for the native coordination plane (libtpuft.so).

Role-equivalent of the reference's pyo3 module ``torchft._torchft``
(/root/reference/src/lib.rs): embeds the C++ Lighthouse and ManagerServer in
Python processes. Only server lifecycles cross the C ABI; clients speak the
framed RPC protocol directly from Python (see torchft_tpu/coordination.py).
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import shutil
import subprocess
import threading
from pathlib import Path
from typing import Tuple

_REPO_ROOT = Path(__file__).resolve().parent.parent
_NATIVE_DIR = _REPO_ROOT / "native"
_BUILD_DIR = _NATIVE_DIR / "build"
_STAMP = _BUILD_DIR / "libtpuft.so.digest"

_lib = None
_lib_lock = threading.Lock()
_has_sim_hooks = False


class NativeToolchainMissing(RuntimeError):
    """libtpuft.so is not prebuilt and the build toolchain (cmake/ninja) is
    absent, so the native plane cannot come up. tests/conftest.py converts
    this into a pytest skip ("native toolchain absent") instead of the
    opaque FileNotFoundError subprocess used to raise; ``doctor`` reports
    the same state in its toolchain check."""


def toolchain_state() -> Tuple[bool, str]:
    """(available, detail): whether the native plane can be loaded or built.

    Available means a packaged libtpuft.so exists, or both cmake and ninja
    are on PATH to build one from ``native/``."""
    for path in _packaged_paths():
        if path.exists():
            return True, f"prebuilt libtpuft.so at {path}"
    missing = [tool for tool in ("cmake", "ninja") if shutil.which(tool) is None]
    if missing:
        return False, (
            f"no prebuilt libtpuft.so and {'/'.join(missing)} not on PATH "
            "(native plane unbuildable)"
        )
    return True, "cmake+ninja available to build libtpuft.so from native/"


def has_sim_hooks() -> bool:
    """True when the loaded libtpuft.so exports the pure-function quorum
    test hooks (tpuft_quorum_compute / tpuft_compute_quorum_results)."""
    load()
    return _has_sim_hooks


def _packaged_paths() -> list[Path]:
    """Libraries someone else built and vouches for: the operator's
    ``$TPUFT_NATIVE_LIB`` and one shipped inside the package. The dev
    build under ``native/build`` is not in this list — it is only loaded
    when its stamp matches today's sources (:func:`ensure_built`)."""
    paths = []
    env = os.environ.get("TPUFT_NATIVE_LIB")
    if env:
        paths.append(Path(env))
    paths.append(Path(__file__).resolve().parent / "libtpuft.so")
    return paths


def source_digest() -> str:
    """sha256 over every file the library is built from — the files git
    would commit: ``native/CMakeLists.txt``, ``native/proto/*``,
    ``native/src/*`` (relative names and bytes, in sorted order)."""
    h = hashlib.sha256()
    files = [_NATIVE_DIR / "CMakeLists.txt"]
    for sub in ("proto", "src"):
        files += sorted(f for f in (_NATIVE_DIR / sub).iterdir() if f.is_file())
    for f in files:
        h.update(str(f.relative_to(_NATIVE_DIR)).encode() + b"\0")
        h.update(f.read_bytes())
    return h.hexdigest()


def _stamp_matches(digest: str) -> bool:
    try:
        return (_BUILD_DIR / "libtpuft.so").exists() and _STAMP.read_text() == digest
    except OSError:
        return False


def ensure_built() -> Path:
    """Returns the path to libtpuft.so, building it if necessary.

    The dev build carries a stamp of :func:`source_digest`; a library whose
    stamp is missing or differs was built from other sources (an earlier
    session, an edited ``native/src``) and is rebuilt from scratch rather
    than loaded — ``native/build`` is git-ignored, so whatever sits there
    is otherwise what would run.

    Raises :class:`NativeToolchainMissing` (not FileNotFoundError from a
    doomed subprocess) when there is nothing to load and no toolchain to
    build with — callers and the test suite key on that type."""
    for path in _packaged_paths():
        if path.exists():
            return path
    lib_path = _BUILD_DIR / "libtpuft.so"
    digest = source_digest()
    if _stamp_matches(digest):
        return lib_path
    available, detail = toolchain_state()
    if not available:
        raise NativeToolchainMissing(detail)
    # Concurrent first loads (multi-process tests, launcher children) must
    # not build over each other: serialize on the source directory itself.
    lock_fd = os.open(_NATIVE_DIR, os.O_RDONLY)
    try:
        fcntl.flock(lock_fd, fcntl.LOCK_EX)
        if _stamp_matches(digest):  # another process built it meanwhile
            return lib_path
        # From scratch: ninja decides by mtime, and a copied or checked-out
        # tree can hold an edited source that is older than its object.
        shutil.rmtree(_BUILD_DIR, ignore_errors=True)
        _BUILD_DIR.mkdir(parents=True)
        for argv in (
            ["cmake", "-B", str(_BUILD_DIR), "-G", "Ninja", str(_NATIVE_DIR)],
            ["ninja", "-C", str(_BUILD_DIR), "tpuft"],
        ):
            proc = subprocess.run(argv, capture_output=True, text=True)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"native build failed ({' '.join(argv)}):\n"
                    f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
                )
        if not lib_path.exists():
            raise RuntimeError(f"native build succeeded but {lib_path} is missing")
        _STAMP.write_text(digest)
        return lib_path
    finally:
        os.close(lock_fd)


def load() -> ctypes.CDLL:
    """Loads (building if needed) and configures the native library."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        lib = ctypes.CDLL(str(ensure_built()))

        lib.tpuft_last_error.restype = ctypes.c_char_p

        lib.tpuft_lighthouse_new.restype = ctypes.c_void_p
        lib.tpuft_lighthouse_new.argtypes = [
            ctypes.c_char_p,
            ctypes.c_uint64,
            ctypes.c_uint64,
            ctypes.c_uint64,
            ctypes.c_uint64,
        ]
        lib.tpuft_lighthouse_address.restype = ctypes.c_int
        lib.tpuft_lighthouse_address.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_int,
        ]
        lib.tpuft_lighthouse_shutdown.argtypes = [ctypes.c_void_p]
        lib.tpuft_lighthouse_free.argtypes = [ctypes.c_void_p]

        lib.tpuft_manager_new.restype = ctypes.c_void_p
        lib.tpuft_manager_new.argtypes = [
            ctypes.c_char_p,  # replica_id
            ctypes.c_char_p,  # lighthouse_addr
            ctypes.c_char_p,  # hostname
            ctypes.c_char_p,  # bind
            ctypes.c_char_p,  # store_addr
            ctypes.c_uint64,  # world_size
            ctypes.c_uint64,  # heartbeat_interval_ms
            ctypes.c_uint64,  # connect_timeout_ms
            ctypes.c_int64,  # quorum_retries
            ctypes.c_int,  # exit_on_kill
        ]
        lib.tpuft_manager_address.restype = ctypes.c_int
        lib.tpuft_manager_address.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_int,
        ]
        lib.tpuft_manager_shutdown.argtypes = [ctypes.c_void_p]
        lib.tpuft_manager_free.argtypes = [ctypes.c_void_p]

        lib.tpuft_store_new.restype = ctypes.c_void_p
        lib.tpuft_store_new.argtypes = [ctypes.c_char_p]
        lib.tpuft_store_address.restype = ctypes.c_int
        lib.tpuft_store_address.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_int,
        ]
        lib.tpuft_store_shutdown.argtypes = [ctypes.c_void_p]
        lib.tpuft_store_free.argtypes = [ctypes.c_void_p]

        # Pure-function test hooks (serialized protos in/out). Guarded: a
        # stale libtpuft.so from before these symbols existed must not take
        # down the production plane (servers/collectives) — only the sim
        # functions, which check `has_sim_hooks` and raise a clear error.
        try:
            lib.tpuft_quorum_compute.restype = ctypes.c_int
            lib.tpuft_quorum_compute.argtypes = [
                ctypes.c_char_p,
                ctypes.c_int,
                ctypes.c_char_p,
                ctypes.c_int,
            ]
            lib.tpuft_compute_quorum_results.restype = ctypes.c_int
            lib.tpuft_compute_quorum_results.argtypes = [
                ctypes.c_char_p,
                ctypes.c_int64,
                ctypes.c_char_p,
                ctypes.c_int,
                ctypes.c_int,
                ctypes.c_char_p,
                ctypes.c_int,
            ]
            has_sim_hooks = True
        except AttributeError:
            has_sim_hooks = False

        global _has_sim_hooks
        _has_sim_hooks = has_sim_hooks
        _lib = lib
        return _lib


def last_error() -> str:
    lib = load()
    err = lib.tpuft_last_error()
    return err.decode() if err else "unknown native error"
