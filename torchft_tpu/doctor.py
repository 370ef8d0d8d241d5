"""Preflight diagnostics: ``python -m torchft_tpu.doctor``.

Checks the things that actually break real deployments — native plane,
control-plane connectivity, accelerator backend, kernel sanity, env-var
typos — and prints one PASS/WARN/FAIL line each, exiting non-zero iff
something FAILed. Beyond-reference ops tooling (torchft debugging leans
on torchrun/NCCL envs; this stack's moving parts are different), built
from the failure modes deployments actually hit: no accelerator behind jax,
unbuildable native lib, unreachable lighthouse, misspelled ``TPUFT_*``
vars silently ignored.

Usage::

    python -m torchft_tpu.doctor [--lighthouse host:port] [--skip-device]
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Callable, List, Tuple

# Everything this process recognizes; drift is caught by the test that
# greps the tree for os.environ reads of TPUFT_* names.
KNOWN_ENV = {
    "TPUFT_LIGHTHOUSE", "TPUFT_MANAGER_PORT", "TPUFT_TIMEOUT_SEC",
    "TPUFT_QUORUM_TIMEOUT_SEC", "TPUFT_CONNECT_TIMEOUT_SEC",
    "TPUFT_QUORUM_RETRIES", "TPUFT_WATCHDOG_TIMEOUT_SEC", "TPUFT_BUCKET_MB",
    "TPUFT_TELEMETRY", "TPUFT_LOG", "TPUFT_STORE_ADDR", "TPUFT_WIRE_DTYPE",
    "TPUFT_JAX_COORDINATOR", "TPUFT_TCP_RING_MIN_MB", "TPUFT_TRACE_LOG",
    "TPUFT_NATIVE_LIB", "TPUFT_ALLOW_UNSAFE_PICKLE", "TPUFT_SOAK",
    "TPUFT_FLIGHT_RECORDER", "TPUFT_FLIGHT_RECORDER_SIZE",
    "TPUFT_STRICT_COMMIT", "TPUFT_COMMIT_PIPELINE",
    "TPUFT_EMULATED_DEVICE_RTT_MS",
    # Depth-N commit pipelining: window depth (int or "auto") and the
    # adaptive controller's depth ceiling.
    "TPUFT_COMMIT_PIPELINE_DEPTH", "TPUFT_COMMIT_PIPELINE_ADAPTIVE",
    # Heal-path hardening: joiner-side progress floor, bounded failover
    # attempts, and the punisher's stream-fault arming channel.
    "TPUFT_HEAL_MIN_BYTES_PER_SEC", "TPUFT_HEAL_MAX_ATTEMPTS",
    "TPUFT_FAULT_FILE",
    # Multi-donor striped heal + delta rejoin (checkpointing/
    # http_transport.py): stripe switch, donor-set cap, delta switch.
    "TPUFT_HEAL_STRIPE", "TPUFT_HEAL_STRIPE_MAX_DONORS", "TPUFT_HEAL_DELTA",
    # Mass-rejoin storm plane: joiner-side aggregate ingress bound (the
    # stripe workers of one heal share one token bucket) and the storm
    # soak's round count (tests/test_chaos_soak.py).
    "TPUFT_HEAL_INGRESS_GBPS", "TPUFT_STORM_SOAK_ROUNDS",
    # Donor sidecar (out-of-process heal serving, checkpointing/
    # serve_child.py): mode switch, snapshot dir (shared-memory tmpfs),
    # child niceness, egress bound, respawn budget.
    "TPUFT_HEAL_SERVE_MODE", "TPUFT_HEAL_SERVE_DIR", "TPUFT_HEAL_SERVE_NICE",
    "TPUFT_HEAL_SERVE_GBPS", "TPUFT_HEAL_SERVE_MAX_RESTARTS",
    # Paced-egress fairness: heal streams' guaranteed share of the
    # serve-rate bucket while serving readers are also active.
    "TPUFT_HEAL_SERVE_PRIORITY_SHARE",
    # Committed-weights serving plane (torchft_tpu/serving): publication
    # cadence + chunking, relay poll cadence, long-poll push edge
    # (switch + bounded server-side hold), multi-tenant fairness + auth
    # (bearer-token table + per-tenant egress entitlements).
    "TPUFT_PUBLISH_EVERY", "TPUFT_PUBLISH_CHUNKS", "TPUFT_SERVING_POLL_SEC",
    "TPUFT_SERVING_NOTIFY", "TPUFT_SERVING_NOTIFY_HOLD_SEC",
    "TPUFT_SERVING_TENANT_TOKENS", "TPUFT_SERVING_TENANT_GBPS",
    # Versioned weight history (torchft_tpu/history.py): resident-bytes
    # budget + version-count cap for the committed-snapshot rings
    # (manager state ring, serving staged ring, relay ring).
    "TPUFT_HISTORY_BYTES", "TPUFT_HISTORY_MAX_VERSIONS",
    "TPUFT_METRICS_PORT", "TPUFT_METRICS_PUSH_SEC",
    # ZeRO plane (torchft_tpu/zero.py): enable flag for the harness/bench
    # loops, fleet-wide shard count, assignment policy, joiner heal
    # policy for shard parts, bench sizing.
    "TPUFT_ZERO", "TPUFT_ZERO_SHARDS", "TPUFT_ZERO_REBALANCE",
    "TPUFT_ZERO_HEAL_SHARDS", "TPUFT_ZERO_BENCH_ELEMS",
    # Quantized wire plane (torchft_tpu/wire_codec.py): per-wire-class
    # codecs for heal chunks, serving fan-out, and the ZeRO shard legs
    # (fp32 default = bit-for-bit the pre-codec wire).
    "TPUFT_HEAL_CODEC", "TPUFT_SERVING_CODEC", "TPUFT_ZERO_CODEC",
    "TPUFT_EMULATED_RTT_MS", "TPUFT_EMULATED_GBPS",
    # WAN topology matrix (utils/netem.py): replica-id -> region map,
    # explicit self-region override, relay-tier region pin, and the heal
    # plane's per-donor bandwidth EWMA smoothing factor. Per-pair link
    # envs (TPUFT_EMULATED_LINK_<SRC>_<DST> / _LOCAL / _CROSS) are
    # prefix-matched in _check_env rather than enumerated here.
    "TPUFT_EMULATED_TOPOLOGY", "TPUFT_EMULATED_REGION",
    "TPUFT_SERVING_REGION", "TPUFT_HEAL_BW_EWMA_ALPHA",
    # Correctness tooling: runtime lock-order detector + static analyzer
    # (python -m torchft_tpu.analysis; docs/static_analysis.md).
    "TPUFT_LOCK_CHECK", "TPUFT_ANALYSIS_REFERENCE", "TPUFT_ANALYSIS_BASELINE",
    # Interleaving explorer budgets (python -m torchft_tpu.analysis
    # --explore; utils/schedules.explore_defaults): schedule budget, RNG
    # seed, max preemption bound, random long-tail count.
    "TPUFT_EXPLORE_BUDGET", "TPUFT_EXPLORE_SEED", "TPUFT_EXPLORE_PREEMPTIONS",
    "TPUFT_EXPLORE_RANDOM",
    # Fleet trace plane (torchft_tpu/tracing.py): recording switch, journal
    # ring size, store clock-beacon sampling switch.
    "TPUFT_TRACE", "TPUFT_TRACE_SIZE", "TPUFT_TRACE_CLOCK",
    # Goodput ledger + SLO plane (torchft_tpu/goodput.py): ledger window
    # width, retained-window count + byte budget, and the declarative
    # goodput SLO (target fraction, K-consecutive-windows hysteresis,
    # burn-rate trip multiplier).
    "TPUFT_GOODPUT_WINDOW_SEC", "TPUFT_GOODPUT_WINDOWS", "TPUFT_GOODPUT_BYTES",
    "TPUFT_SLO_GOODPUT", "TPUFT_SLO_WINDOWS", "TPUFT_SLO_BURN_RATE",
    # Gray-failure ejection plane (torchft_tpu/health.py): master switch,
    # verdict knobs (fleet-relative threshold / hysteresis windows / peer
    # freshness / absolute gap floor), board push cadence, wedge watchdog
    # (deadline scale + floor + escalation action), injected-stall size,
    # self-probe toggles, and the quarantine gate (backoff base/cap,
    # crash-loop sliding window + park cooldown, state dir).
    "TPUFT_HEALTH", "TPUFT_HEALTH_THRESHOLD", "TPUFT_HEALTH_CONSECUTIVE",
    "TPUFT_HEALTH_MIN_PEERS", "TPUFT_HEALTH_EWMA_ALPHA",
    "TPUFT_HEALTH_PEER_TTL_SEC", "TPUFT_HEALTH_PUSH_SEC",
    "TPUFT_HEALTH_MIN_GAP_SEC", "TPUFT_HEALTH_WEDGE_SCALE",
    "TPUFT_HEALTH_WEDGE_FLOOR_SEC", "TPUFT_HEALTH_WEDGE_ACTION",
    "TPUFT_HEALTH_SLOW_MS", "TPUFT_HEALTH_PROBE",
    "TPUFT_HEALTH_PROBE_TIMEOUT_SEC", "TPUFT_QUARANTINE_BASE_SEC",
    "TPUFT_QUARANTINE_CAP_SEC", "TPUFT_QUARANTINE_MAX_EJECTS",
    "TPUFT_QUARANTINE_WINDOW_SEC", "TPUFT_QUARANTINE_PARK_SEC",
    "TPUFT_QUARANTINE_DIR",
    # Progressive delivery (torchft_tpu/serving/rollout.py): per-tenant
    # stream policy table, sha256 canary-cohort width, shadow-tenant
    # list, verdict actuation mode (actuate|alert), and the rollout
    # evaluator's hysteresis knobs (multiplicative threshold /
    # K-consecutive windows / absolute gap floor / evidence floor).
    "TPUFT_ROLLOUT_POLICY", "TPUFT_ROLLOUT_CANARY_PERCENT",
    "TPUFT_ROLLOUT_SHADOW_TENANTS", "TPUFT_ROLLOUT_MODE",
    "TPUFT_ROLLOUT_THRESHOLD", "TPUFT_ROLLOUT_WINDOWS",
    "TPUFT_ROLLOUT_MIN_GAP", "TPUFT_ROLLOUT_MIN_SAMPLES",
    # Repo tooling outside the package (tests/benchmarks/sentinel) — real
    # knobs a user may have exported; not typos.
    "TPUFT_SOAK_SECONDS", "TPUFT_SOAK_SEED",
    "TPUFT_REGEN_FIXTURES",
    "TPUFT_TRANSPORT_BENCH_GB", "TPUFT_TRANSPORT_BENCH_MODE",
    "TPUFT_TRANSPORT_BENCH_DEADLINE", "TPUFT_TRANSPORT_RSS_BOUND",
    "TPUFT_TRANSPORT_BENCH_PACE_GBPS", "TPUFT_TRANSPORT_BENCH_STRIPE_GBPS",
    "TPUFT_CPS_REPLICAS", "TPUFT_CPS_ROUNDS", "TPUFT_CPS_GROUP_WORLD_SIZE",
    "TPUFT_STORM_BENCH_MB", "TPUFT_STORM_BENCH_GBPS",
    "TPUFT_STORM_BENCH_INGRESS_GBPS", "TPUFT_STORM_BENCH_DEADLINE",
    "TPUFT_WAN_BENCH_MB", "TPUFT_WAN_BENCH_DEADLINE",
    "TPUFT_QUANT_BENCH_BYTES",
}

Check = Tuple[str, Callable[[], Tuple[str, str]]]  # name -> (status, detail)


def _check_toolchain() -> Tuple[str, str]:
    """Native build toolchain state. WARN, not FAIL, when absent: the
    pure-python planes still work and the test suite skips (not errors) the
    native-gated cases — but the operator should know why."""
    from torchft_tpu import _native

    available, detail = _native.toolchain_state()
    return ("PASS" if available else "WARN"), detail


def _check_native() -> Tuple[str, str]:
    from torchft_tpu import _native

    try:
        path = _native.ensure_built()
    except _native.NativeToolchainMissing as e:
        return "FAIL", f"native plane unavailable: {e}"
    return "PASS", f"libtpuft loaded ({path})"


def _check_lighthouse(address: str) -> Tuple[str, str]:
    if not address:
        return "WARN", "no --lighthouse / TPUFT_LIGHTHOUSE set; skipped"
    from torchft_tpu.coordination import LighthouseClient

    client = LighthouseClient(address, connect_timeout=5.0)
    status = client.status(timeout=5.0)
    return (
        "PASS",
        f"lighthouse at {address} answered "
        f"({len(status.members)} members, has_quorum={status.has_quorum})",
    )


def _check_store() -> Tuple[str, str]:
    from torchft_tpu.parallel.store import StoreClient, StoreServer

    server = StoreServer()
    try:
        client = StoreClient(server.address())
        client.set("doctor/ping", b"ok")
        if client.get("doctor/ping", timeout=5.0) != b"ok":
            return "FAIL", "KV roundtrip returned wrong value"
        return "PASS", "native KV store roundtrip ok"
    finally:
        server.shutdown()


def _check_device() -> Tuple[str, str]:
    """A compile→execute→fetch round trip on the default device, reported
    under the platform that answered. In-process: the chip belongs to one
    process at a time, so the doctor is run on its own before a trainer
    starts, not beside one. With no chip JAX answers on the CPU — a WARN
    that names it, never a pass under the accelerator's name."""
    import jax

    from torchft_tpu.utils.platform import device_round_trip

    dev = jax.devices()[0]
    if not device_round_trip():
        return "FAIL", f"{dev.platform} ({dev.device_kind}) returned a wrong matmul"
    detail = f"{dev.platform} {dev.device_kind} x{len(jax.devices())}"
    if dev.platform == "cpu":
        return "WARN", f"no accelerator: jax answered on {detail}"
    return "PASS", f"accelerator round trip ok ({detail})"


def _check_kernels() -> Tuple[str, str]:
    import numpy as np

    from torchft_tpu.ops import quantization as q

    x = np.linspace(-3, 3, 1000, dtype=np.float32)
    for wire in ("fp8", "int8", "int4"):
        payload, scales = q.quantize_blocks(x, wire=wire)
        back = q.dequantize_blocks(payload, scales, x.shape, x.dtype)
        if not np.allclose(back, x, atol=0.5):
            return "FAIL", f"{wire} codec roundtrip error"
    return "PASS", "host wire codecs (fp8/int8/int4) roundtrip ok"


def _check_wire_codec_negotiation() -> Tuple[str, str]:
    """Quantized-wire-plane preflight. WARN, never FAIL: the codec knobs
    change the wire FORMAT, so the thing that breaks real deployments is
    a mixed fleet — a codec-less (format-2) peer refuses an encoded
    donor's format-3 /meta cleanly and the heal retries elsewhere, which
    in a fully mixed fleet means "falls back to operators setting fp32",
    never a silent misdecode. This check names that, probes an
    encode/decode roundtrip per configured codec, and flags the
    bitwise-heal envelope."""
    from torchft_tpu import wire_codec

    knobs = []
    for env in (
        wire_codec.ENV_HEAL_CODEC,
        wire_codec.ENV_SERVING_CODEC,
        wire_codec.ENV_ZERO_CODEC,
    ):
        raw = os.environ.get(env)
        if raw is None or raw.strip() == "":
            continue
        try:
            codec = wire_codec._env_codec(env)
        except ValueError:
            return (
                "WARN",
                f"{env}={raw!r} is not one of {sorted(wire_codec.CODECS)}; "
                "the plane would refuse to stage — unset it or pick a "
                "valid codec",
            )
        if codec != "fp32":
            knobs.append(f"{env}={codec}")
    if not knobs:
        return (
            "PASS",
            "all bulk wires fp32 (bit-for-bit pre-codec format; "
            "TPUFT_HEAL_CODEC/TPUFT_SERVING_CODEC/TPUFT_ZERO_CODEC unset)",
        )
    try:
        import numpy as np

        probe = {"w": np.linspace(-2, 2, 4096, dtype=np.float32)}
        for knob in knobs:
            codec = knob.split("=", 1)[1]
            enc, stats = wire_codec.encode_state(probe, codec)
            wire_codec.decode_state(enc)
            if stats["encoded_leaves"] != 1:
                return "WARN", f"{codec} probe encoded nothing"
    except Exception as e:  # noqa: BLE001 — WARN-never-FAIL probe
        return "WARN", f"codec roundtrip probe failed: {e}"
    return (
        "WARN",
        f"{', '.join(knobs)}: encoded stages are /meta format 3 — "
        "codec-less peers refuse them cleanly and a MIXED fleet must fall "
        "back to fp32 (unset the knob) until every peer is codec-aware; "
        "quantized HEALS are lossy per adoption (pair with ZeRO, whose "
        "next allgather re-syncs params bitwise, or DiLoCo outer syncs)",
    )


def _check_metrics() -> Tuple[str, str]:
    """Probes the local /metrics endpoint when TPUFT_METRICS_PORT is set.
    Never FAILs: the metrics plane is optional, and a dead scrape endpoint
    must not block a launch the way a dead native plane should."""
    from torchft_tpu import metrics

    value = os.environ.get(metrics.ENV_PORT, "")
    if not value:
        return (
            "PASS",
            f"metrics export off (set {metrics.ENV_PORT} to serve /metrics)",
        )
    try:
        port = int(value)
    except ValueError:
        return "WARN", f"{metrics.ENV_PORT}={value!r} is not an integer"
    import urllib.request

    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ) as resp:
            body = resp.read().decode(errors="replace")
    except Exception as e:  # noqa: BLE001 — WARN, never FAIL, on any probe error
        return (
            "WARN",
            f"no /metrics listener on 127.0.0.1:{port} ({e}) — is a "
            "replica (or metrics.maybe_start_http_server) running here?",
        )
    n_series = sum(
        1 for line in body.splitlines() if line and not line.startswith("#")
    )
    return "PASS", f"/metrics on :{port} serving {n_series} series"


def _check_trace() -> Tuple[str, str]:
    """Fleet trace plane preflight: validates the TPUFT_TRACE* knobs and
    probes the local /trace.json surface when a metrics port is up.
    WARN, never FAIL: the trace plane is observability — a dead journal
    endpoint must not block a launch."""
    from torchft_tpu import tracing

    if os.environ.get(tracing.ENV_TRACE, "1") == "0":
        return "PASS", f"trace plane off ({tracing.ENV_TRACE}=0)"
    size_raw = os.environ.get(tracing.ENV_SIZE)
    if size_raw is not None:
        try:
            if int(size_raw) < 1:
                raise ValueError
        except ValueError:
            return "WARN", f"{tracing.ENV_SIZE}={size_raw!r} is not a positive int"
    value = os.environ.get("TPUFT_METRICS_PORT", "")
    if not value:
        return (
            "PASS",
            "trace plane on (journal in-process; set TPUFT_METRICS_PORT to "
            "also serve GET /trace.json)",
        )
    try:
        port = int(value)
    except ValueError:
        return "PASS", "trace plane on (metrics port unparseable; see metrics check)"
    import json as _json
    import urllib.request

    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/trace.json", timeout=5
        ) as resp:
            payload = _json.loads(resp.read().decode(errors="replace"))
    except Exception as e:  # noqa: BLE001 — WARN, never FAIL, on any probe error
        return (
            "WARN",
            f"no /trace.json listener on 127.0.0.1:{port} ({e}) — is a "
            "replica (or metrics.maybe_start_http_server) running here?",
        )
    n_events = len(payload.get("events", []))
    return (
        "PASS",
        f"/trace.json on :{port} serving {n_events} journal events "
        f"(replica {payload.get('replica_id')}/{payload.get('group_rank')})",
    )


def _check_goodput() -> Tuple[str, str]:
    """Goodput ledger + SLO plane preflight: names any unparsable
    ``TPUFT_SLO_*`` / ledger-budget env, and warns when the trace plane is
    disabled (the ledger is a fold over the trace ring, so it degrades
    with it). WARN, never FAIL: accounting and alerting are observability
    — a bad knob must not block a launch."""
    from torchft_tpu import goodput, tracing

    problems: List[str] = []
    for name, floor in (
        (goodput.ENV_WINDOW_SEC, 1e-3),
        (goodput.ENV_SLO_BURN_RATE, 1e-9),
    ):
        raw = os.environ.get(name)
        if raw is None:
            continue
        try:
            if float(raw) < floor:
                raise ValueError
        except ValueError:
            problems.append(f"{name}={raw!r} is not a float >= {floor:g}")
    for name in (goodput.ENV_WINDOWS, goodput.ENV_BYTES, goodput.ENV_SLO_WINDOWS):
        raw = os.environ.get(name)
        if raw is None:
            continue
        try:
            if int(raw) < 1:
                raise ValueError
        except ValueError:
            problems.append(f"{name}={raw!r} is not a positive int")
    slo_raw = os.environ.get(goodput.ENV_SLO_GOODPUT)
    slo_state = "unset (SLO alerting off)"
    if slo_raw is not None:
        try:
            target = float(slo_raw)
            if not 0.0 < target <= 1.0:
                raise ValueError
            slo_state = f"target {target:g}"
        except ValueError:
            problems.append(
                f"{goodput.ENV_SLO_GOODPUT}={slo_raw!r} is not a fraction in "
                "(0, 1] — SLO alerting stays OFF"
            )
    if problems:
        return "WARN", "; ".join(problems)
    if os.environ.get(tracing.ENV_TRACE, "1") == "0":
        return (
            "WARN",
            f"trace plane off ({tracing.ENV_TRACE}=0): the goodput ledger "
            "is a fold over the trace ring, so windows degrade to "
            "{'enabled': False} and SLO alerting never evaluates",
        )
    return "PASS", f"ledger armed; SLO {slo_state}"


def _check_heal_serve() -> Tuple[str, str]:
    """Heal-serving sidecar preflight: validates the mode switch and
    probes the shared-memory snapshot directory (a write + unlink).
    WARN, never FAIL: inline serving always remains as the fallback, so
    a missing tmpfs must not block a launch."""
    import tempfile

    from torchft_tpu.checkpointing import serve_child

    mode = os.environ.get(serve_child.ENV_SERVE_MODE, "inline")
    if mode not in ("inline", "child"):
        return (
            "WARN",
            f"{serve_child.ENV_SERVE_MODE}={mode!r} is not inline|child "
            "(transports will refuse it; unset or fix)",
        )
    root = serve_child.serve_dir_root()
    shm = "shared-memory tmpfs" if root.startswith("/dev/shm") else "plain dir"
    try:
        with tempfile.NamedTemporaryFile(dir=root, prefix="tpuft-doctor-"):
            pass
        import shutil

        free_gb = shutil.disk_usage(root).free / (1 << 30)
        detail = (
            f"serve mode {mode}; snapshot dir {root} ({shm}) writable, "
            f"{free_gb:.1f} GB free"
        )
        if mode == "child" and free_gb < 1.0:
            return "WARN", detail + " — low for a checkpoint snapshot"
        return "PASS", detail
    except OSError as e:
        status = "WARN" if mode == "child" else "PASS"
        return (
            status,
            f"serve mode {mode}; snapshot dir {root} not writable ({e}) — "
            "child mode would degrade to inline serving",
        )


def _check_zero(lighthouse: str) -> Tuple[str, str]:
    """ZeRO plane preflight. WARN, never FAIL: the plane degrades to
    unsharded math, it never breaks training — but an operator who set
    TPUFT_ZERO expecting 1/N memory should hear that a cohort of one (or
    a bad knob) silently degenerates to full state on every replica."""
    from torchft_tpu import zero

    enabled = os.environ.get(zero.ENV_ZERO, "0") not in ("", "0")
    shards_raw = os.environ.get(zero.ENV_ZERO_SHARDS)
    if not enabled and shards_raw is None:
        return "PASS", f"ZeRO off (set {zero.ENV_ZERO}=1 to shard the update)"
    try:
        num_shards = int(shards_raw) if shards_raw else zero.DEFAULT_NUM_SHARDS
        if num_shards < 1:
            raise ValueError
    except ValueError:
        return "WARN", f"{zero.ENV_ZERO_SHARDS}={shards_raw!r} is not a positive int"
    policy = os.environ.get(zero.ENV_ZERO_REBALANCE, "block")
    if policy not in ("block", "strided"):
        return "WARN", f"{zero.ENV_ZERO_REBALANCE}={policy!r} is not block|strided"
    heal = os.environ.get(zero.ENV_ZERO_HEAL_SHARDS, "skip")
    if heal not in ("skip", "fetch"):
        return "WARN", f"{zero.ENV_ZERO_HEAL_SHARDS}={heal!r} is not skip|fetch"
    if not lighthouse:
        return (
            "PASS",
            f"ZeRO on: {num_shards} shards, policy {policy} (no lighthouse "
            "to probe cohort size)",
        )
    try:
        from torchft_tpu.coordination import LighthouseClient

        client = LighthouseClient(lighthouse, connect_timeout=5.0)
        try:
            members = len(client.status(timeout=5.0).members)
        finally:
            client.close()
    except Exception as e:  # noqa: BLE001 — WARN-never-FAIL probe
        return "WARN", f"ZeRO on but lighthouse probe failed ({e})"
    if members <= 1:
        return (
            "WARN",
            f"ZeRO on with a cohort of {members}: one replica owns all "
            f"{num_shards} shards — memory/heal savings silently degenerate "
            "to unsharded until more replicas join",
        )
    return (
        "PASS",
        f"ZeRO on: {num_shards} shards over {members} replicas "
        f"(~1/{members} opt state each), policy {policy}",
    )


def _check_heal_stripe(lighthouse: str) -> Tuple[str, str]:
    """Striped-heal preflight. WARN, never FAIL: the heal plane degrades
    to the single-donor path, it never breaks recovery — but an operator
    expecting recovery bandwidth to scale with fleet size should hear
    that the donor set is degenerate (striping off, cap of one, or a
    fleet with at most one donor-capable member)."""
    from torchft_tpu.checkpointing import http_transport as ht

    stripe = ht.heal_stripe_enabled()
    delta = ht.heal_delta_enabled()
    cap = ht.heal_stripe_max_donors()
    knobs = f"stripe={'on' if stripe else 'off'}, cap={cap}, delta={'on' if delta else 'off'}"
    if not stripe:
        return (
            "WARN",
            f"{knobs}: heals run single-donor — recovery time will not "
            f"improve with fleet size (unset {ht.ENV_HEAL_STRIPE}=0 to "
            "re-enable)",
        )
    if cap <= 1:
        return (
            "WARN",
            f"{knobs}: {ht.ENV_HEAL_STRIPE_MAX_DONORS}={cap} caps every "
            "stripe set to the assigned donor — striping is effectively off",
        )
    if not lighthouse:
        return "PASS", f"{knobs} (no lighthouse to probe the donor set)"
    try:
        from torchft_tpu.coordination import LighthouseClient

        client = LighthouseClient(lighthouse, connect_timeout=5.0)
        try:
            members = client.status(timeout=5.0).members
        finally:
            client.close()
    except Exception as e:  # noqa: BLE001 — WARN-never-FAIL probe
        return "WARN", f"{knobs} but lighthouse probe failed ({e})"
    donors = sum(1 for m in members if not m.joining)
    if donors <= 1:
        return (
            "WARN",
            f"{knobs}: only {donors} donor-capable member(s) in the fleet "
            "— heals degrade to the single-donor path until more replicas "
            "join",
        )
    return (
        "PASS",
        f"{knobs}: {min(donors, cap)} donors available per striped heal "
        f"({donors} donor-capable members)",
    )


def _check_rejoin_storm(lighthouse: str) -> Tuple[str, str]:
    """Mass-rejoin storm preflight. WARN, never FAIL: a degenerate storm
    (more joiners than donor-capable members) still converges — the
    per-joiner fairness split keeps every joiner progressing and
    ``TPUFT_HEAL_MAX_ATTEMPTS`` still bounds each heal — but the
    operator should hear that time-to-full-strength is donor-egress
    bound, not joiner-count bound, in that regime."""
    from torchft_tpu.checkpointing import http_transport as ht

    raw = os.environ.get(ht.ENV_HEAL_INGRESS)
    if raw is not None:
        try:
            gbps = float(raw)
        except ValueError:
            return (
                "WARN",
                f"{ht.ENV_HEAL_INGRESS}={raw!r} is not a number (the "
                "joiner ingress bound will silently fall back to "
                "unbounded)",
            )
        ingress = f"ingress={gbps} Gbps" if gbps > 0 else "ingress=unbounded"
    else:
        ingress = "ingress=unbounded"
    if not lighthouse:
        return (
            "PASS",
            f"{ingress} (no lighthouse to probe the joiner/donor balance)",
        )
    try:
        from torchft_tpu.coordination import LighthouseClient

        client = LighthouseClient(lighthouse, connect_timeout=5.0)
        try:
            members = client.status(timeout=5.0).members
        finally:
            client.close()
    except Exception as e:  # noqa: BLE001 — WARN-never-FAIL probe
        return "WARN", f"{ingress} but lighthouse probe failed ({e})"
    joiners = sum(1 for m in members if m.joining)
    donors = len(members) - joiners
    if joiners > max(donors, 0):
        return (
            "WARN",
            f"{ingress}: degenerate storm in flight — {joiners} joiner(s) "
            f"vs {donors} donor-capable member(s); every joiner still "
            "progresses (per-joiner share of the paced donor egress), but "
            "time-to-full-strength is bound by aggregate donor egress "
            "(TPUFT_HEAL_SERVE_GBPS x donors), not by joiner parallelism",
        )
    return (
        "PASS",
        f"{ingress}: {joiners} joiner(s) / {donors} donor-capable "
        "member(s) — storm headroom ok",
    )


def _check_serving() -> Tuple[str, str]:
    """Committed-weights serving-plane preflight: validates the serving
    knobs, then runs one in-process relay-TREE roundtrip over loopback
    HTTP (publisher -> root relay -> edge relay -> subscriber, tiny
    payload) so tier stacking — the depth chain every production fan-out
    relies on — is probed, not assumed. WARN, never FAIL — serving is a
    read path; a broken relay means readers lag, not that training is
    wrong."""
    import numpy as np

    from torchft_tpu.checkpointing import serve_child
    from torchft_tpu.serving import (
        CachingRelay,
        WeightPublisher,
        WeightSubscriber,
        notify_enabled,
        publish_every,
    )

    hold_raw = os.environ.get("TPUFT_SERVING_NOTIFY_HOLD_SEC")
    if hold_raw is not None:
        try:
            if float(hold_raw) <= 0:
                raise ValueError
        except ValueError:
            return (
                "WARN",
                f"TPUFT_SERVING_NOTIFY_HOLD_SEC={hold_raw!r} is not a "
                "positive number (the long-poll hold will fall back to its "
                "default)",
            )
    for env, parser in (
        (serve_child.ENV_SERVING_TENANT_TOKENS, serve_child.serving_tenant_tokens),
        (serve_child.ENV_SERVING_TENANT_GBPS, serve_child.serving_tenant_gbps),
    ):
        raw = os.environ.get(env, "")
        configured = [e for e in raw.split(",") if e.strip()]
        if len(configured) != len(parser()):
            return (
                "WARN",
                f"{env}={raw!r} has malformed entries (parsed "
                f"{len(parser())} of {len(configured)}) — the skipped "
                "tenants silently lose their identity/entitlement",
            )

    pub = None
    root = None
    edge = None
    try:
        pub = WeightPublisher(num_chunks=2, timeout=5.0)
        pub.publish(
            step=1, quorum_id=0, state={"doctor": np.arange(8, dtype=np.float32)}
        )
        root = CachingRelay([pub.address()], timeout=5.0, start=False)
        if not root.poll_once():
            return "WARN", "root relay failed to pull the probe version"
        edge = CachingRelay([root.address()], timeout=5.0, start=False)
        if not edge.poll_once():
            return "WARN", "edge relay failed to pull through the root tier"
        version = WeightSubscriber([edge.address()], timeout=5.0).poll()
        if version is None or version.step != 1:
            return "WARN", "subscriber failed to adopt through the 2-deep tree"
        tenants = serve_child.serving_tenant_gbps()
        return (
            "PASS",
            "publisher->root->edge->subscriber tree probe ok (publish "
            f"cadence: every {publish_every()} committed step(s); push "
            f"{'on' if notify_enabled() else 'off'}; "
            + (
                f"{len(tenants)} tenant entitlement(s)"
                if tenants
                else "single-tenant egress"
            )
            + ")",
        )
    except Exception as e:  # noqa: BLE001 — WARN, never FAIL
        return "WARN", f"serving probe failed: {type(e).__name__}: {e}"
    finally:
        for node in (edge, root):
            if node is not None:
                node.shutdown(wait=False)
        if pub is not None:
            pub.shutdown(wait=False)


def _check_rollout() -> Tuple[str, str]:
    """Progressive-delivery preflight (serving/rollout.py). WARN, never
    FAIL: rollout is serving-plane policy — a broken table means readers
    see the wrong stream view (or the full pre-rollout view), never that
    training is wrong. Validates the policy table + cohort/hysteresis
    knobs and names the two intentional degenerate modes: no policy at
    all (the exact pre-rollout wire — every publish is stream-less) and
    alerting-only actuation (verdicts counted + traced, publisher never
    touched)."""
    from torchft_tpu.serving import rollout

    policy = rollout.RolloutPolicy.from_env()
    if policy.errors:
        return (
            "WARN",
            f"{rollout.ENV_POLICY} has malformed entries "
            f"({'; '.join(policy.errors)}) — the skipped tenants silently "
            "fall back to the percent-cohort/stable default",
        )
    problems = []
    for env, floor in (
        (rollout.ENV_THRESHOLD, 1.01),
        (rollout.ENV_MIN_GAP, 0.0),
    ):
        raw = os.environ.get(env)
        if raw is None:
            continue
        try:
            if float(raw) < floor:
                raise ValueError
        except ValueError:
            problems.append(f"{env}={raw!r} is not a float >= {floor:g}")
    for env in (rollout.ENV_WINDOWS, rollout.ENV_MIN_SAMPLES):
        raw = os.environ.get(env)
        if raw is None:
            continue
        try:
            if int(raw) < 1:
                raise ValueError
        except ValueError:
            problems.append(f"{env}={raw!r} is not a positive int")
    percent_raw = os.environ.get(rollout.ENV_CANARY_PERCENT)
    if percent_raw is not None:
        try:
            if not 0.0 <= float(percent_raw) <= 100.0:
                raise ValueError
        except ValueError:
            problems.append(
                f"{rollout.ENV_CANARY_PERCENT}={percent_raw!r} is not a "
                "percentage in [0, 100]"
            )
    mode = os.environ.get(rollout.ENV_MODE, "actuate").strip().lower()
    if mode not in ("actuate", "alert"):
        problems.append(
            f"{rollout.ENV_MODE}={mode!r} is not actuate|alert "
            "(falls back to actuate)"
        )
    if problems:
        return "WARN", "; ".join(problems)
    if not policy.active():
        return (
            "PASS",
            "no rollout policy configured — publishes are stream-less and "
            "every tenant sees the full view (the exact pre-rollout wire)",
        )
    pieces = [
        f"{len(policy.entries)} explicit tenant entr(y/ies)",
        f"{policy.percent:g}% sha256 canary cohort",
        f"{len(policy.shadows)} shadow tenant(s)",
    ]
    if mode == "alert":
        pieces.append(
            "ALERTING-ONLY verdicts (bad canaries are counted + traced "
            "but never auto-retracted)"
        )
    return "PASS", "rollout policy active: " + "; ".join(pieces)


def _check_commit_pipeline() -> Tuple[str, str]:
    """Commit-pipeline window preflight. WARN, never FAIL: any depth
    trains correctly — but the snapshot ring holds one full
    ``(params, opt_state)`` copy per window slot (resident bytes ~=
    depth x (params + optimizer state); watch
    ``tpuft_pipeline_snapshot_bytes``), so an operator who set a deep
    window should hear the memory formula before HBM does."""
    from torchft_tpu import manager as mgr

    raw = os.environ.get(mgr.COMMIT_PIPELINE_DEPTH_ENV)
    legacy = os.environ.get(mgr.COMMIT_PIPELINE_ENV)
    if raw is None:
        raw = legacy
    adaptive_raw = os.environ.get(mgr.COMMIT_PIPELINE_ADAPTIVE_ENV)
    adaptive_max = mgr.DEFAULT_ADAPTIVE_MAX_DEPTH
    if adaptive_raw is not None:
        try:
            adaptive_max = int(adaptive_raw)
            if adaptive_max < 1:
                raise ValueError
        except ValueError:
            return (
                "WARN",
                f"{mgr.COMMIT_PIPELINE_ADAPTIVE_ENV}={adaptive_raw!r} is not "
                "a positive int (the adaptive depth ceiling)",
            )
    if raw is None:
        return (
            "PASS",
            "commit pipeline off (set "
            f"{mgr.COMMIT_PIPELINE_DEPTH_ENV}=N|auto to hide commit RTTs "
            "behind an N-step speculative window)",
        )
    if raw.strip().lower() == "auto":
        depth = adaptive_max  # the ceiling is what bounds the ring
        label = f"auto (ceiling {adaptive_max})"
    else:
        try:
            depth = int(raw)
            if depth < 0:
                raise ValueError
        except ValueError:
            return (
                "WARN",
                f"commit pipeline depth {raw!r} is not an int >= 0 or "
                "'auto' (Manager will refuse it)",
            )
        label = str(depth)
    if depth > 8:
        return (
            "WARN",
            f"commit pipeline depth {label}: the rollback snapshot ring "
            f"holds {depth} full (params, opt_state) copies — resident "
            f"bytes ~= {depth} x (params + optimizer state). Past ~8 the "
            "memory bill usually dwarfs the hidden RTT; watch "
            "tpuft_pipeline_snapshot_bytes and size against HBM",
        )
    return (
        "PASS",
        f"commit pipeline depth {label} (phantom-commit envelope <= "
        f"{depth} step(s); snapshot ring ~= {max(depth, 1)} x "
        "(params + opt_state) resident)",
    )


def _check_history() -> Tuple[str, str]:
    """Versioned weight-history preflight (torchft_tpu/history.py).
    WARN, never FAIL: any budget trains and serves correctly — but every
    resident ring version is one full ``(params, opt_state)`` copy, the
    same K x (params + opt_state) formula as the commit-pipeline snapshot
    ring (watch ``tpuft_history_bytes``), so an operator who pinned a
    deep history should hear the memory bill before HBM does."""
    from torchft_tpu import history as hist
    from torchft_tpu import manager as mgr

    raw_versions = os.environ.get(hist.ENV_HISTORY_MAX_VERSIONS)
    raw_bytes = os.environ.get(hist.ENV_HISTORY_BYTES)
    if raw_versions is not None:
        try:
            if int(raw_versions) < 1:
                raise ValueError
        except ValueError:
            return (
                "WARN",
                f"{hist.ENV_HISTORY_MAX_VERSIONS}={raw_versions!r} is not a "
                "positive int (rings will fall back to their defaults)",
            )
    if raw_bytes is not None:
        try:
            float(raw_bytes)
        except ValueError:
            return (
                "WARN",
                f"{hist.ENV_HISTORY_BYTES}={raw_bytes!r} is not a number "
                "(rings will fall back to count-bounded budgets)",
            )
    # Effective manager-ring width: env override, else window depth + 1.
    depth_raw = os.environ.get(mgr.COMMIT_PIPELINE_DEPTH_ENV) or os.environ.get(
        mgr.COMMIT_PIPELINE_ENV
    )
    if depth_raw and depth_raw.strip().lower() == "auto":
        depth = mgr.DEFAULT_ADAPTIVE_MAX_DEPTH
    else:
        try:
            depth = int(depth_raw) if depth_raw else 0
        except ValueError:
            depth = 0
    k = hist.history_max_versions(depth + 1)
    serving_k = hist.history_max_versions(hist.DEFAULT_SERVING_VERSIONS)
    budget = hist.history_bytes_budget()
    budget_note = (
        f"; byte budget {budget} ({hist.ENV_HISTORY_BYTES})"
        if budget is not None
        else "; count-bounded (set TPUFT_HISTORY_BYTES for a byte budget)"
    )
    if k > 8 and budget is None:
        # Same threshold as the commit-pipeline snapshot probe: past ~8
        # resident copies the memory bill dwarfs what the history buys.
        return (
            "WARN",
            f"history ring keeps {k} committed versions with no byte "
            f"budget — resident bytes ~= {k} x (params + opt_state); "
            "watch tpuft_history_bytes, or set TPUFT_HISTORY_BYTES",
        )
    return (
        "PASS",
        f"history ring: manager keeps {k} committed version(s) (exact "
        f"deep-window donor serves), serving keeps {serving_k} staged "
        f"version(s) (pinned/latest-1/rollback reads){budget_note}",
    )


def _check_health(lighthouse: str) -> Tuple[str, str]:
    """Gray-failure ejection plane preflight. WARN, never FAIL: the
    plane only ever REMOVES a replica that judged itself degraded, and
    every refusal path keeps training — but an operator who armed it
    should hear about knob typos, a probe that cannot run, and the N=2
    degenerate regime where a verdict can never actuate: with two
    participants and ``min_replica_size=2``, ejecting would drop the
    quorum below min_replica, so the verdict latches and is REFUSED
    (counted in ``tpuft_health_ejections_refused_total``) while
    training continues degraded."""
    from torchft_tpu import health

    if not health.enabled():
        return (
            "PASS",
            f"health plane off (set {health.ENV_HEALTH}=1 for "
            "slow-is-the-new-dead straggler verdicts + self-ejection)",
        )
    threshold = os.environ.get(health.ENV_THRESHOLD)
    if threshold is not None:
        try:
            if float(threshold) <= 1.0:
                raise ValueError
        except ValueError:
            return (
                "WARN",
                f"{health.ENV_THRESHOLD}={threshold!r} must be a number > 1 "
                "(a multiplicative bound vs the fleet median)",
            )
    for env, floor in (
        (health.ENV_CONSECUTIVE, 1),
        (health.ENV_MIN_PEERS, 1),
        (health.ENV_QUARANTINE_MAX_EJECTS, 1),
    ):
        raw = os.environ.get(env)
        if raw is not None:
            try:
                if int(raw) < floor:
                    raise ValueError
            except ValueError:
                return "WARN", f"{env}={raw!r} is not an int >= {floor}"
    for env in (
        health.ENV_QUARANTINE_BASE,
        health.ENV_QUARANTINE_CAP,
        health.ENV_QUARANTINE_WINDOW,
        health.ENV_QUARANTINE_PARK,
        health.ENV_WEDGE_FLOOR,
    ):
        raw = os.environ.get(env)
        if raw is not None:
            try:
                if float(raw) <= 0:
                    raise ValueError
            except ValueError:
                return "WARN", f"{env}={raw!r} is not a positive number"
    knobs = (
        f"threshold {os.environ.get(health.ENV_THRESHOLD, '3.0')}x, "
        f"K={os.environ.get(health.ENV_CONSECUTIVE, '3')} windows, "
        f"wedge floor {os.environ.get(health.ENV_WEDGE_FLOOR, '30')}s, "
        f"probe {'off' if os.environ.get(health.ENV_PROBE, '1') == '0' else 'on'}"
    )
    if not lighthouse:
        return "PASS", f"health plane on ({knobs}; no lighthouse to probe fleet size)"
    try:
        from torchft_tpu.coordination import LighthouseClient

        client = LighthouseClient(lighthouse, connect_timeout=5.0)
        try:
            members = len(client.status(timeout=5.0).members)
        finally:
            client.close()
    except Exception as e:  # noqa: BLE001 — WARN-never-FAIL probe
        return "WARN", f"health plane on but lighthouse probe failed ({e})"
    if members <= 2:
        return (
            "WARN",
            f"health plane on with only {members} member(s): the N=2 "
            "degenerate regime — under min_replica_size=2 an ejection "
            "would drop the quorum below min_replica, so degraded "
            "verdicts are REFUSED (counted, training continues slow); "
            "self-ejection needs ejectable headroom (N-1 >= min_replica)",
        )
    return (
        "PASS",
        f"health plane on ({knobs}; {members} members — ejectable headroom ok)",
    )


def _check_topology() -> Tuple[str, str]:
    """WAN topology matrix state. WARN, never FAIL: a malformed topology
    env degrades to the global single link at runtime (heals still work,
    just region-blind), so the doctor's job is to make that visible."""
    from torchft_tpu.utils import netem

    desc = netem.describe_topology()
    if not desc.get("configured"):
        return (
            "PASS",
            "no WAN topology (TPUFT_EMULATED_TOPOLOGY unset; wire planes "
            "region-blind, single global link applies)",
        )
    errors = desc.get("errors") or []
    if errors:
        return (
            "WARN",
            "topology configured but partially malformed (falls back to "
            f"the global link where unparsable): {'; '.join(errors)}",
        )
    names = desc.get("region_names") or []
    if desc.get("single_region"):
        return (
            "WARN",
            f"topology maps every replica to one region ({names[0] if names else '?'}) "
            "— degenerate case: region-aware striping/relay/DiLoCo routing "
            "all reduce to the region-blind path (is a region missing?)",
        )
    pieces = [
        f"{len(names)} regions ({', '.join(names)})",
        f"{desc.get('num_links', 0)} per-pair links",
    ]
    if desc.get("has_intra_default") or desc.get("has_cross_default"):
        pieces.append(
            "defaults: "
            + "/".join(
                n for n, on in (
                    ("intra", desc.get("has_intra_default")),
                    ("cross", desc.get("has_cross_default")),
                ) if on
            )
        )
    self_region = desc.get("self_region")
    if self_region:
        pieces.append(f"self={self_region}")
    return "PASS", "WAN topology: " + ", ".join(pieces)


def _check_explore() -> Tuple[str, str]:
    """Interleaving-explorer budget knobs. WARN, never FAIL: an
    unparsable TPUFT_EXPLORE_* value silently falls back to its default
    at runtime (schedules.explore_defaults), so the operator should hear
    about the typo without the preflight going red."""
    from torchft_tpu.utils.schedules import explore_defaults

    bad = []
    for env in (
        "TPUFT_EXPLORE_BUDGET", "TPUFT_EXPLORE_SEED",
        "TPUFT_EXPLORE_PREEMPTIONS", "TPUFT_EXPLORE_RANDOM",
    ):
        raw = os.environ.get(env, "")
        if not raw:
            continue
        try:
            int(raw)
        except ValueError:
            bad.append(f"{env}={raw!r}")
    d = explore_defaults()
    budgets = (
        f"budget={d['budget']} preemptions<={d['preemptions']} "
        f"random={d['random']} seed={d['seed']}"
    )
    if bad:
        return (
            "WARN",
            "unparsable TPUFT_EXPLORE_* value(s) ignored (defaults "
            f"apply): {', '.join(bad)}; effective {budgets}",
        )
    return "PASS", f"explorer budgets: {budgets}"


def _check_env() -> Tuple[str, str]:
    # Value validation first — a fatal misconfig must FAIL even when a
    # typo'd var would also WARN.
    wire = os.environ.get("TPUFT_WIRE_DTYPE")
    if wire and wire not in ("fp8", "int8", "int4"):
        return "FAIL", f"TPUFT_WIRE_DTYPE={wire!r} is invalid"
    unknown = sorted(
        name for name in os.environ
        if name.startswith("TPUFT_") and name not in KNOWN_ENV
        # Per-pair WAN link envs embed region names, so they can't be
        # enumerated in KNOWN_ENV — the topology check validates them.
        and not name.startswith("TPUFT_EMULATED_LINK_")
    )
    if unknown:
        return "WARN", f"unrecognized TPUFT_* vars (typo?): {', '.join(unknown)}"
    return "PASS", "TPUFT_* env vars recognized"


def run_checks(lighthouse: str, skip_device: bool = False) -> int:
    checks: List[Check] = [
        ("build toolchain", _check_toolchain),
        ("native plane", _check_native),
        ("kv store", _check_store),
        ("wire codecs", _check_kernels),
        ("codec negotiation", _check_wire_codec_negotiation),
        ("env vars", _check_env),
        ("wan topology", _check_topology),
        ("commit pipeline", _check_commit_pipeline),
        ("weight history", _check_history),
        ("metrics", _check_metrics),
        ("trace plane", _check_trace),
        ("interleaving explorer", _check_explore),
        ("goodput/slo", _check_goodput),
        ("heal serving", _check_heal_serve),
        ("weights serving", _check_serving),
        ("rollout policy", _check_rollout),
        ("heal striping", lambda: _check_heal_stripe(lighthouse)),
        ("health plane", lambda: _check_health(lighthouse)),
        ("rejoin storm", lambda: _check_rejoin_storm(lighthouse)),
        ("zero plane", lambda: _check_zero(lighthouse)),
        ("lighthouse", lambda: _check_lighthouse(lighthouse)),
    ]
    if not skip_device:
        checks.append(("accelerator", _check_device))
    failed = False
    for name, fn in checks:
        try:
            status, detail = fn()
        except Exception as e:  # noqa: BLE001 — each check reports, never aborts
            status, detail = "FAIL", f"{type(e).__name__}: {e}"
        failed |= status == "FAIL"
        print(f"[{status:4s}] {name}: {detail}", flush=True)
    print("doctor: " + ("FAIL" if failed else "OK"))
    return 1 if failed else 0


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--lighthouse",
        default=os.environ.get("TPUFT_LIGHTHOUSE", ""),
        help="lighthouse address to ping (default: $TPUFT_LIGHTHOUSE)",
    )
    parser.add_argument(
        "--skip-device", action="store_true",
        help="skip the accelerator probe (slow when the backend is wedged)",
    )
    args = parser.parse_args()
    sys.exit(run_checks(args.lighthouse, skip_device=args.skip_device))


if __name__ == "__main__":
    main()
