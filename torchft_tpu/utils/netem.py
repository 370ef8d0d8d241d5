"""Emulated-DCN network shim for single-host benchmarking.

This box (and any single-host CI) can only produce loopback numbers for
cross-replica traffic, which says nothing about the design claims that
motivate streaming DiLoCo and the int4 wire — hiding outer-sync latency
and halving bytes only MATTER under non-zero RTT and bounded bandwidth
(the reference's whole DiLoCo pitch, reference local_sgd.py:176-568
design comments). This shim injects both at the Python wire choke points
(ProcessGroupTCP sends, HTTP checkpoint chunk serves) so a loopback bench
can sweep a latency-tolerance curve.

Configuration, in precedence order:

- :func:`configure` / :func:`configure_topology` (what benches call per
  sweep point), or
- env at first use: ``TPUFT_EMULATED_RTT_MS`` (per-message one-way delay
  = RTT/2) and ``TPUFT_EMULATED_GBPS`` (serialization time =
  bytes / bandwidth) for the single global link, plus optionally a
  per-(src,dst)-region link MATRIX:

  - ``TPUFT_EMULATED_TOPOLOGY="r0=us,r1=us,r2=eu[,*=us]"`` assigns a
    region per replica id (stable id — the part before the first ``:``;
    ``*`` is the default region for unlisted replicas);
  - ``TPUFT_EMULATED_LINK_<SRC>_<DST>="rtt_ms,gbps"`` sets one DIRECTED
    pair's link (region names uppercased in the env name, so they must
    not contain ``_``); ``TPUFT_EMULATED_LINK_LOCAL`` /
    ``TPUFT_EMULATED_LINK_CROSS`` are the intra-/cross-region defaults
    for pairs without an explicit entry. Any pair still unresolved falls
    back to the global single-link envs — with no topology configured
    at all, behavior is byte-identical to the single-link shim (the
    1-region degenerate case).

  A process learns its own region from ``TPUFT_EMULATED_REGION`` or from
  :func:`set_local_replica_id` (the manager calls it with its replica
  id); wire seams that know the PEER's region (the heal chunk server
  reads the joiner's ``?region=`` tag) pace per the (local, peer) link.

Disabled (the default) costs one attribute load + truthiness test per
message. This is a measurement shim, not a simulator: delays are sleeps
on the sending side, so concurrent flows each pay their own
serialization — a per-flow bandwidth model, not a shared-link one.
"""

from __future__ import annotations

import os
import socket
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

# (one_way_delay_s, seconds_per_byte); None = not yet resolved from env.
_config: Optional[Tuple[float, float]] = None

ENV_TOPOLOGY = "TPUFT_EMULATED_TOPOLOGY"
ENV_REGION = "TPUFT_EMULATED_REGION"
LINK_ENV_PREFIX = "TPUFT_EMULATED_LINK_"

# Response header a netem-paced HTTP server sets on bodies it already
# charged the emulated link for (pace_latency + PacingWriter). A paced
# CLIENT fetch seam (serving/_wire.py) skips its response-leg charge when
# it sees this, so a hop is never double-billed no matter which side of
# it carries the shim.
PACED_HEADER = "X-TPUFT-Link-Paced"


def configure(rtt_ms: float = 0.0, gbps: float = 0.0) -> None:
    """Set the emulated link for this process; zeros disable."""
    global _config
    one_way = max(rtt_ms, 0.0) / 2000.0
    spb = 8.0 / (gbps * 1e9) if gbps > 0 else 0.0
    _config = (one_way, spb)


def _resolve() -> Tuple[float, float]:
    global _config
    if _config is None:
        configure(
            float(os.environ.get("TPUFT_EMULATED_RTT_MS", "0") or 0.0),
            float(os.environ.get("TPUFT_EMULATED_GBPS", "0") or 0.0),
        )
    assert _config is not None
    return _config


class _Topology:
    """Parsed region map + directed link matrix. Pure data; all lookups
    fall back (pair -> intra/cross default -> global single link) so a
    partially-specified matrix is always servable."""

    __slots__ = (
        "regions", "default_region", "links", "intra_default",
        "cross_default", "self_region", "errors",
    )

    def __init__(self) -> None:
        self.regions: Dict[str, str] = {}
        self.default_region: Optional[str] = None
        # (src_region, dst_region) -> (one_way_delay_s, seconds_per_byte)
        self.links: Dict[Tuple[str, str], Tuple[float, float]] = {}
        self.intra_default: Optional[Tuple[float, float]] = None
        self.cross_default: Optional[Tuple[float, float]] = None
        self.self_region: Optional[str] = None
        self.errors: List[str] = []

    def region_names(self) -> List[str]:
        names = set(self.regions.values())
        if self.default_region:
            names.add(self.default_region)
        return sorted(names)

    def any_paced(self) -> bool:
        for pair in list(self.links.values()) + [
            link
            for link in (self.intra_default, self.cross_default)
            if link is not None
        ]:
            if pair[0] > 0.0 or pair[1] > 0.0:
                return True
        return False


# None = no topology configured; unresolved until first use.
_topology_cache: Optional[_Topology] = None
_topology_resolved = False
_local_replica_id: Optional[str] = None


def _parse_link(raw: str) -> Tuple[float, float]:
    """``"rtt_ms,gbps"`` (``:`` separator accepted) -> (delay_s, spb)."""
    parts = [p.strip() for p in raw.replace(":", ",").split(",")]
    rtt_ms = float(parts[0] or 0.0)
    gbps = float(parts[1] or 0.0) if len(parts) > 1 and parts[1] else 0.0
    return (max(rtt_ms, 0.0) / 2000.0, 8.0 / (gbps * 1e9) if gbps > 0 else 0.0)


def _resolve_topology() -> Optional[_Topology]:
    global _topology_cache, _topology_resolved
    if _topology_resolved:
        return _topology_cache
    topo = _Topology()
    raw = os.environ.get(ENV_TOPOLOGY, "").strip()
    for token in filter(None, (t.strip() for t in raw.split(","))):
        rid, sep, region = token.partition("=")
        if not sep or not region.strip():
            topo.errors.append(f"bad {ENV_TOPOLOGY} token {token!r}")
            continue
        rid, region = rid.strip(), region.strip().lower()
        if rid == "*":
            topo.default_region = region
        else:
            topo.regions[rid] = region
    for name in sorted(os.environ):
        if not name.startswith(LINK_ENV_PREFIX):
            continue
        try:
            link = _parse_link(os.environ[name])
        except ValueError:
            topo.errors.append(f"unparseable link {name}={os.environ[name]!r}")
            continue
        tail = name[len(LINK_ENV_PREFIX):]
        if tail == "LOCAL":
            topo.intra_default = link
        elif tail == "CROSS":
            topo.cross_default = link
        else:
            src, sep, dst = tail.partition("_")
            if not sep or not src or not dst or "_" in dst:
                topo.errors.append(
                    f"link env {name} is not <SRC>_<DST> (region names "
                    "must not contain '_')"
                )
                continue
            topo.links[(src.lower(), dst.lower())] = link
    region = os.environ.get(ENV_REGION, "").strip().lower()
    if region:
        topo.self_region = region
    has_any = bool(
        topo.regions or topo.default_region or topo.links
        or topo.intra_default or topo.cross_default or topo.self_region
    )
    _topology_cache = topo if has_any else None
    _topology_resolved = True
    return _topology_cache


def configure_topology(
    regions: Optional[Dict[str, str]] = None,
    links: Optional[Dict[Tuple[str, str], Tuple[float, float]]] = None,
    intra: Optional[Tuple[float, float]] = None,
    cross: Optional[Tuple[float, float]] = None,
    self_region: Optional[str] = None,
    default_region: Optional[str] = None,
) -> None:
    """Programmatic topology for benches/tests: ``links``/``intra``/
    ``cross`` take (rtt_ms, gbps) pairs. Passing nothing installs an
    EMPTY topology (region-blind — the single-link degenerate case);
    call :func:`reset_topology` to go back to env resolution."""
    global _topology_cache, _topology_resolved
    has_any = bool(regions or links or intra or cross or self_region)
    if not has_any:
        _topology_cache = None
        _topology_resolved = True
        return
    topo = _Topology()
    topo.regions = {k: v.lower() for k, v in (regions or {}).items()}
    topo.default_region = default_region.lower() if default_region else None
    topo.links = {
        (s.lower(), d.lower()): _parse_link(f"{rtt},{gbps}")
        for (s, d), (rtt, gbps) in (links or {}).items()
    }
    topo.intra_default = _parse_link(f"{intra[0]},{intra[1]}") if intra else None
    topo.cross_default = _parse_link(f"{cross[0]},{cross[1]}") if cross else None
    topo.self_region = self_region.lower() if self_region else None
    _topology_cache = topo
    _topology_resolved = True


def reset_topology() -> None:
    """Forget any parsed/programmatic topology; env re-resolves at next use."""
    global _topology_cache, _topology_resolved
    _topology_cache = None
    _topology_resolved = False


def topology_enabled() -> bool:
    return _resolve_topology() is not None


def set_local_replica_id(replica_id: Optional[str]) -> None:
    """Tell the shim who THIS process is (the manager calls it with its
    replica id) so :func:`local_region` can answer from the topology map.
    Cheap and unconditional — a no-op without a topology."""
    global _local_replica_id
    _local_replica_id = replica_id


def region_of(replica_id: Optional[str]) -> Optional[str]:
    """The region the topology assigns to ``replica_id`` (exact id first,
    then the stable prefix before the first ``:``), or None."""
    topo = _resolve_topology()
    if topo is None or not replica_id:
        return None
    if replica_id in topo.regions:
        return topo.regions[replica_id]
    stable = replica_id.split(":", 1)[0]
    return topo.regions.get(stable, topo.default_region)


def local_region() -> Optional[str]:
    """This process's own region: explicit (``TPUFT_EMULATED_REGION`` /
    ``configure_topology(self_region=...)``) first, else derived from the
    replica id registered via :func:`set_local_replica_id`."""
    topo = _resolve_topology()
    if topo is None:
        return None
    return topo.self_region or region_of(_local_replica_id)


def link_params(
    src_region: Optional[str], dst_region: Optional[str]
) -> Tuple[float, float]:
    """(one_way_delay_s, seconds_per_byte) for the DIRECTED (src, dst)
    region pair: exact pair entry -> intra/cross default -> the global
    single link. Either side unknown degrades to the global link."""
    topo = _resolve_topology()
    if topo is None or src_region is None or dst_region is None:
        return _resolve()
    src, dst = src_region.lower(), dst_region.lower()
    link = topo.links.get((src, dst))
    if link is not None:
        return link
    fallback = topo.intra_default if src == dst else topo.cross_default
    return fallback if fallback is not None else _resolve()


def _link_for_peer(peer_region: Optional[str]) -> Tuple[float, float]:
    """Sender-side link choice: the (local, peer) pair when the peer's
    region is known, the global single link otherwise."""
    if peer_region is None or not topology_enabled():
        return _resolve()
    return link_params(local_region(), peer_region)


def describe_topology() -> Dict[str, Any]:
    """Parse summary for the doctor's WARN-never-FAIL topology probe."""
    topo = _resolve_topology()
    if topo is None:
        return {"configured": False}
    names = topo.region_names()
    return {
        "configured": True,
        "regions": dict(topo.regions),
        "default_region": topo.default_region,
        "region_names": names,
        "single_region": len(names) <= 1,
        "num_links": len(topo.links),
        "has_intra_default": topo.intra_default is not None,
        "has_cross_default": topo.cross_default is not None,
        "self_region": local_region(),
        "errors": list(topo.errors),
    }


def enabled() -> bool:
    delay, spb = _resolve()
    if delay > 0.0 or spb > 0.0:
        return True
    topo = _resolve_topology()
    return topo is not None and topo.any_paced()


def emulated_device_sync(rtt_ms: float, ack_threshold_s: float = 1e-3):
    """A ``jax.block_until_ready`` replacement that emulates a
    high-latency device: one whose readiness answer travels ``rtt_ms``
    (env ``TPUFT_EMULATED_DEVICE_RTT_MS`` when ``rtt_ms`` is 0) back to
    the host. The model is a choice, not a measurement of any machine: a
    readiness call on IN-FLIGHT work costs completion plus one full round
    trip (the round trip does not hide under remaining compute), while a
    call on work that had already completed is free. The shim
    distinguishes the two by how long the real (local,
    ~instant-on-complete) wait took: longer than ``ack_threshold_s`` means
    the work was still in flight, and the response round trip is charged
    after completion. On a chip the process owns, the wait costs only what
    the remaining compute costs.

    Shimming ``optim._bound_device`` with this shows, deterministically,
    why the pipelined-commit mode wins under such latency: it only ever
    probes the PREVIOUS step's (completed) work, where the serialized
    orderings probe in-flight work every step. A measurement shim for the
    emulated-DCN bench, not a simulator."""
    if not rtt_ms:
        rtt_ms = float(os.environ.get("TPUFT_EMULATED_DEVICE_RTT_MS", "0") or 0.0)
    rtt_s = max(rtt_ms, 0.0) / 1000.0

    def sync(x: Any) -> Any:
        import jax

        t0 = time.monotonic()
        out = jax.block_until_ready(x)
        if rtt_s and time.monotonic() - t0 > ack_threshold_s:
            time.sleep(rtt_s)
        return out

    return sync


def pace(nbytes: int, peer_region: Optional[str] = None) -> None:
    """Sleep for the emulated link's share of sending ``nbytes`` as one
    message: RTT/2 of propagation + bytes/bandwidth of serialization.
    ``peer_region`` selects the (local, peer) link from the topology
    matrix when known; None keeps the global single link."""
    delay, spb = _link_for_peer(peer_region)
    d = delay + nbytes * spb
    if d > 0.0:
        time.sleep(d)


def pace_deadline(
    nbytes: int, deadline: float, peer_region: Optional[str] = None
) -> None:
    """:func:`pace`, bounded by an absolute monotonic ``deadline``: sleeps
    at most the remaining time and raises ``socket.timeout`` when the
    emulated link cannot deliver the message in time — the failure a real
    link of this speed would produce under the caller's op timeout.
    Deadline-bounded wire paths (ProcessGroupTCP sends) must use this so
    an emulated slow link cannot stall an op past its deadline."""
    delay, spb = _link_for_peer(peer_region)
    d = delay + nbytes * spb
    if d <= 0.0:
        return
    remaining = deadline - time.monotonic()
    if d > max(remaining, 0.0):
        time.sleep(max(remaining, 0.0))
        raise socket.timeout("emulated link exceeded the op deadline")
    time.sleep(d)


def pace_latency(peer_region: Optional[str] = None) -> None:
    """The propagation half only (RTT/2) — charge once per message when
    the serialization share is paced incrementally via a PacingWriter."""
    delay, _ = _link_for_peer(peer_region)
    if delay > 0.0:
        time.sleep(delay)


class PacingWriter:
    """File-like wrapper that charges the emulated link's serialization
    time interleaved with the actual writes, in bounded slices — one
    up-front sleep for a huge body would hold the wire silent longer than
    a per-recv inactivity timeout, a failure a real link of the same
    bandwidth (which trickles bytes) would not produce. Wrap only when
    :func:`enabled`; pace latency separately via :func:`pace_latency`.
    ``peer_region`` pins the topology link once at construction (the peer
    does not move mid-body)."""

    _SLICE = 8 << 20  # 8 MiB: bandwidth sleep per write stays ~sub-second

    def __init__(self, raw: Any, peer_region: Optional[str] = None) -> None:
        self._raw = raw
        self._peer_region = peer_region

    def write(self, data: Any) -> int:
        _, spb = _link_for_peer(self._peer_region)
        view = memoryview(data)
        for off in range(0, max(len(view), 1), self._SLICE):
            part = view[off : off + self._SLICE]
            if spb > 0.0 and len(part):
                time.sleep(len(part) * spb)
            self._raw.write(part)
        return len(view)

    def flush(self) -> None:
        self._raw.flush()


class TCPFront:
    """Shared scaffolding for wire-front proxies placed ahead of a real
    server (latency injection here; fault injection in the lighthouse
    tests): target address parsing, the listener + accept loop, and
    per-connection handler threads. Subclasses implement
    :meth:`handle`."""

    def __init__(self, target_addr: str) -> None:
        host, _, port = target_addr.rpartition(":")
        self.target = (host.strip("[]") or "127.0.0.1", int(port))
        self._stop = False
        self._srv = socket.create_server(("127.0.0.1", 0))
        self._srv.settimeout(0.2)
        self._thread = threading.Thread(target=self._accept_loop, daemon=True)
        self._thread.start()

    def address(self) -> str:
        return f"127.0.0.1:{self._srv.getsockname()[1]}"

    @property
    def stopping(self) -> bool:
        return self._stop

    def _accept_loop(self) -> None:
        while not self._stop:
            try:
                conn, _ = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            threading.Thread(target=self.handle, args=(conn,), daemon=True).start()

    def handle(self, conn: socket.socket) -> None:
        raise NotImplementedError

    def shutdown(self) -> None:
        self._stop = True
        self._thread.join(timeout=2)
        self._srv.close()


class LatencyProxy(TCPFront):
    """Byte-level proxy that sleeps RTT/2 before forwarding each burst in
    each direction — a DCN hop in front of a control-plane server. Framing
    agnostic; used by the emulated-DCN bench to measure quorum latency
    sensitivity."""

    def __init__(self, target_addr: str, rtt_ms: float) -> None:
        self._one_way = max(rtt_ms, 0.0) / 2000.0
        super().__init__(target_addr)

    def handle(self, conn: socket.socket) -> None:
        try:
            up = socket.create_connection(self.target, timeout=10)
        except OSError:
            conn.close()
            return

        def copy(src: socket.socket, dst: socket.socket) -> None:
            try:
                while True:
                    data = src.recv(65536)
                    if not data:
                        break
                    if self._one_way:
                        time.sleep(self._one_way)
                    dst.sendall(data)
            except OSError:
                pass
            finally:
                for s in (src, dst):
                    try:
                        s.shutdown(socket.SHUT_RDWR)
                    except OSError:
                        pass

        t = threading.Thread(target=copy, args=(up, conn), daemon=True)
        t.start()
        copy(conn, up)
        t.join(timeout=10)
        conn.close()
        up.close()
