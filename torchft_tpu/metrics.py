"""Fleet metrics plane: dependency-free counters, gauges, and histograms.

The reference leans on external sinks for aggregate observability (its OTel
hookup, otel.py, exports raw event records and leaves aggregation to a
collector). This stack runs where neither prometheus_client nor an OTel
collector can be assumed, so the registry here is self-contained stdlib:
every FT phase (quorum wait, wire allreduce, device sync, vote RTT, heal
transfer) lands in process-local metrics that export on three surfaces —

- ``prometheus_text()``: the Prometheus exposition format, served by
  :func:`start_http_server` (``$TPUFT_METRICS_PORT``) and by the
  checkpoint transport's HTTP server at ``GET /metrics``;
- ``snapshot()``: a JSON-safe dict; the flight recorder appends it as a
  dump trailer, and each Manager pushes it into its group store under
  ``metrics/<replica_id>/<group_rank>`` for ``scripts/fleet_status.py``;
- direct reads: :func:`counter_total` / :func:`histogram_stats` for tests
  and the ft_harness counter assertions.

Metric identity is ``(name, sorted label items)``; get-or-create accessors
return the same live object for the same identity, and every mutation takes
the metric's own lock so concurrent increments from the op-worker, quorum,
and train-loop threads never lose updates. The canonical metric names and
label sets are tabulated in METRICS.md — a drift test greps the tree and
diffs against that table, so new metrics must be registered there.

Env: ``TPUFT_METRICS_PORT`` (serve /metrics on this port; 0 = ephemeral),
``TPUFT_METRICS_PUSH_SEC`` (min seconds between store pushes, default 10;
<= 0 disables the push).
"""

from __future__ import annotations

import functools
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Generator, Iterable, List, Optional, Set, Tuple

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "REGISTRY",
    "WindowedSeries",
    "DEFAULT_TIME_BUCKETS",
    "DEFAULT_BYTES_PER_SEC_BUCKETS",
    "ENV_PORT",
    "ENV_PUSH_SEC",
    "counter",
    "gauge",
    "histogram",
    "inc",
    "set_gauge",
    "observe",
    "timer",
    "snapshot",
    "snapshot_to_prometheus",
    "prometheus_text",
    "counter_total",
    "gauge_value",
    "histogram_stats",
    "start_http_server",
    "maybe_start_http_server",
]

ENV_PORT = "TPUFT_METRICS_PORT"
ENV_PUSH_SEC = "TPUFT_METRICS_PUSH_SEC"

# Seconds-scale phases span ~100 us (acked-buffer readiness probes) to the
# 60 s RPC timeout ceiling; edges follow the Prometheus 1-2.5-5 ladder.
DEFAULT_TIME_BUCKETS: Tuple[float, ...] = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)

# Byte-rate phases (heal stream throughput): a fenced gray donor drips at
# ~100 B/s, a healthy DCN heal runs at GB/s — same 1-2.5-5 ladder.
DEFAULT_BYTES_PER_SEC_BUCKETS: Tuple[float, ...] = (
    1e2, 2.5e2, 5e2, 1e3, 2.5e3, 5e3, 1e4, 2.5e4, 5e4, 1e5, 2.5e5, 5e5,
    1e6, 2.5e6, 5e6, 1e7, 2.5e7, 5e7, 1e8, 2.5e8, 5e8, 1e9, 2.5e9, 5e9,
)

LabelItems = Tuple[Tuple[str, str], ...]


def _label_items(labels: Dict[str, Any]) -> LabelItems:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _escape(value: str) -> str:
    return value.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _label_str(items: LabelItems) -> str:
    if not items:
        return ""
    return "{" + ",".join(f'{k}="{_escape(v)}"' for k, v in items) + "}"


def _fmt(value: float) -> str:
    # Integral values print as integers so counter lines stay diff-stable.
    if float(value).is_integer() and abs(value) < 2**53:
        return str(int(value))
    return repr(float(value))


class Counter:
    """Monotonically increasing float; negative increments are rejected."""

    __slots__ = ("_lock", "_value")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._value = 0.0

    def inc(self, value: float = 1.0) -> None:
        if value < 0:
            raise ValueError(f"counter increment must be >= 0, got {value}")
        with self._lock:
            self._value += value

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Gauge:
    """Last-write-wins scalar."""

    __slots__ = ("_lock", "_value")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    def inc(self, value: float = 1.0) -> None:
        with self._lock:
            self._value += value

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Histogram:
    """Fixed-bucket histogram (Prometheus ``le`` semantics: a bucket counts
    observations <= its edge; ``+Inf`` counts everything). Bounded memory:
    one int per edge, no per-observation storage."""

    __slots__ = ("_lock", "edges", "_bucket_counts", "_sum", "_count")

    def __init__(self, buckets: Iterable[float] = DEFAULT_TIME_BUCKETS) -> None:
        edges = tuple(sorted(float(b) for b in buckets))
        if not edges:
            raise ValueError("histogram needs at least one bucket edge")
        self._lock = threading.Lock()
        self.edges = edges
        self._bucket_counts = [0] * len(edges)
        self._sum = 0.0
        self._count = 0

    def observe(self, value: float) -> None:
        value = float(value)
        with self._lock:
            self._sum += value
            self._count += 1
            for i, edge in enumerate(self.edges):
                if value <= edge:
                    self._bucket_counts[i] += 1
                    break

    def stats(self) -> Dict[str, Any]:
        """{"sum", "count", "mean", "buckets"}: buckets are CUMULATIVE
        counts keyed by edge string plus "+Inf" (the exposition format)."""
        with self._lock:
            cumulative: Dict[str, int] = {}
            running = 0
            for edge, n in zip(self.edges, self._bucket_counts):
                running += n
                cumulative[_fmt(edge)] = running
            cumulative["+Inf"] = self._count
            return {
                "sum": self._sum,
                "count": self._count,
                "mean": (self._sum / self._count) if self._count else 0.0,
                "buckets": cumulative,
            }


_KINDS = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class Registry:
    """Thread-safe get-or-create store of metrics keyed (name, labels)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: Dict[Tuple[str, LabelItems], Any] = {}
        self._kinds: Dict[str, str] = {}

    def _get(self, kind: str, name: str, labels: Dict[str, Any], **kw: Any) -> Any:
        key = (name, _label_items(labels))
        with self._lock:
            existing_kind = self._kinds.get(name)
            if existing_kind is not None and existing_kind != kind:
                raise ValueError(
                    f"metric {name!r} already registered as a "
                    f"{existing_kind}, cannot reuse as a {kind}"
                )
            metric = self._metrics.get(key)
            if metric is None:
                metric = _KINDS[kind](**kw)
                self._metrics[key] = metric
                self._kinds[name] = kind
            return metric

    def counter(self, name: str, **labels: Any) -> Counter:
        return self._get("counter", name, labels)

    def gauge(self, name: str, **labels: Any) -> Gauge:
        return self._get("gauge", name, labels)

    def histogram(
        self,
        name: str,
        buckets: Iterable[float] = DEFAULT_TIME_BUCKETS,
        **labels: Any,
    ) -> Histogram:
        return self._get("histogram", name, labels, buckets=buckets)

    def reset(self) -> None:
        """Drops every metric (tests / per-window benchmark phases)."""
        with self._lock:
            self._metrics.clear()
            self._kinds.clear()

    # -- reads -------------------------------------------------------------

    def metric_names(self) -> Set[str]:
        """Names currently registered (used to avoid duplicate TYPE lines
        when a merged child snapshot shares a name with this registry)."""
        with self._lock:
            return {name for name, _ in self._metrics}

    def _items(self) -> List[Tuple[str, LabelItems, str, Any]]:
        with self._lock:
            return [
                (name, items, self._kinds[name], metric)
                for (name, items), metric in sorted(self._metrics.items())
            ]

    def counter_total(self, name: str, **label_filter: Any) -> float:
        """Sum of ``name`` across every label set matching the (possibly
        partial) filter — e.g. commits for one replica_id over all ranks."""
        want = dict(_label_items(label_filter))
        total = 0.0
        for metric_name, items, kind, metric in self._items():
            if metric_name != name or kind != "counter":
                continue
            have = dict(items)
            if all(have.get(k) == v for k, v in want.items()):
                total += metric.value
        return total

    def gauge_value(self, name: str, **labels: Any) -> Optional[float]:
        key = (name, _label_items(labels))
        with self._lock:
            metric = self._metrics.get(key)
        return metric.value if isinstance(metric, Gauge) else None

    def histogram_stats(self, name: str, **label_filter: Any) -> Dict[str, Any]:
        """Aggregated {"sum","count","mean"} over matching label sets."""
        want = dict(_label_items(label_filter))
        total_sum, total_count = 0.0, 0
        for metric_name, items, kind, metric in self._items():
            if metric_name != name or kind != "histogram":
                continue
            have = dict(items)
            if all(have.get(k) == v for k, v in want.items()):
                stats = metric.stats()
                total_sum += stats["sum"]
                total_count += stats["count"]
        return {
            "sum": total_sum,
            "count": total_count,
            "mean": (total_sum / total_count) if total_count else 0.0,
        }

    # -- exporters ---------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """JSON-safe dump: {"counters"|"gauges"|"histograms": {name:
        [{"labels": {...}, ...value fields}]}}."""
        out: Dict[str, Any] = {"counters": {}, "gauges": {}, "histograms": {}}
        for name, items, kind, metric in self._items():
            entry: Dict[str, Any] = {"labels": dict(items)}
            if kind == "histogram":
                entry.update(metric.stats())
            else:
                entry["value"] = metric.value
            out[kind + "s"].setdefault(name, []).append(entry)
        return out

    def prometheus_text(self) -> str:
        lines: List[str] = []
        seen_type: set = set()
        for name, items, kind, metric in self._items():
            if name not in seen_type:
                seen_type.add(name)
                lines.append(f"# TYPE {name} {kind}")
            if kind == "histogram":
                stats = metric.stats()
                for le, count in stats["buckets"].items():
                    bucket_items = items + (("le", le),)
                    lines.append(
                        f"{name}_bucket{_label_str(bucket_items)} {count}"
                    )
                lines.append(f"{name}_sum{_label_str(items)} {_fmt(stats['sum'])}")
                lines.append(f"{name}_count{_label_str(items)} {stats['count']}")
            else:
                lines.append(f"{name}{_label_str(items)} {_fmt(metric.value)}")
        return "\n".join(lines) + "\n"


REGISTRY = Registry()


class WindowedSeries:
    """Fixed-size, byte-budgeted ring of per-window aggregate dicts.

    The registry above is cumulative-only; this is the windowed time-series
    layer on top (history.py's budgeting discipline applied to metrics):
    each appended window is a JSON-safe dict, its retained cost is its
    compact-JSON encoding size, and the ring evicts oldest-first past
    EITHER bound (``max_windows`` windows or ``max_bytes`` bytes, always
    keeping the newest window) — so rates and percentiles over recent
    windows stay queryable live without unbounded growth. First consumer:
    the goodput ledger (torchft_tpu/goodput.py); the class is generic so
    future planes can ring their own windows.
    """

    def __init__(self, max_windows: int = 60, max_bytes: int = 262144) -> None:
        self.max_windows = max(1, int(max_windows))
        self.max_bytes = max(1, int(max_bytes))
        self._ring: List[Tuple[Dict[str, Any], int]] = []
        self._bytes = 0
        self._lock = threading.Lock()
        self._evicted = 0

    def append(self, window: Dict[str, Any]) -> None:
        size = len(json.dumps(window, separators=(",", ":"), default=str))
        with self._lock:
            self._ring.append((window, size))
            self._bytes += size
            while len(self._ring) > 1 and (
                len(self._ring) > self.max_windows or self._bytes > self.max_bytes
            ):
                _, evicted_size = self._ring.pop(0)
                self._bytes -= evicted_size
                self._evicted += 1

    def windows(self) -> List[Dict[str, Any]]:
        """Retained windows, oldest first."""
        with self._lock:
            return [window for window, _ in self._ring]

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def total_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def evicted(self) -> int:
        """Windows dropped by either budget so far."""
        with self._lock:
            return self._evicted

    def values(self, key: str) -> List[float]:
        """Numeric ``window[key]`` values across retained windows (windows
        without the key, or with a non-numeric value, are skipped)."""
        out: List[float] = []
        for window in self.windows():
            value = window.get(key)
            if isinstance(value, (int, float)) and not isinstance(value, bool):
                out.append(float(value))
        return out

    def rate(self, key: str) -> Optional[float]:
        """Mean of ``window[key]`` over retained windows (None when empty)."""
        values = self.values(key)
        return sum(values) / len(values) if values else None

    def percentile(self, key: str, q: float) -> Optional[float]:
        """Nearest-rank percentile of ``window[key]`` (``q`` in [0, 100])."""
        values = sorted(self.values(key))
        if not values:
            return None
        rank = min(len(values) - 1, max(0, int(round(q / 100.0 * (len(values) - 1)))))
        return values[rank]


# -- module-level conveniences bound to the default registry ----------------


def counter(name: str, **labels: Any) -> Counter:
    return REGISTRY.counter(name, **labels)


def gauge(name: str, **labels: Any) -> Gauge:
    return REGISTRY.gauge(name, **labels)


def histogram(
    name: str, buckets: Iterable[float] = DEFAULT_TIME_BUCKETS, **labels: Any
) -> Histogram:
    return REGISTRY.histogram(name, buckets=buckets, **labels)


def inc(name: str, amount: float = 1.0, **labels: Any) -> None:
    REGISTRY.counter(name, **labels).inc(amount)


def set_gauge(name: str, value: float, **labels: Any) -> None:
    REGISTRY.gauge(name, **labels).set(value)


def observe(name: str, value: float, **labels: Any) -> None:
    REGISTRY.histogram(name, **labels).observe(value)


@contextmanager
def timer(name: str, **labels: Any) -> Generator[None, None, None]:
    """Times the with-body into histogram ``name`` (seconds)."""
    start = time.perf_counter()
    try:
        yield
    finally:
        observe(name, time.perf_counter() - start, **labels)


def snapshot() -> Dict[str, Any]:
    return REGISTRY.snapshot()


def snapshot_to_prometheus(
    snap: Dict[str, Any],
    extra_labels: Optional[Dict[str, str]] = None,
    skip_type_names: Iterable[str] = (),
) -> str:
    """Renders a :func:`snapshot`-shaped dict (possibly from ANOTHER
    process, e.g. the heal-serving child's scraped registry) into the
    Prometheus exposition format, adding ``extra_labels`` to every series
    so merged foreign series stay distinguishable. Names in
    ``skip_type_names`` suppress the ``# TYPE`` line (already emitted by
    the local registry). Best-effort on malformed input: bad entries are
    skipped, never raised."""
    extra = tuple(sorted((extra_labels or {}).items()))
    skip = set(skip_type_names)
    kind_of = {"counters": "counter", "gauges": "gauge", "histograms": "histogram"}
    lines: List[str] = []
    seen_type: set = set()
    for section, kind in kind_of.items():
        for name, entries in sorted((snap.get(section) or {}).items()):
            for entry in entries:
                try:
                    items = _label_items({**entry.get("labels", {}), **dict(extra)})
                    if name not in seen_type and name not in skip:
                        seen_type.add(name)
                        lines.append(f"# TYPE {name} {kind}")
                    if kind == "histogram":
                        for le, count in entry.get("buckets", {}).items():
                            bucket_items = items + (("le", str(le)),)
                            lines.append(
                                f"{name}_bucket{_label_str(bucket_items)} {count}"
                            )
                        lines.append(
                            f"{name}_sum{_label_str(items)} {_fmt(entry['sum'])}"
                        )
                        lines.append(
                            f"{name}_count{_label_str(items)} {entry['count']}"
                        )
                    else:
                        lines.append(
                            f"{name}{_label_str(items)} {_fmt(entry['value'])}"
                        )
                except (KeyError, TypeError, ValueError):
                    continue
    return "\n".join(lines) + ("\n" if lines else "")


def prometheus_text() -> str:
    return REGISTRY.prometheus_text()


def counter_total(name: str, **label_filter: Any) -> float:
    return REGISTRY.counter_total(name, **label_filter)


def gauge_value(name: str, **labels: Any) -> Optional[float]:
    return REGISTRY.gauge_value(name, **labels)


def histogram_stats(name: str, **label_filter: Any) -> Dict[str, Any]:
    return REGISTRY.histogram_stats(name, **label_filter)


# -- HTTP exposition --------------------------------------------------------


def _serve_metrics_http(
    handler: Any,
    registry: Registry,
    path: str,
    extra_text: Optional[Any] = None,
    extra_json: Optional[Any] = None,
) -> bool:
    """Shared route logic for any BaseHTTPRequestHandler: serves
    ``/metrics`` (Prometheus text) and ``/metrics.json`` (snapshot);
    returns False when the path is not a metrics route. Reused by the
    checkpoint transport's server so every replica already listening for
    heals answers scrapes on the same port. ``extra_text``/``extra_json``
    (callables) let a caller merge foreign series — e.g. the donor merges
    its heal-serving child's scraped registry; both are best-effort and
    never fail the scrape."""
    route = path.split("?", 1)[0].rstrip("/")
    if route == "/trace.json":
        # The fleet trace plane's pull surface: the process journal's full
        # ring + clock info, merged across replicas by scripts/
        # fleet_trace.py. Lazy import keeps metrics a leaf module.
        try:
            from torchft_tpu import tracing

            payload = tracing.trace_json_payload()
        except Exception as e:  # noqa: BLE001 — scrape must never fail
            payload = {"error": str(e)}
        body = json.dumps(payload).encode()
        content_type = "application/json"
    elif route == "/metrics":
        body_text = registry.prometheus_text()
        if extra_text is not None:
            try:
                body_text += extra_text() or ""
            except Exception:  # noqa: BLE001 — merge is best-effort
                pass
        body = body_text.encode()
        content_type = "text/plain; version=0.0.4; charset=utf-8"
    elif route == "/metrics.json":
        payload = {"ts": time.time(), "metrics": registry.snapshot()}
        if extra_json is not None:
            try:
                extra = extra_json()
                if extra:
                    payload.update(extra)
            except Exception:  # noqa: BLE001 — merge is best-effort
                pass
        body = json.dumps(payload).encode()
        content_type = "application/json"
    else:
        return False
    handler.send_response(200)
    handler.send_header("Content-Type", content_type)
    handler.send_header("Content-Length", str(len(body)))
    handler.end_headers()
    handler.wfile.write(body)
    return True


class MetricsHTTPServer:
    """Standalone threaded /metrics endpoint (processes with no checkpoint
    transport: lighthouse daemons, benchmarks, the doctor's probe target)."""

    def __init__(self, port: int, registry: Registry = REGISTRY) -> None:
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt: str, *args: Any) -> None:  # silence
                pass

            def do_GET(self) -> None:
                if not _serve_metrics_http(self, registry, self.path):
                    self.send_error(404, "unknown route (try /metrics)")

        self._server = ThreadingHTTPServer(("", port), Handler)
        self._server.daemon_threads = True
        self._thread = threading.Thread(
            target=functools.partial(self._server.serve_forever, poll_interval=0.05), daemon=True, name="tpuft-metrics"
        )
        self._thread.start()

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    def shutdown(self) -> None:
        self._server.shutdown()
        self._server.server_close()


_HTTP_SERVER: Optional[MetricsHTTPServer] = None
_HTTP_LOCK = threading.Lock()


def start_http_server(port: int, registry: Registry = REGISTRY) -> MetricsHTTPServer:
    return MetricsHTTPServer(port, registry)


def maybe_start_http_server() -> Optional[MetricsHTTPServer]:
    """Starts the per-process /metrics server iff ``$TPUFT_METRICS_PORT``
    is set (idempotent; one server per process). A malformed or
    already-bound port logs and returns None — metrics must never take
    down training."""
    global _HTTP_SERVER
    value = os.environ.get(ENV_PORT)
    if not value:
        return None
    with _HTTP_LOCK:
        if _HTTP_SERVER is not None:
            return _HTTP_SERVER
        try:
            _HTTP_SERVER = start_http_server(int(value))
        except (ValueError, OSError) as e:
            import logging

            logging.getLogger(__name__).warning(
                "TPUFT_METRICS_PORT=%r: /metrics server not started (%s)",
                value, e,
            )
            return None
        return _HTTP_SERVER


def push_interval_sec(default: float = 10.0) -> float:
    """The store-push rate limit from ``$TPUFT_METRICS_PUSH_SEC``
    (malformed values fall back to the default; <= 0 disables)."""
    try:
        return float(os.environ.get(ENV_PUSH_SEC, str(default)))
    except ValueError:
        return default
