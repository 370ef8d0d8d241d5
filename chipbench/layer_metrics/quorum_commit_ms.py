"""quorum_commit_ms: host milliseconds per inner step in the control plane:
the window's growth of tpuft_quorum_seconds + tpuft_commit_barrier_seconds
(manager.py) over its steps. Streaming DiLoCo votes once a fragment sync."""


def of(obs):
    counters = obs.get("counters") or {}
    names = ("tpuft_quorum_seconds", "tpuft_commit_barrier_seconds")
    if not any(counters.get(n, {}).get("count") for n in names) or not obs["steps"]:
        return None
    return 1e3 * sum(counters.get(n, {}).get("sum", 0.0) for n in names) / obs["steps"]


def read(obs):
    return of(obs)
