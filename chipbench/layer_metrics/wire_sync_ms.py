"""wire_sync_ms: host milliseconds per step in the cross-group gradient sync:
the harness's span around ft_allreduce_sharded (staging, the manager's
allreduce_pytree, the scatter back), worst replica group."""


def read(obs):
    values = []
    for group in obs.get("groups", []):
        span = (group.get("spans") or {}).get("chipbench/wire")
        if span and span["count"]:
            values.append(1e3 * span["sum"] / span["count"])
    return max(values) if values else None
