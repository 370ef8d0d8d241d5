"""ft_host_ms: host milliseconds a step spends in the FT step protocol's own
sections: the window's growth of tpuft_device_sync_seconds and
tpuft_update_dispatch_seconds (optim.py records both) over its steps. The
device sync contains the wait for the step's compute, so read it against the
step time (1000 * 8192 / tokens_per_s ms): what exceeds it is the protocol's."""


def read(obs):
    counters = obs.get("counters") or {}
    names = ("tpuft_device_sync_seconds", "tpuft_update_dispatch_seconds")
    if not all(counters.get(n, {}).get("count") for n in names) or not obs["steps"]:
        return None
    return 1e3 * sum(counters[n]["sum"] for n in names) / obs["steps"]
