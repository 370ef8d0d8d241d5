"""device_idle_pct: 1 minus the union of the device's op intervals over the
traced window; for several chips the mean, each traced by the process that
holds it."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
