"""ft_step_host_ms: the FT step's serial host path, from the capture's journal
(``--trace 2``'s traced tail): the mean over the root ``step`` events of a
root's duration less the ``device_sync`` events inside it on its thread. The
device sync is the wait for the step's compute, so what is left is the host
work a lone replica's step adds in series: quorum hand-over and wait, dispatch,
commit wait, adopt and the root's own uncovered time. Beside it ``ft_host_ms``
times the same layer from outside (and holds the wait). None without a
capture (``--trace 1`` of before this metric keeps none)."""


def read(obs):
    events = (obs.get("capture") or {}).get("events") or []
    roots = [e for e in events if e["name"] == "step" and e.get("ph") == "X"]
    if not roots:
        return None
    syncs = [e for e in events if e["name"] == "device_sync" and e.get("ph") == "X"]
    host = 0.0
    for root in roots:
        start, end = root["t_mono"], root["t_mono"] + root["dur"]
        host += root["dur"] - sum(
            s["dur"] for s in syncs
            if s["thread"] == root["thread"] and start <= s["t_mono"] and s["t_mono"] + s["dur"] <= end
        )
    return 1e3 * host / len(roots)
