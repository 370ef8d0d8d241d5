"""outer_sync_host_ms: host milliseconds one fragment sync of streaming DiLoCo
takes, from the capture's journal (``--trace 2``'s traced tail): the seconds of
the ``prepare_sync`` and ``perform_sync`` events over the ``perform_sync``
events (one a sync). Beside ``outer_sync_ms``, which times a sync from outside
and holds the device's share, it says how much of a sync is the host's. None
without a capture."""


def read(obs):
    events = (obs.get("capture") or {}).get("events") or []
    spans = [e for e in events if e.get("ph") == "X"]
    syncs = sum(1 for e in spans if e["name"] == "perform_sync")
    if not syncs:
        return None
    seconds = sum(e["dur"] for e in spans if e["name"] in ("prepare_sync", "perform_sync"))
    return 1e3 * seconds / syncs
