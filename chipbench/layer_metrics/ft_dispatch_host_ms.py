"""ft_dispatch_host_ms: host milliseconds a step the FT step spends in the call
of its jitted step program, from the capture's journal (``--trace 2``'s traced
tail): the seconds of the ``update_dispatch`` events inside a root ``step``
event on the root's thread, over the roots. The selection is
``ft_step_host_ms``'s for ``device_sync``; that metric holds this one, and
``ft_dispatch_execute_ms`` says how much of it the runtime's execute call is.
None without a capture, and where no root holds such an event."""


def per_root_ms(obs, name):
    """Seconds of the ``name`` events inside a root on its thread, in ms a
    root (``ft_adopt_host_ms`` reads ``adopt`` through this)."""
    events = (obs.get("capture") or {}).get("events") or []
    roots = [e for e in events if e["name"] == "step" and e.get("ph") == "X"]
    parts = [e for e in events if e["name"] == name and e.get("ph") == "X"]
    seconds, found = 0.0, False
    for root in roots:
        start, end = root["t_mono"], root["t_mono"] + root["dur"]
        for p in parts:
            if p["thread"] == root["thread"] and start <= p["t_mono"] and p["t_mono"] + p["dur"] <= end:
                seconds += p["dur"]
                found = True
    return 1e3 * seconds / len(roots) if found else None


def read(obs):
    return per_root_ms(obs, "update_dispatch")
