"""outer_sync_idle_ms: milliseconds of device idle one fragment sync costs:
the traced window's idle seconds under the ``tpuft::local_sgd::`` spans of a
sync (all but the inner step's root ``::step`` and its own
``::inner_dispatch``, which every inner step has, sync or none), and under
``tpuft::manager::`` spans (in a lone-replica DiLoCo run those open only
inside a sync), over the fragment syncs in the window (fragments x rounds).
Which stage owns the idle is in the run's ``breakdown.idle_gaps``."""

INNER_STEP = ("tpuft::local_sgd::step", "tpuft::local_sgd::inner_dispatch")


def read(obs):
    trace, fragments = obs.get("trace"), obs.get("fragments")
    if not trace or not fragments or not obs.get("units"):
        return None
    idle = sum(
        seconds for name, seconds in trace["gaps"]
        if name.startswith("tpuft::manager::")
        or (name.startswith("tpuft::local_sgd::") and name not in INNER_STEP)
    )
    return 1e3 * idle / (fragments * obs["units"])
