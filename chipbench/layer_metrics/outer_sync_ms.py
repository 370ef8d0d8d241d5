"""outer_sync_ms: what one fragment sync adds to the loop: the window's time
between its fetches minus its steps times the median step period, divided by
the fragment syncs in the window (fragments x rounds). Step periods are the
gaps between the harness's per-step clock readings (host clock, paced by the
readiness wait one step back), so the host's stall in a sync and the device's
both land in it, whichever iteration absorbs them."""

import statistics


def read(obs):
    ends, fragments = obs.get("step_ends"), obs.get("fragments")
    if not ends or not fragments or not obs.get("units") or len(ends) < 3:
        return None
    typical = statistics.median(b - a for a, b in zip(ends[:-1], ends[1:]))
    extra = obs["window_s"] - obs["steps"] * typical
    return 1e3 * extra / (fragments * obs["units"])
