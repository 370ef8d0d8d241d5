"""ft_host_us_per_buffer: microseconds of the FT step's serial host path for
each buffer a dispatch hands to the runtime and takes back: ``ft_step_host_ms``
(its own arithmetic, from the capture's journal) over the growth of
``tpuft_step_dispatch_buffers_total`` (both directions) a
``tpuft_step_dispatch_total``, from the capture's ``counters``. The host path
grows with the leaves of the state (PERF.md section 6, PR 57): a change that
makes a buffer cheaper moves this number, one that makes buffers fewer moves
``ft_step_host_ms`` and leaves it. None without a capture and where the
counters did not grow (a program of before PR 59)."""

from pathlib import Path

from chipbench import spec


def _growth(counters, name):
    return sum(entry.get("value", 0.0) for entry in counters.get(name) or [])


def read(obs):
    counters = (obs.get("capture") or {}).get("counters") or {}
    dispatches = _growth(counters, "tpuft_step_dispatch_total")
    buffers = _growth(counters, "tpuft_step_dispatch_buffers_total")
    if not dispatches or not buffers:
        return None
    host_ms = spec.load_module(Path(__file__).with_name("ft_step_host_ms.py")).read(obs)
    if host_ms is None:
        return None
    return 1e3 * host_ms / (buffers / dispatches)
