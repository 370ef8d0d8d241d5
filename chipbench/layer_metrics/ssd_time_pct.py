"""ssd_time_pct: device seconds of the state-space layers' scan and its short
convolution (forward and backward; NOT the projections around them) over the
window's busy device seconds. Which device ops those are is the architecture
file's to say (``ssd_seconds``: the ops of the program's chunked XLA path,
found by the result shapes only that path produces, a Mosaic call by its name;
its docstring lists the shapes, what it cannot see, and why the share is not
comparable across a change of the path).

Whom it is for: a cell whose architecture file has that function; where it has
none, or finds no such op (a parent without the path), nothing is read."""

from pathlib import Path

from chipbench.spec import load_module

architecture_of = load_module(Path(__file__).with_name("expert_time_pct.py")).architecture_of


def scan_seconds(obs) -> float:
    """Device seconds of the scan's ops; 0.0 where the trace, the architecture
    or its ``ssd_seconds`` is not there."""
    trace = obs.get("trace")
    if not trace or not trace.get("busy_s"):
        return 0.0
    try:
        find = getattr(architecture_of(obs), "ssd_seconds", None)
    except (OSError, KeyError, TypeError):
        return 0.0
    return find(trace, obs["config"], obs["batch"], obs["seq"]) if find else 0.0


def read(obs):
    seconds = scan_seconds(obs)
    return 100.0 * seconds / obs["trace"]["busy_s"] if seconds else None
