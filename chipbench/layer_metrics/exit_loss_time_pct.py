"""exit_loss_time_pct: device seconds of the exits' vocabulary products and
loss (forward and backward, every exit of a step) over the window's busy
device seconds, in a model that leaves through several exits a step. Which
device ops those are is the architecture file's to say (``exit_loss_seconds``:
the fused loss is plain XLA, so its ops are found by the result shapes only
its vocabulary slabs have; its docstring lists the shapes, what it cannot see,
and why the share is not comparable across a change of the path).

Whom it is for: a cell whose architecture file has that function; where it has
none, or finds no such op (a parent without the path), nothing is read."""

from pathlib import Path

from chipbench.spec import load_module

architecture_of = load_module(Path(__file__).with_name("expert_time_pct.py")).architecture_of


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    try:
        find = getattr(architecture_of(obs), "exit_loss_seconds", None)
    except (OSError, KeyError, TypeError):
        return None
    seconds = find(trace, obs["config"], obs["batch"], obs["seq"]) if find else 0.0
    return 100.0 * seconds / trace["busy_s"] if seconds else None
