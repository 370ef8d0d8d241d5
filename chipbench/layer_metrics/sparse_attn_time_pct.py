"""sparse_attn_time_pct: device seconds of attention under the learned key
selection (index scores, per-row threshold, attention over the selected keys,
forward and backward) over the window's busy device seconds. Which device ops
those are is the architecture file's to say (``selected_attention_seconds``:
the ops of the program's tiled XLA path, found by their result shapes; its
docstring names what that cannot see, and why the share is not comparable
across a change of the path).

Whom it is for: a cell whose architecture file has that function; where it has
none, or finds no such op (a parent without the path), nothing is read."""

from pathlib import Path

from chipbench.spec import load_module

architecture_of = load_module(Path(__file__).with_name("expert_time_pct.py")).architecture_of


def selected_seconds(obs, architecture) -> float:
    find = getattr(architecture, "selected_attention_seconds", None)
    return find(obs["trace"], obs["config"], obs["seq"]) if find else 0.0


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = selected_seconds(obs, architecture_of(obs))
    return 100.0 * seconds / trace["busy_s"] if seconds else None
