"""mixed_attn_time_pct: device seconds in the attention kernels of a stack
whose layers are of two kinds, full and windowed, over the window's busy device
seconds. The kernels are the Pallas calls of the step programs whose names the
cell's architecture file states (``ATTENTION_KERNEL``: the flash forward and
the one backward call of a layer without a window, under the scope the model
traces them in, and the two calls of a layer with one, which carry names of
their own), both kinds of layer, forward and backward. Found by that pattern
and never by exclusion: the routed layer's calls (the grouped products and the
sums by token) are Pallas calls too, and are not attention.

Whom it is for: a cell whose architecture file has ``ATTENTION_KERNEL``; where
the program has no such call (a parent without the window's kernels, or any
other architecture) nothing is read."""

from pathlib import Path

from chipbench.spec import load_module

architecture_of = load_module(Path(__file__).with_name("expert_time_pct.py")).architecture_of


def seconds_of(obs, pattern: str) -> float:
    """Device seconds of the calls the architecture's ``pattern`` names; 0.0
    where the trace, the architecture or the pattern is not there."""
    trace = obs.get("trace")
    if not trace or not trace.get("busy_s"):
        return 0.0
    try:
        architecture = architecture_of(obs)
    except (OSError, KeyError, TypeError):
        return 0.0
    named = getattr(architecture, pattern, None)
    if named is None:
        return 0.0
    return sum(
        s for rows in trace.get("kernels", {}).values() for name, s in rows if named.search(name)
    )


def share_of_peak(obs, pattern: str, count: str):
    """100 x the operations the architecture's ``count`` gives for the
    window's steps over the seconds of the calls ``pattern`` names, against
    the published bf16 peak; None where any of them is missing."""
    if not obs.get("peaks") or not obs.get("steps"):
        return None
    seconds = seconds_of(obs, pattern)
    if not seconds:
        return None
    needed = obs["steps"] * getattr(architecture_of(obs), count)(obs["config"], obs["batch"], obs["seq"])
    return 100.0 * needed / seconds / (obs["peaks"]["bf16_tflops"] * 1e12)


def read(obs):
    seconds = seconds_of(obs, "ATTENTION_KERNEL")
    return 100.0 * seconds / obs["trace"]["busy_s"] if seconds else None
