"""sparse_attn_time_pct: device seconds of attention under the learned key
selection (index scores, per-row threshold, attention over the selected keys,
forward and backward, whatever implements any of them) over the window's busy
device seconds. Which device ops those are is the architecture file's to say
(``selected_attention_seconds``: since PR 64 the selection's tiled XLA ops,
found by their result shapes, AND the Pallas calls that are not the expert
layer's, which since PR 47 are the flash kernels that attend with the selection
as an operand and which ``sparse_flash_time_pct`` reads alone; from PR 47 to
PR 63 this read the XLA ops alone, 18.58 on the ledger where the whole
mechanism is some 47). Its docstring names what the shapes cannot see.

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
