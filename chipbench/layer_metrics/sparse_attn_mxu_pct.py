"""sparse_attn_mxu_pct: selected attention's share of the chip's bf16 peak:
the operations attention over the SELECTED pairs needs for the window's steps
(the architecture file's ``selected_attention_flops``: seven matmuls over the
sum of min(t + 1, topk) pairs, plus the index scores over the causal pairs,
forward only) over the device seconds sparse_attn_time_pct sums, against the
published peak (chipbench/peaks.json). It cannot pass 100. What lowers it: the
pairs computed under the mask and thrown away (a plain tiled path computes
every causal pair of a tile's key length where the need is the selected ones),
the float32 index scores at six passes of the MXU, a tile recomputed in the
backward. The reader finds the path by its result shapes and sees part of its
time only (see ``selected_attention_seconds``), so the share reads HIGH by
that part, and a reading is comparable with another of the SAME path only."""

from pathlib import Path

from chipbench.spec import load_module

_time = load_module(Path(__file__).with_name("sparse_attn_time_pct.py"))


def read(obs):
    trace = obs.get("trace")
    if not trace or not obs.get("peaks") or not obs.get("steps"):
        return None
    architecture = _time.architecture_of(obs)
    seconds = _time.selected_seconds(obs, architecture)
    if not seconds:
        return None
    needed = obs["steps"] * architecture.selected_attention_flops(obs["config"], obs["batch"], obs["seq"])
    return 100.0 * needed / seconds / (obs["peaks"]["bf16_tflops"] * 1e12)
