"""sparse_attn_mxu_pct: selected attention's share of the chip's bf16 peak:
the operations attention over the SELECTED pairs needs for the window's steps
(the architecture file's ``selected_attention_flops``: seven matmuls over the
sum of min(t + 1, topk) pairs, plus the index scores over the causal pairs,
forward only) over the device seconds sparse_attn_time_pct sums, against the
published peak (chipbench/peaks.json). Count and seconds are of the SAME
mechanism since PR 64: the index scores, the threshold and attention over the
selected keys, forward and backward, whatever implements any of them: the
selection's XLA ops and every Pallas call that is not the expert layer's (from
PR 47 to PR 63 the seconds were the selection's alone under a count that held
attention's matmuls: 49.33 on 18.58% of the busy time, ledger PR 63, and a
faster selection alone would have read past 100). It cannot pass 100. What
lowers it: the pairs the flash kernels step and mask where the need is the
selected ones (72 of 128 block pairs a head stepped, every one under the int8
mask), the float32 index scores at six passes of the MXU, the radix select's 32
counting passes (no product at all), the backward's recomputation beyond the
one the count holds. The reader sees the selection's XLA ops by their result
shapes and misses a few (see ``selected_attention_seconds``), so the share reads
a little HIGH; the kernels are found by name and all seen."""

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
