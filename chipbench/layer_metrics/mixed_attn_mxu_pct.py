"""mixed_attn_mxu_pct: the attention kernels' share of the chip's bf16 peak in
a stack of full and windowed layers: the operations attention NEEDS for the
window's steps (the architecture file's ``mixed_attention_flops``: seven
matmuls of 2 x head_dim x heads over the (query, key) pairs each layer's mask
allows, s (s + 1) / 2 in a full layer and the sum over t of min(t + 1, window)
in a windowed one) over the device seconds mixed_attn_time_pct sums, against
the published peak (chipbench/peaks.json). Needed pairs, not the blocks the
kernels walk: it cannot pass 100, and what lowers it is the masked part of a
block on the diagonal or on the window's edge (since PR 56 a step whose KV
block the mask cuts between its halves computes the needed half alone) and the
backward's recomputation beyond the one the count holds. A pair of blocks the
mask leaves nothing of is no longer stepped (the listed walk, PR 55)."""

from pathlib import Path

from chipbench.spec import load_module

_time = load_module(Path(__file__).with_name("mixed_attn_time_pct.py"))


def read(obs):
    return _time.share_of_peak(obs, "ATTENTION_KERNEL", "mixed_attention_flops")
