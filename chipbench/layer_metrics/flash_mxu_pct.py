"""flash_mxu_pct: the flash attention kernels' share of the chip's bf16 peak:
the operations causal attention NEEDS for the window's steps
(chipbench/flops.py: per layer and sequence seven matmuls of s x s x head_dim
over every head, each halved by the causal mask) over the device seconds of
the same ``tpu_custom_call``s flash_time_pct sums, against the published peak
(chipbench/peaks.json). A kernel's roofline share (attention at these shapes
is bound by operations, not bytes): it cannot pass 100, and what the kernels
recompute beyond the need, or a forward repeated under remat, lowers it."""

from pathlib import Path

from chipbench import flops
from chipbench.spec import load_module

flash_seconds = load_module(Path(__file__).with_name("flash_time_pct.py")).flash_seconds


def read(obs):
    trace = obs.get("trace")
    if not trace or not obs.get("peaks") or not obs.get("steps"):
        return None
    seconds = flash_seconds(trace)
    if not seconds:
        return None
    needed = obs["steps"] * flops.flash_attention_flops(obs["config"], obs["batch"], obs["seq"])
    return 100.0 * needed / seconds / (obs["peaks"]["bf16_tflops"] * 1e12)
