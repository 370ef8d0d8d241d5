"""flash_mxu_pct: the flash attention kernels' share of the chip's bf16 peak:
the operations causal attention NEEDS for the window's steps
(chipbench/flops.py: per layer and sequence seven matmuls of s x s x head_dim
over every head, each halved by the causal mask) over the device seconds of
the same ``tpu_custom_call``s flash_time_pct sums, against the published peak
(chipbench/peaks.json). A kernel's roofline share (attention at these shapes
is bound by operations, not bytes): it cannot pass 100, and what the kernels
recompute beyond the need, or a forward repeated under remat, lowers it.

Whom it is for: a cell is listed only if every Pallas call of its step programs
is causal flash attention and every one of its ``num_hidden_layers`` layers
runs it at ``num_attention_heads x head_dim``; any other cell stays out of this
list and flash_time_pct's and brings readers of its own for its kernels. In a
stack where one layer in four is attention the count would be four times the
need and a kernel at 50% would read 200%. ``spec.problems`` holds the lists to
it through the line below."""

from pathlib import Path

from chipbench import flops
from chipbench.spec import load_module

ARCHITECTURE_SAYS = "FLASH_ATTENTION_IN_EVERY_LAYER"
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
