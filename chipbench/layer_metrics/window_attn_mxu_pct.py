"""window_attn_mxu_pct: the share of the chip's bf16 peak that the WINDOWED
layers' attention kernels reach, the new pair classes' share of their
roofline: the operations those layers need for the window's steps (the
architecture file's ``window_attention_flops``: seven matmuls over the sum
over t of min(t + 1, window) pairs a layer) over the device seconds of the
calls that carry a window (``WINDOW_KERNEL``: they have names of their own in
a device trace, so they are told from a full layer's calls by name), against
the published peak (chipbench/peaks.json). It cannot pass 100. A kernel that
only masked the window would walk every causal block and read under half of
what one that skips the blocks behind it reads."""

from pathlib import Path

from chipbench.spec import load_module

_time = load_module(Path(__file__).with_name("mixed_attn_time_pct.py"))


def read(obs):
    return _time.share_of_peak(obs, "WINDOW_KERNEL", "window_attention_flops")
