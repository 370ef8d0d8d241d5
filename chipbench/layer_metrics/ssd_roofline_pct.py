"""ssd_roofline_pct: the state-space scan's share of its roofline: the least
time the chip could take for what the scan and its convolution NEED for the
window's steps, the larger of operations over the published bf16 peak and
bytes over the published memory bandwidth (chipbench/peaks.json; the
architecture file's ``ssd_flops``: the chunked algorithm's matmuls at the
PUBLISHED chunk over the causal pairs alone, three times the forward;
``ssd_bytes``: the convolution's and the scan's inputs, outputs and their
gradients in the run dtype), over the device seconds ssd_time_pct sums. Needed
work, not work done, whatever chunk or kernel the program runs: it cannot pass
100. What lowers it: the decay matrices and scores written to memory and read
back, the masked half of a chunk's scores computed, float32 elementwise work on
(chunk x chunk) a head, the forward repeated under remat. The reader finds the
path by its result shapes and sees part of its time only (see
``ssd_seconds``), so the share reads HIGH by that part, and a reading is
comparable with another of the SAME path only."""

from pathlib import Path

from chipbench.spec import load_module

_time = load_module(Path(__file__).with_name("ssd_time_pct.py"))


def read(obs):
    if not obs.get("peaks") or not obs.get("steps"):
        return None
    seconds = _time.scan_seconds(obs)
    if not seconds:
        return None
    architecture = _time.architecture_of(obs)
    size = (obs["config"], obs["batch"], obs["seq"])
    least = max(
        architecture.ssd_flops(*size) / (obs["peaks"]["bf16_tflops"] * 1e12),
        architecture.ssd_bytes(*size) / (obs["peaks"]["hbm_gbps"] * 1e9),
    )
    return 100.0 * obs["steps"] * least / seconds
