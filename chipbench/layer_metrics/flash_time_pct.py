"""flash_time_pct: device seconds in the flash attention kernels over the
window's busy device seconds. The kernels are the Pallas calls (forward, dq,
dk/dv of ops/flash_attention.py) inside the train-step programs: every
``tpu_custom_call`` of the trace that is not in one of DiLoCo's codec
programs, whose kernels codec_gbps reads.

Whom it is for: a cell is listed only if every Pallas call of its step programs
is causal flash attention and every one of its ``num_hidden_layers`` layers
runs it at ``num_attention_heads x head_dim``; any other cell stays out of this
list and flash_mxu_pct's and brings readers of its own for its kernels.
``spec.problems`` holds the lists to it through the line below."""

import re

ARCHITECTURE_SAYS = "FLASH_ATTENTION_IN_EVERY_LAYER"
CODEC_PROGRAM = re.compile(r"quantize_pseudograd|apply_outer")


def flash_seconds(trace) -> float:
    return sum(
        s for module, rows in trace["kernels"].items()
        if not CODEC_PROGRAM.search(module) for _, s in rows
    )


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = flash_seconds(trace)
    return 100.0 * seconds / trace["busy_s"] if seconds else None
