"""flash_time_pct: device seconds in the flash attention kernels over the
window's busy device seconds. The kernels are the Pallas calls (forward, dq,
dk/dv of ops/flash_attention.py) inside the train-step programs: every
``tpu_custom_call`` of the trace that is not in one of DiLoCo's codec
programs, whose kernels codec_gbps reads."""

import re

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
