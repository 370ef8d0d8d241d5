"""codec_gbps: bytes the fp8 quantize and dequantize kernels must read and
write (chipbench/flops.py, from the fragments' element counts) over their
device seconds in the trace; read beside the chip's 819 GB/s. The kernels are
the ``tpu_custom_call``s of the two codec programs (jit_quantize_pseudograd
and jit_apply_outer of local_sgd.py)."""

import re

from chipbench import flops

CODEC_PROGRAM = re.compile(r"quantize_pseudograd|apply_outer")


def read(obs):
    trace, elements = obs.get("trace"), obs.get("fragment_elements")
    if not trace or not elements or not obs.get("units"):
        return None
    seconds = sum(
        s for module, rows in trace["kernels"].items()
        if CODEC_PROGRAM.search(module) for _, s in rows
    )
    if not seconds:
        return None
    # Every fragment is quantized once and dequantized once a round.
    per_round = sum(sum(flops.fp8_codec_bytes(n).values()) for n in elements)
    return per_round * obs["units"] / seconds / 1e9
