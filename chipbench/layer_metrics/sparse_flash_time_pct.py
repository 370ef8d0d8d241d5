"""sparse_flash_time_pct: device seconds in the flash attention kernels that
attend under the learned key selection (ops/flash_attention.py with the
selection as an operand: one forward and one backward Mosaic call a layer step)
over the window's busy device seconds. The kernels are the Pallas calls of the
step programs that the cell's architecture file does NOT name as the expert
layer's: ``EXPERT_LAYER_KERNEL`` where the file has it (the grouped product's
calls and the sums by token, which are Mosaic calls since PR 51), else
``EXPERT_KERNEL`` (the grouped product's, which ``expert_time_pct`` reads): in
such a cell every other call is attention. Calls inside DiLoCo's codec programs
are ``codec_gbps``'s, as for ``flash_time_pct``.

Whom it is for: a cell whose architecture file has ``EXPERT_KERNEL``; where the
program has no such call (a parent whose attention is plain tiled XLA) nothing
is read. It says whether the mechanism engages; beside it ``sparse_attn_time_pct``
reads these calls AND the selection's XLA ops (since PR 64)."""

from pathlib import Path

from chipbench.spec import load_module

architecture_of = load_module(Path(__file__).with_name("expert_time_pct.py")).architecture_of
CODEC_PROGRAM = load_module(Path(__file__).with_name("flash_time_pct.py")).CODEC_PROGRAM


def kernel_seconds_but(trace, named) -> float:
    """Seconds of the step programs' Pallas calls whose names ``named`` does
    not match."""
    return sum(
        s for module, rows in trace.get("kernels", {}).items()
        if not CODEC_PROGRAM.search(module)
        for name, s in rows if not named.search(name)
    )


def attention_seconds(trace, architecture) -> float:
    named = getattr(architecture, "EXPERT_LAYER_KERNEL", None) or getattr(architecture, "EXPERT_KERNEL", None)
    return kernel_seconds_but(trace, named) if named is not None else 0.0


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = attention_seconds(trace, architecture_of(obs))
    return 100.0 * seconds / trace["busy_s"] if seconds else None
