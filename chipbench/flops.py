"""Operations and bytes the algorithms NEED, computed from shapes.

Kept with the benchmark so that no PR that claims a gain can change how its
own utilisation is counted. What belongs to one architecture (its parameter
counts, the operations a trained token requires) is in its file under
``architectures/``; here is what several share.
"""

from __future__ import annotations

from typing import Any, Dict


def flash_attention_flops(config: Dict[str, Any], batch: int, seq: int) -> float:
    """Operations causal flash attention needs for ONE training step of
    ``batch`` sequences of ``seq`` positions. A matmul of the scores' shape
    (s x s x head_dim, every head) is 2 * s^2 * head_dim * heads operations,
    half of them under the causal mask. Forward two (q k^T, p v); backward
    five: the scores once more, which the algorithm keeps nowhere and cannot
    avoid recomputing, then dv = p^T do, dp = do v^T, dq = ds k, dk = ds^T q.
    What the backward kernel (one call since PR 42; two before) recomputes
    beyond that one, and a forward repeated under remat, is time and not need.
    For cells in which every one of ``num_hidden_layers`` layers runs causal
    flash attention at ``num_attention_heads x head_dim`` and no other Pallas
    call is in the step programs; any other cell brings a count of its own."""
    per_matmul = seq * seq * config["head_dim"] * config["num_attention_heads"]
    return 7.0 * per_matmul * config["num_hidden_layers"] * batch


def fp8_codec_bytes(elements: int, block: int = 256) -> Dict[str, int]:
    """Bytes the fp8 block codecs must move for ``elements`` values in blocks
    of ``block`` (ops/quantization.py's BLOCK): quantize reads 4 bytes a
    value (a float32 pseudo-gradient until PR 44; since then the two bf16
    operands whose difference the kernel forms in VMEM) and writes one byte a
    value plus a float32 scale a block; dequantize the reverse."""
    blocks = -(-elements // block)
    padded = blocks * block
    one_way = padded * 4 + padded * 1 + blocks * 4
    return {"quantize": one_way, "dequantize": one_way}
