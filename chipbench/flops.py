"""Operations and bytes the algorithms NEED, computed from shapes.

Kept with the benchmark so that no PR that claims a gain can change how its
own utilisation is counted.
"""

from __future__ import annotations

from typing import Any, Dict


def parameter_counts(config: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of the model as it is run (depth cut included)."""
    d, ffn = config["hidden_size"], config["intermediate_size"]
    heads, kv, hd = (
        config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"],
    )
    layers, vocab = config["num_hidden_layers"], config["vocab_size"]
    attention = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    mlp = 3 * d * ffn
    norms = 2 * d
    embedding = vocab * d
    head = 0 if config.get("tie_word_embeddings") else vocab * d
    return {
        "per_layer": attention + mlp + norms,
        "embedding": embedding,
        "head": head,
        "total": layers * (attention + mlp + norms) + embedding + head + d,
        # What a matrix multiplication touches every token: the embedding
        # table is a gather, the head (tied or not) is a matmul.
        "matmul": layers * (attention + mlp) + vocab * d,
    }


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Forward + backward operations one trained token requires:
    6 * N_matmul + 12 * L * d * s, the PaLM convention (attention scores and
    values counted over the whole sequence, not the causal half: the flash
    kernels skip the masked half, so the attention term, 5.5% of the total
    here, over-counts the work they need by up to a factor of two).
    Recomputation under remat is not counted."""
    counts = parameter_counts(config)
    attention = 12 * config["num_hidden_layers"] * config["hidden_size"] * seq
    return 6.0 * counts["matmul"] + attention


def fp8_codec_bytes(elements: int, block: int = 256) -> Dict[str, int]:
    """Bytes the fp8 block codecs must move for ``elements`` values in blocks
    of ``block`` (ops/quantization.py's BLOCK): quantize reads float32 and
    writes one byte a value plus a float32 scale a block; dequantize the
    reverse."""
    blocks = -(-elements // block)
    padded = blocks * block
    one_way = padded * 4 + padded * 1 + blocks * 4
    return {"quantize": one_way, "dequantize": one_way}
