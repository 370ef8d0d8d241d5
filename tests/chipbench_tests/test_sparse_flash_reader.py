"""The reader ``sparse_flash_time_pct`` (PR 47) on hand-made traces: the share
of busy time in the flash kernels that attend under the key selection, in a
cell whose other Pallas calls are the expert layer's (the grouped product's,
and since PR 51 the sums by token). PR 64 listed it in BENCHMARK.json, for the
Keye cell alone; the entry is held to what PR 47 wrote down here. On the CPU;
tier-1 collects it.

    JAX_PLATFORMS=cpu python -m pytest tests/chipbench_tests/test_sparse_flash_reader.py -q
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import spec  # noqa: E402

CELL = "keye-vl2-30b-a3b-1chip.ftddp-seq8k"
NAME = "sparse_flash_time_pct"
ENTRY = {
    "name": NAME, "unit": "%", "better": "lower", "source": "device_trace",
    "layer": "kernels", "moves": "tokens_per_s", "workloads": [CELL],
}
# A step program as the change traces it (my chip run, PR 47: XLA's names),
# and a codec program's kernel, which is another reader's.
KERNELS = {
    "jit__fused": [
        ["attn.31 bf16[1,32,8192,128]", 0.75], ["attn.32 bf16[1,32,8192,128]", 0.45],
        ["gmm.42 bf16[65536,768]", 0.30], ["tgmm.13 bf16[16,2048,768]", 0.10],
    ],
    "jit_quantize_pseudograd": [["quantize.3 f8e4m3fn[1048576,256]", 9.0]],
}


@pytest.fixture(scope="module")
def bench():
    return spec.Benchmark(ROOT)


@pytest.fixture(scope="module")
def read(bench):
    return bench.reader("per_layer", NAME).read


def obs_of(bench, config: str, trace):
    return {"config": bench.config(config), "batch": 1, "seq": 8192, "steps": 14, "trace": trace}


def test_the_share_is_the_calls_the_expert_pattern_does_not_name(bench, read):
    obs = obs_of(bench, "keye-vl2-30b-a3b-ep8-1chip", {"busy_s": 6.0, "kernels": KERNELS, "ops": []})
    assert read(obs) == pytest.approx(100 * (0.75 + 0.45) / 6.0)
    expert = bench.reader("per_layer", "expert_time_pct").read(obs)
    assert expert == pytest.approx(100 * (0.30 + 0.10) / 6.0)  # the two share no call


@pytest.mark.parametrize("case", [
    "no-trace", "the-parent-whose-attention-is-plain-xla", "no-busy-time",
    "an-architecture-that-names-no-expert-kernel",
])
def test_with_nothing_to_read_it_returns_nothing(case, bench, read):
    """As on the parent commit, whose step program's only Pallas calls are the
    expert product's: the line leaves the metric out and nothing raises."""
    keye, parent = "keye-vl2-30b-a3b-ep8-1chip", {"jit__fused": KERNELS["jit__fused"][2:]}
    obs = {
        "no-trace": obs_of(bench, keye, None),
        "the-parent-whose-attention-is-plain-xla": obs_of(
            bench, keye, {"busy_s": 6.0, "kernels": parent, "ops": [["fusion.1906 f32[4,8,512]", 1.0]]}),
        "no-busy-time": obs_of(bench, keye, {"busy_s": 0.0, "kernels": {}, "ops": []}),
        "an-architecture-that-names-no-expert-kernel": obs_of(
            bench, "mistral-7b-v0.3-1chip", {"busy_s": 6.0, "kernels": KERNELS, "ops": []}),
    }[case]
    assert read(obs) is None


def test_the_benchmark_is_sound_and_stays_so_with_the_entry_appended(bench):
    """Appended by PR 64, as PR 47 wrote it down: the Keye cell reports it and
    no other cell does."""
    assert spec.problems(bench) == []
    (entry,) = [m for m in bench.data["per_layer"] if m["name"] == NAME]
    assert entry == ENTRY
    assert NAME in [m["name"] for m in bench.metrics_of(CELL, "per_layer")]
    assert all(
        NAME not in [m["name"] for m in bench.metrics_of(w["name"], "per_layer")]
        for w in bench.data["workloads"] if w["name"] != CELL
    )


def test_the_sums_by_token_are_the_expert_layers_and_not_attention(bench, read):
    """PR 51 made the expert layer's two sums by token Mosaic calls: the Keye
    file names them (``EXPERT_LAYER_KERNEL``) beside the grouped product's, so
    they are not read as attention; ``expert_time_pct`` stays the product's."""
    kernels = {"jit__fused": KERNELS["jit__fused"] + [
        ["sum_by_token.9 f32[8192,2048]", 0.27], ["transpose_jvp_sum_by_token__.9 bf16[8192,2048]", 0.21]]}
    obs = obs_of(bench, "keye-vl2-30b-a3b-ep8-1chip", {"busy_s": 6.0, "kernels": kernels, "ops": []})
    assert read(obs) == pytest.approx(100 * (0.75 + 0.45) / 6.0)
    assert bench.reader("per_layer", "expert_time_pct").read(obs) == pytest.approx(100 * (0.30 + 0.10) / 6.0)
