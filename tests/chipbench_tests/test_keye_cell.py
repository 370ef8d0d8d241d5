"""The cell ``keye-vl2-30b-a3b-1chip.ftddp-seq8k`` (PR 46): its configuration
against the published one, what its architecture file counts, its three
readers on hand-made traces, a rehearsal under its own overlay, and the fp8
control of its limits through the harness's own comparison. On the CPU;
tier-1 collects it.

    JAX_PLATFORMS=cpu python -m pytest tests/chipbench_tests/test_keye_cell.py -q
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import spec  # noqa: E402
from test_chipbench import RESULT_KEYS, run_cell  # noqa: E402

CELL, CONFIG = "keye-vl2-30b-a3b-1chip.ftddp-seq8k", "keye-vl2-30b-a3b-ep8-1chip"
OVERLAY = ROOT / "chipbench/fixtures/rehearsal-keye.json"
# The language model's keys of the public config.json, as the model-configs
# guide's catalog row has them.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 262144,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
CUTS = {"num_hidden_layers": 6, "num_local_experts": 16, "vocab_size": 18992}
LISTED = ("tokens_per_s", "ft_host_ms", "quorum_commit_ms", "mfu_pct", "device_idle_pct",
          "host_stall_ms", "ft_idle_ms", "ft_step_host_ms", "trace_overhead_pct")
# ISSUE 46 named a fourth, ``expert_mxu_pct``: left out, because no reader can
# see how many rows arrived at the held experts (PERF.md section 7, PR 46).
# ``sparse_flash_time_pct`` (reader and test since PR 47) was listed by PR 64,
# which also gave ``expert_time_pct`` its second cell: the windowed one's.
OWN = ("expert_time_pct", "sparse_attn_time_pct", "sparse_attn_mxu_pct", "sparse_flash_time_pct")


@pytest.fixture(scope="module")
def bench():
    return spec.Benchmark(ROOT)


@pytest.fixture(scope="module")
def config(bench):
    return bench.config(CONFIG)


@pytest.fixture(scope="module")
def architecture(bench, config):
    return bench.architecture(config["model_type"])


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_a_published_key_is_in_the_file_unchanged_or_is_a_listed_cut(key, bench, config):
    entry = next(c for c in bench.data["configs"] if c["name"] == CONFIG)
    if key in CUTS:
        assert key in entry["reduced"] and key in config["reduced"]
        assert config[key] == CUTS[key] and config["published"][key] == PUBLISHED[key]
    else:
        assert config[key] == PUBLISHED[key] and key not in entry["reduced"]


def test_the_file_says_what_it_assumed_and_where_it_departs(config):
    assert config["source"].endswith("Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json")
    said = " ".join(config["assumed"])
    for word in ("RMSNorm", "LayerNorm", "rotary", "q_chunk_size", "Ties", "catalog"):
        assert word in said, word
    assert len(config["departures"]) == 3
    assert "not trained" in config["departures"][0]
    assert "auxiliary" in config["departures"][1]
    assert "norms do not train" in config["departures"][2] and "run.norm_dtype" in config["departures"][2]
    assert config["run"]["norm_dtype"] == "bfloat16"
    assert config["run"]["indexer_dtype"] == "float32" and config["expert_share"] == 0
    assert config["num_experts"] == 128  # the router's width is not the share


def test_the_entries_are_the_ones_the_issue_names(bench):
    entry = next(c for c in bench.data["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_local_experts", "vocab_size",
                                "adam_mu_dtype", "manager_timeout_s"]
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "ftddp-seq8k", 1)
    by_name = {m["name"]: m for m in bench.data["end_to_end"] + bench.data["per_layer"]}
    for name in LISTED:  # membership, not places: a later PR appends after these
        assert CELL in by_name[name]["workloads"], name
    # A metric outside these lists may list the cell only if it names its
    # cells by job (PR 59's four list every ``ftddp`` cell): then all of them.
    by_job = {w["name"] for w in bench.data["workloads"] if bench.traffic(w["traffic"])["job"] == "ftddp"}
    for name, metric in by_name.items():
        if name not in LISTED + OWN and CELL in metric.get("workloads", ()):
            assert by_job <= set(metric["workloads"]), name
    names = [m["name"] for m in bench.data["per_layer"]]
    assert [n for n in names if n in OWN] == list(OWN)
    assert "expert_mxu_pct" not in by_name
    for name in OWN:
        metric = by_name[name]
        assert metric["workloads"][0] == CELL and metric["unit"] == "%"
        assert metric["workloads"] == [CELL] or name == "expert_time_pct"  # the other routed stack's too
        assert (metric["source"], metric["layer"], metric["moves"]) == ("device_trace", "kernels", "tokens_per_s")


def test_what_is_counted(architecture, config):
    counts = architecture.parameter_counts(config)
    assert counts["per_layer"] == 96_899_456 and counts["total"] == 659_190_016
    assert counts["experts"] == 6 * 16 * 3 * 2048 * 768
    # One expert-equivalent a token on this chip: 8 choices x 16 / 128.
    layer = 18_874_368 + 2_260_992 + 262_144 + 3 * 2048 * 768
    assert counts["matmul"] == 6 * layer + 18992 * 2048
    pairs = architecture.selected_pairs
    assert pairs(8192, 2048) == sum(min(t + 1, 2048) for t in range(8192))
    assert pairs(100, 2048) == 100 * 101 // 2
    flops = architecture.train_flops_per_token(config, 8192)
    assert flops == 6 * counts["matmul"] + 12 * 6 * 4096 * 2048 + 2 * 6 * 16 * 64 * 8193 / 2
    need = architecture.selected_attention_flops(config, 1, 8192)
    assert need == 6 * (14.0 * pairs(8192, 2048) * 128 * 32 + 2.0 * (8192 * 8193 // 2) * 16 * 64)
    # Under the dense causal count of the same heads: the selection removes pairs.
    assert need < 6 * 7.0 * 8192 * 8192 * 128 * 32


KERNELS = {"jit__fused": [["gmm.45 bf16[65536,768]", 0.30], ["tgmm.12 bf16[16,2048,768]", 0.10],
                          ["gmm.52 f32[65536,2048]", 0.20]]}
OPS = [
    ["fusion.1 f32[1,4,8,512,8192]", 0.50], ["fusion.2 u32[1,512,64,128]", 0.25],
    ["fusion.3 f32[512,16,6144]", 0.25], ["fusion.4 bf16[4,4096,512,8]", 0.25],
    ["fusion.5 pred[512,2048]", 0.125], ["fusion.10 f32[4,8,512]", 0.0625], ["fusion.11 s32[512]", 0.03125],
    ["fusion.12 bf16[4,512,8,128]", 0.015625], ["fusion.13 bf16[1,6144,4,128]", 0.015625],
    # Not the tiled path's: a key projection, a weight's gradient, the whole k.
    ["fusion.6 bf16[8192,512]", 9.0], ["fusion.7 f32[2048,512]", 9.0], ["fusion.8 bf16[1,8192,4,128]", 9.0],
    ["fusion.9 f32[65536,2048]", 9.0],
]
PEAK = {"bf16_tflops": 197.0}


def obs_of(config, **more):
    return {"config": config, "batch": 1, "seq": 8192, "steps": 20, "peaks": PEAK, **more}


def test_the_readers_read_the_kernels_and_the_tiled_path(bench, config, architecture):
    trace = {"busy_s": 6.0, "kernels": KERNELS, "ops": OPS}
    read = lambda name, obs: bench.reader("per_layer", name).read(obs)
    obs = obs_of(config, trace=trace)
    assert read("expert_time_pct", obs) == pytest.approx(100 * 0.6 / 6.0)
    assert read("sparse_attn_time_pct", obs) == pytest.approx(100 * 1.5 / 6.0)
    assert read("sparse_attn_mxu_pct", obs) == pytest.approx(
        100 * 20 * architecture.selected_attention_flops(config, 1, 8192) / 1.5 / 197e12)
    assert read("sparse_flash_time_pct", obs) is None  # PR 46's program: no attention kernel


def test_selected_attention_is_the_selection_and_the_kernels_that_attend_under_it(bench, config, architecture):
    """Since PR 64 the two ``sparse_attn_*`` readers sum the selection's XLA ops
    AND the Pallas calls that are not the expert layer's (PR 47's flash calls
    with the selection as an operand): the count ``selected_attention_flops``
    holds attention's seven matmuls, so the seconds must hold attention's
    kernels, or a faster selection alone pushes the share of the peak past 100
    (ledger, PR 63: 49.33 on 18.58% of the busy time). No op is counted twice,
    the expert layer's sums by token are neither attention nor the grouped
    product, and a codec program's kernels are another reader's."""
    kernels = {
        "jit__fused": KERNELS["jit__fused"] + [
            ["attn.21 bf16[1,32,8192,128]", 1.0], ["attn.22 bf16[1,32,8192,128]", 0.5],
            ["sum_by_token.9 f32[8192,2048]", 0.25], ["transpose_jvp_sum_by_token__.9 bf16[8192,2048]", 0.25],
        ],
        "jit_quantize_pseudograd": [["quantize.3 f8e4m3fn[1048576,256]", 9.0]],
    }
    read = lambda name, obs: bench.reader("per_layer", name).read(obs)
    obs = obs_of(config, trace={"busy_s": 6.0, "kernels": kernels, "ops": OPS})
    assert architecture.selected_attention_seconds(obs["trace"], config, 8192) == pytest.approx(1.5 + 1.5)
    assert read("sparse_flash_time_pct", obs) == pytest.approx(100 * 1.5 / 6.0)
    assert read("sparse_attn_time_pct", obs) == pytest.approx(100 * 3.0 / 6.0)
    assert read("sparse_attn_mxu_pct", obs) == pytest.approx(
        100 * 20 * architecture.selected_attention_flops(config, 1, 8192) / 3.0 / 197e12)
    assert read("expert_time_pct", obs) == pytest.approx(100 * 0.6 / 6.0)  # as before: the product's calls
    # A selection made by a kernel one day stays in sight, and a selection ten
    # times faster cannot pass the peak while attention's kernels are counted.
    faster = [[name, s / 10] for name, s in OPS]
    obs = obs_of(config, trace={"busy_s": 6.0, "kernels": kernels, "ops": faster})
    assert read("sparse_attn_time_pct", obs) == pytest.approx(100 * (0.15 + 1.5) / 6.0)
    names = [name for rows in kernels.values() for name, _ in rows]
    assert not [n for n in names if architecture.EXPERT_KERNEL.search(n) and not architecture.EXPERT_LAYER_KERNEL.search(n)]


@pytest.mark.parametrize("name", OWN)
@pytest.mark.parametrize("case", ["no-trace", "a-program-without-the-path", "no-steps"])
def test_a_reader_with_nothing_to_read_returns_nothing(name, case, bench, config):
    """As on the parent commit, whose program has neither the kernels nor the
    tiles: the line leaves the metric out and nothing raises."""
    dense = {"busy_s": 6.0, "kernels": {"jit_quantize_pseudograd": [["quantize.3 f8e4m3fn[1048576,256]", 1.0]]},
             "ops": [["fusion.9 bf16[8192,4096]", 3.0]]}
    obs = {
        "no-trace": obs_of(config, trace=None),
        "a-program-without-the-path": obs_of(config, trace=dense),
        "no-steps": obs_of(config, trace={"busy_s": 0.0, "kernels": {}, "ops": []}, steps=0),
    }[case]
    assert bench.reader("per_layer", name).read(obs) is None


@pytest.mark.parametrize("trace", ["0", "2"])
def test_the_cell_rehearses_under_its_own_overlay(trace, bench):
    done = run_cell(CELL, "--trace", trace, "--rehearse", str(OVERLAY), rehearse=False)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == RESULT_KEYS | {"rehearsal"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert {"tokens_per_s", "peak_hbm_gib", "setup_s"} <= set(line["metrics"])
    assert "reference: first loss" in done.stderr and "reference: second loss" in done.stderr
    if trace == "2":  # what needs no device plane is on the line
        assert {"ft_host_ms", "quorum_commit_ms", "ft_step_host_ms", "trace_overhead_pct"} <= set(line["metrics"])
        assert not set(OWN) & set(line["metrics"])  # device_trace: no TPU plane on the CPU


@pytest.fixture(scope="module")
def check_script():
    return spec.load_module(ROOT / "scripts/keye_selection_check.py")


@pytest.mark.parametrize("seed", [7, 46001, 2**31 + 5])
def test_the_fp8_control_is_not_correct_by_the_harness_own_comparison(seed, bench, config, check_script, monkeypatch):
    """The float32 reference with its weights in fp8, through
    ``harness.reference_check`` under the overlay's limits: a problem comes
    back (and the program itself, on the same seed, gives none)."""
    from chipbench import harness, reference
    from chipbench.model import System

    overlay = json.loads(OVERLAY.read_text())
    toy = {**config, **overlay["config"]}
    toy["run"] = {**config["run"], **overlay["run"]}
    traffic = {**bench.traffic("ftddp-seq8k"), **overlay["traffic"]["ftddp-seq8k"]}
    for constant, value in overlay["reference"].items():
        monkeypatch.setattr(reference, constant, value)
    system = System(toy, bench.architecture(toy["model_type"]), traffic, seed)
    params = system.init_params()
    control = check_script.control(system, params)
    assert control["problems"] and all("loss differs" in p for p in control["problems"])
    sound = [float(system.loss_fn(params, system.tokens(0)))]
    assert not [p for p in harness.reference_check(system, sound) if p.startswith("first")]
