"""The cell ``granite-4.0-h-micro-1chip.ftddp-seq8k`` (PR 57): its
configuration against the published one, what its architecture file counts, its
two readers on hand-made traces, a rehearsal under its own overlay, the fp8
control of its limits through the harness's own comparison, and a step that
leaves its state where it was. On the CPU; tier-1 collects it.

    JAX_PLATFORMS=cpu python -m pytest tests/chipbench_tests/test_granite_cell.py -q
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

CELL, CONFIG = "granite-4.0-h-micro-1chip.ftddp-seq8k", "granite-4.0-h-micro-1chip"
OVERLAY = ROOT / "chipbench/fixtures/rehearsal-granite.json"
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# The keys of the public config.json, as the model-configs guide's catalog row
# has them.
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 8192,
    "layer_types": PERIOD * 4, "logits_scaling": 8, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
}
CUTS = {"num_hidden_layers": 10, "vocab_size": 25088}
# The nine lists of an ``ftddp`` cell, and the two accepted readers that find
# the one attention layer's flash calls by the name its architecture file states.
LISTED = ("tokens_per_s", "ft_host_ms", "quorum_commit_ms", "mfu_pct", "device_idle_pct",
          "host_stall_ms", "ft_idle_ms", "ft_step_host_ms", "trace_overhead_pct",
          "mixed_attn_time_pct", "mixed_attn_mxu_pct")
OWN = ("ssd_time_pct", "ssd_roofline_pct")


@pytest.fixture(scope="module")
def bench():
    return spec.Benchmark(ROOT)


@pytest.fixture(scope="module")
def config(bench):
    return bench.config(CONFIG)


@pytest.fixture(scope="module")
def architecture(bench, config):
    return bench.architecture(config["model_type"])


def toy_of(config):
    overlay = json.loads(OVERLAY.read_text())
    toy = {**config, **overlay["config"]}
    toy["run"] = {**config["run"], **overlay["run"]}
    return toy, overlay


def test_the_catalogs_row_is_the_one_this_file_holds(config):
    """Where the guide's catalog is installed, its row is what PUBLISHED says."""
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(line) for line in catalog.read_text().splitlines() if line.strip()]
    (row,) = [r for r in rows if r["name"] == "granite-4.0-h-micro"]
    assert row["config"] == PUBLISHED
    assert row["source_url"] == config["source"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_a_published_key_is_in_the_file_unchanged_or_is_a_listed_cut(key, bench, config):
    entry = next(c for c in bench.data["configs"] if c["name"] == CONFIG)
    if key in CUTS:
        assert key in entry["reduced"] and key in config["reduced"]
        assert config[key] == CUTS[key] and config["published"][key] == PUBLISHED[key]
    else:
        assert config[key] == PUBLISHED[key] and key not in entry["reduced"]


def test_the_file_says_what_it_assumed_and_where_it_departs(config):
    assert config["source"].endswith("ibm-granite/granite-4.0-h-micro/blob/main/config.json")
    said = " ".join(config["assumed"])
    for word in ("catalog", "in-projection", "A_log", "dt_bias", "head_dim", "initializer",
                 "NO scale", "TIED", "21.3", "AdamW"):
        assert word in said, word
    # Nine of ten layers are Mamba-2, and the file's depth is one period.
    assert config["layer_types"][: config["num_hidden_layers"]] == PERIOD
    assert config["run"]["remat"] == "dots" and config["run"]["scan_layers"] is True
    assert config["run"]["attention_impl"] == "auto" and config["run"]["loss_vocab_chunk"] == 4096
    assert config["run"]["dtype"] == config["run"]["norm_dtype"] == "bfloat16"
    assert not [key for key in config["run"] if key.endswith("_init_scale")]  # the model's own
    optimizer = config["optimizer"]
    assert (optimizer["learning_rate"], optimizer["weight_decay"]) == (3e-4, 0.1)
    assert len(config["departures"]) == 2 and "do not train" in config["departures"][0]
    for word in ("A_log", "dt_bias", "run.dtype"):
        assert word in config["departures"][0], word
    # No cut names a width, the layout stays whole, and a quarter of the rows.
    assert not [key for key in config["reduced"] if spec.is_width(key)]
    assert len(config["layer_types"]) == 40 and config["vocab_size"] * 4 == 100352
    assert "4 stages x 4 chips" in config["stands_for"]
    assert config["layout"] == {"chips": 1, "groups": 1, "mesh": {"fsdp": 1}}
    tolerance = config["reference_tolerance"]
    # The first loss at the other cells' limit; the second between ITS two
    # readings (the sound runs' 2.13e-4 at most on 22 seeds, the fp8 control's
    # 2.5e-3), and the update still 4 limits away.
    assert tolerance["relative"] == 2**-14 and tolerance["update_relative"] == 2**-10.5
    assert 3 * 2.13e-4 < tolerance["update_relative"] < 2.52e-3 / 3
    assert 4 * tolerance["update_relative"] < 1.04e-2
    # ... and the file says what the sound runs' reading follows, and why the stream is float32.
    for word in ("fp8", "seeds", "2^31", "geometric middle", "flash FORWARD call", "blockwise path 0.99992",
                 "2.65 times inside 2^-14"):
        assert word in tolerance["why"], word


def test_the_entries_are_the_ones_the_issue_names(bench):
    """Membership, not places: a later PR appends after these."""
    (entry,) = [c for c in bench.data["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size", "adam_mu_dtype", "manager_timeout_s"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["source"] == bench.config(CONFIG)["source"]
    (cell,) = [w for w in bench.data["workloads"] if w["config"] == CONFIG]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (CELL, "ftddp-seq8k", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200 and "path-bound" in cell["why"]
    traffic = bench.traffic("ftddp-seq8k")
    assert (traffic["job"], traffic["batch"], traffic["seq"]) == ("ftddp", 1, 8192)
    assert traffic["seq"] % bench.config(CONFIG)["mamba_chunk_size"] == 0
    by_name = {m["name"]: m for m in bench.data["end_to_end"] + bench.data["per_layer"]}
    for name in LISTED:
        assert CELL in by_name[name]["workloads"], name
    # A metric outside these lists may list the cell only if it names its
    # cells by job (PR 59's four list every ``ftddp`` cell): then all of them.
    by_job = {w["name"] for w in bench.data["workloads"] if bench.traffic(w["traffic"])["job"] == "ftddp"}
    for name, metric in by_name.items():
        if name not in LISTED + OWN and CELL in metric.get("workloads", ()):
            assert by_job <= set(metric["workloads"]), name
    names = [m["name"] for m in bench.data["per_layer"]]
    assert [n for n in names if n in OWN] == list(OWN)
    for name in OWN:
        metric = by_name[name]
        assert metric["workloads"] == [CELL] and metric["unit"] == "%"
        assert (metric["source"], metric["layer"], metric["moves"]) == ("device_trace", "kernels", "tokens_per_s")
    assert by_name["ssd_time_pct"]["better"] == "lower"
    assert by_name["ssd_roofline_pct"]["better"] == "higher"
    assert spec.problems(bench) == []


def test_not_every_layer_runs_flash_attention_and_the_file_does_not_say_so(bench):
    text = spec.architecture_text(bench, CELL)
    assert "FLASH_ATTENTION_IN_EVERY_LAYER" not in text
    for name in ("flash_time_pct", "flash_mxu_pct"):
        (metric,) = [m for m in bench.data["per_layer"] if m["name"] == name]
        assert CELL not in metric["workloads"]


def test_the_readers_spell_no_other_architectures_names(bench):
    for name in OWN:
        text = bench.reader_path("per_layer", name).read_text()
        for word in ("mamba_", "in_proj", "granite", "llama"):
            assert word not in text, (name, word)


def test_the_reference_shares_nothing_with_the_programs_scan(bench):
    text = spec.architecture_text(bench, CELL)
    assert "ops.ssd" not in text and "ops import ssd" not in text and "ssd_scan" not in text
    imports = [line.strip() for line in text.splitlines() if "import" in line and "torchft_tpu" in line]
    assert imports == ["from torchft_tpu.models.granite import Granite, GraniteConfig"]  # in build


def test_what_is_counted(architecture, config):
    counts = architecture.parameter_counts(config)
    assert counts["mamba"] == 2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048 == 25_847_232
    assert counts["attention"] == 2 * 2048 * 2048 + 2 * 2048 * 512 == 10_485_760
    assert counts["unit"] == 3 * 2048 * 8192 == 50_331_648
    assert counts["mamba_layer"] == 76_182_976 and counts["attention_layer"] == 60_821_504
    assert counts["embedding"] == 25088 * 2048 == 51_380_224
    assert counts["total"] == 9 * 76_182_976 + 60_821_504 + 51_380_224 + 2048 == 797_850_560
    # The matrices a token is multiplied by; the tied matrix once, as the head.
    matrices = 9 * (2048 * 8512 + 4096 * 2048 + 50_331_648) + 10_485_760 + 50_331_648 + 51_380_224
    assert counts["matmul"] == matrices == 797_573_120
    forward = architecture.ssd_forward_flops_per_token(config)
    assert forward == 257 * 128 + 64 * (257 * 64 + 4 * 64 * 128) == 3_182_720
    flops = architecture.train_flops_per_token(config, 8192)
    assert flops == 6 * 797_573_120 + 3 * 3_182_720 * 9 + 12 * 2048 * 8192
    assert [round(part / 1e9, 2) for part in (6 * 797_573_120, 27 * 3_182_720, 12 * 2048 * 8192)] == [4.79, 0.09, 0.20]
    assert architecture.ssd_flops(config, 1, 8192) == 3.0 * 3_182_720 * 9 * 8192
    assert architecture.ssd_flops(config, 2, 8192) == 2 * architecture.ssd_flops(config, 1, 8192)
    # Bytes a token and layer in bfloat16, what one kernel a direction must move:
    # xBC and dt read (forward, and again backward), y written, y's gradient
    # read, the gradients of xBC and dt written; nothing from convolution to scan.
    read = 2 * (4352 + 64)
    assert architecture.ssd_bytes(config, 1, 8192) == (3 * read + 2 * 2 * 4096) * 9 * 8192 == 42_880 * 9 * 8192
    # Bytes bind by a little: 3.86 ms a step at 819 GB/s against 3.57 ms of operations at 197 TFLOP/s.
    seconds = (architecture.ssd_bytes(config, 1, 8192) / 819e9, architecture.ssd_flops(config, 1, 8192) / 197e12)
    assert seconds[0] > seconds[1] and [round(1e3 * x, 2) for x in seconds] == [3.86, 3.57]
    # The one attention layer: 7 matmuls x 2 x 64 x 32 over the causal pairs.
    assert architecture.mixed_attention_flops(config, 1, 8192) == 14 * 2048 * (8192 * 8193 / 2)
    assert architecture.mixed_attention_flops(config, 2, 4096) == 2 * 14 * 2048 * (4096 * 4097 / 2)


# A step program's scan as the change's chipless compile draws it (XLA's
# result shapes, PR 57): the convolution's fusions, the scores and decay
# matrices with the batch, chunks and heads folded, the chunk states, x and y
# by chunk; and the projections, the unit and the loss, which are not the scan's.
OPS = [
    ["fusion.11 bf16[1,8192,4352]", 0.20], ["fusion.12 f32[1,8195,4352]", 0.10],
    ["fusion.13 f32[32,256,256]", 0.05], ["fusion.14 bf16[32,64,256,256]", 0.60],
    ["fusion.15 f32[1,32,256,1,64,64]", 0.30], ["fusion.16 f32[32,64,64,128]", 0.15],
    ["fusion.17 bf16[64,64,128,32]", 0.05], ["fusion.18 f32[32,256,64]", 0.05],
    ["fusion.19 f32[32,32,64,1]", 0.01], ["fusion.20 bf16[32,256,128]", 0.04],
    ["fusion.21 bf16[2048,256,64]", 0.05],
    # not the scan's: in-projection, gated norm / out-projection input, unit, stream, loss
    ["fusion.30 bf16[8192,8512]", 1.00], ["fusion.31 bf16[1,8192,4096]", 0.40],
    ["fusion.32 bf16[8192,16384]", 2.00], ["fusion.33 bf16[1,8192,2048]", 0.30],
    ["fusion.34 f32[8192,4096]", 0.50], ["fusion.35 f32[2048,28672]", 0.20],
    ["fusion.36 f32[1,8192,64]", 0.01], ["tpuft__nope_attention.3 bf16[1,32,8192,64]", 0.30],
]
SCAN_SECONDS = 0.20 + 0.10 + 0.05 + 0.60 + 0.30 + 0.15 + 0.05 + 0.05 + 0.01 + 0.04 + 0.05
PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}


def obs_of(config, **more):
    return {"config": config, "batch": 1, "seq": 8192, "steps": 5, "peaks": PEAK, **more}


def test_the_readers_find_the_scans_ops_by_shape_and_a_kernel_by_name(bench, config, architecture):
    trace = {"busy_s": 8.0, "kernels": {}, "ops": OPS}
    read = lambda name, obs: bench.reader("per_layer", name).read(obs)
    obs = obs_of(config, trace=trace)
    assert architecture.ssd_seconds(trace, config, 1, 8192) == pytest.approx(SCAN_SECONDS)
    assert read("ssd_time_pct", obs) == pytest.approx(100 * SCAN_SECONDS / 8.0)
    least = max(architecture.ssd_flops(config, 1, 8192) / 197e12, architecture.ssd_bytes(config, 1, 8192) / 819e9)
    assert least == architecture.ssd_bytes(config, 1, 8192) / 819e9  # bytes bind
    assert read("ssd_roofline_pct", obs) == pytest.approx(100 * 5 * least / SCAN_SECONDS)
    assert read("ssd_roofline_pct", obs) < 100
    # A Mosaic call in the path's place is found by its name, whatever its shape.
    kernel = ["ssd_chunk_scan.7 bf16[1,8192,4096]", 0.25]
    with_kernel = {"busy_s": 8.0, "ops": OPS + [kernel], "kernels": {"jit__fused": [kernel]}}
    assert architecture.ssd_seconds(with_kernel, config, 1, 8192) == pytest.approx(SCAN_SECONDS + 0.25)
    # Another batch and sequence are other shapes: two sequences of 4096 fold otherwise.
    folded = {"ops": [["fusion.1 bf16[2,16,1,64,256,256]", 1.0], ["fusion.2 bf16[8192,4352]", 0.5],
                      ["fusion.3 bf16[2,4096,4096]", 2.0], ["fusion.4 bf16[32,64,256,256]", 4.0]]}
    assert architecture.ssd_seconds(folded, config, 2, 4096) == pytest.approx(1.0 + 0.5 + 4.0)
    assert architecture.ssd_seconds(folded, config, 1, 4096) == 0.0


def test_the_accepted_attention_readers_find_the_one_flash_layer_by_name(bench, config, architecture):
    """``mixed_attn_time_pct`` / ``mixed_attn_mxu_pct`` (PR 54's readers, unedited) ask the
    architecture file for ``ATTENTION_KERNEL`` and ``mixed_attention_flops``: the layer's two
    Mosaic calls carry the model's scope, and no op of the scan does."""
    calls = [["tpuft__nope_attention.3 bf16[1,32,8192,64]", 0.30], ["tpuft__nope_attention.9 f32[1,32,8192,64]", 0.50]]
    trace = {"busy_s": 8.0, "kernels": {"jit__fused": calls}, "ops": OPS}
    obs = obs_of(config, trace=trace)
    read = lambda name: bench.reader("per_layer", name).read(obs)
    assert read("mixed_attn_time_pct") == pytest.approx(100 * 0.80 / 8.0)
    needed = 5 * architecture.mixed_attention_flops(config, 1, 8192)
    assert read("mixed_attn_mxu_pct") == pytest.approx(100 * needed / 0.80 / 197e12)
    assert not [name for name, _ in OPS if architecture.ATTENTION_KERNEL.search(name) and "nope" not in name]
    # A program without the layer's calls (the scan's ops alone): nothing is read.
    without = obs_of(config, trace={"busy_s": 8.0, "kernels": {}, "ops": OPS})
    assert bench.reader("per_layer", "mixed_attn_time_pct").read(without) is None
    assert bench.reader("per_layer", "mixed_attn_mxu_pct").read(without) is None


@pytest.mark.parametrize("name", OWN)
@pytest.mark.parametrize("case", [
    "no-trace", "a-program-without-such-ops", "no-steps", "another-architecture",
])
def test_a_reader_with_nothing_to_read_returns_nothing(name, case, bench, config):
    """As on the parent commit, which has no such architecture file, and in
    any other cell: the line leaves the metric out and nothing raises."""
    other = {"busy_s": 6.0, "kernels": {"jit__fused": [["attn.17 bf16[1,32,8192,128]", 1.0]]},
             "ops": [["fusion.9 bf16[8192,4096]", 3.0], ["attn.17 bf16[1,32,8192,128]", 1.0]]}
    obs = {
        "no-trace": obs_of(config, trace=None),
        "a-program-without-such-ops": obs_of(config, trace=other),
        "no-steps": obs_of(config, trace={"busy_s": 0.0, "kernels": {}, "ops": []}, steps=0),
        "another-architecture": obs_of(
            bench.config("keye-vl2-30b-a3b-ep8-1chip"), trace={"busy_s": 8.0, "kernels": {}, "ops": OPS}),
    }[case]
    assert bench.reader("per_layer", name).read(obs) is None


def test_a_reader_on_a_checkout_without_the_architecture_file_returns_nothing(bench, config):
    """The parent commit under this PR's benchmark files has the readers and
    the entries but, were the architecture file missing, nothing to ask."""
    obs = obs_of({**config, "model_type": "no-such-architecture"}, trace={"busy_s": 8.0, "ops": OPS})
    for name in OWN:
        assert bench.reader("per_layer", name).read(obs) is None


def test_the_cell_rehearses_under_its_own_overlay(bench):
    """``--trace 2`` is ``--trace 0`` up to the taking of its numbers and then
    the traced tail, so one process rehearses both."""
    done = run_cell(CELL, "--trace", "2", "--rehearse", str(OVERLAY), rehearse=False)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == RESULT_KEYS | {"rehearsal"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert {"tokens_per_s", "peak_hbm_gib", "setup_s"} <= set(line["metrics"])
    assert "reference: first loss" in done.stderr and "reference: second loss" in done.stderr
    # What needs no device plane is on the line; a device_trace metric has no TPU plane on the CPU.
    assert {"ft_host_ms", "quorum_commit_ms", "ft_step_host_ms", "trace_overhead_pct"} <= set(line["metrics"])
    assert not (set(OWN) | {"mixed_attn_time_pct", "mixed_attn_mxu_pct"}) & set(line["metrics"])


def test_the_plain_rehearsal_overlay_would_undo_the_period(bench):
    """Why the cell has an overlay of its own: the shared one sets a depth of 2
    (no whole period) and names another block's keys."""
    shared = json.loads((ROOT / "chipbench/fixtures/rehearsal.json").read_text())["config"]
    assert shared["num_hidden_layers"] == 2
    mine = json.loads(OVERLAY.read_text())
    assert "num_hidden_layers" not in mine["config"]  # the file's own ten
    assert mine["config"]["mamba_n_heads"] * mine["config"]["mamba_d_head"] == 2 * mine["config"]["hidden_size"]
    assert set(mine["traffic"]) == {"ftddp-seq8k"}


@pytest.fixture(scope="module")
def toy_system(bench, config):
    """The cell at the overlay's size, its weights, the reference's three
    losses and the fp8 control's two (``control`` computes both), once for the
    tests below."""
    from chipbench import reference
    from chipbench.model import System

    toy, overlay = toy_of(config)
    traffic = {**bench.traffic("ftddp-seq8k"), **overlay["traffic"]["ftddp-seq8k"]}
    saved = {name: getattr(reference, name) for name in overlay["reference"]}
    for constant, value in overlay["reference"].items():
        setattr(reference, constant, value)
    try:
        system = System(toy, bench.architecture(toy["model_type"]), traffic, 2**31 + 57)
        params = system.init_params()
        control = spec.load_module(ROOT / "scripts/keye_selection_check.py").control(system, params)
        yield system, params, control
    finally:
        for name, value in saved.items():
            setattr(reference, name, value)


def test_the_weights_are_the_models_own_initialisation(toy_system):
    """No scale of the yardstick's is laid over the model's initialisers:
    ``build`` returns the model itself, and its tied matrix starts the stream
    at unit variance after the multiplier."""
    import jax.numpy as jnp

    from torchft_tpu.models.granite import Granite

    system, params, _ = toy_system
    assert isinstance(system.model, Granite)
    table = params["params"]["tok_embed"]["embedding"]
    assert abs(float(jnp.std(table)) * system.config["embedding_multiplier"] - 1.0) < 0.05


def test_the_fp8_control_is_not_correct_by_the_harness_own_comparison(toy_system):
    """The float32 reference with its weights in fp8, through
    ``harness.reference_check`` under the overlay's limits: a problem comes
    back on a loss (and the program itself, on the same seed, gives none)."""
    from chipbench import harness

    system, params, control = toy_system
    assert control["problems"] and all("loss differs" in p for p in control["problems"])
    sound = [float(system.loss_fn(params, system.tokens(0)))]
    assert not [p for p in harness.reference_check(system, sound) if p.startswith("first")]


def test_the_update_probe_reads_the_references_own_descent_at_toy_size(toy_system):
    """``scripts/granite_check.py --update-by-path``: the program's first step
    against the reference's on the NEXT batch, by the attention layer's path.
    Float32 on both sides and no flash kernel on the CPU: every ratio is 1 to
    rounding (on the chip the cell's own step reads 0.992: PERF.md section 7)."""
    system, params, _ = toy_system
    probe = spec.load_module(ROOT / "scripts/granite_check.py").update_by_attention_path
    by_path = probe(system, params)
    assert set(by_path) == {"auto", "blockwise"}
    for leaves in by_path.values():
        assert set(leaves) == {"matrices", "all_leaves"}
        assert all(abs(ratio - 1.0) < 1e-3 for kind in leaves.values() for ratio in kind.values())


def test_a_step_that_returns_its_state_unchanged_is_not_correct(toy_system):
    """The second loss of a program whose update never lands is the loss of
    batch 1 on the seeded weights: the harness's comparison refuses it, by far
    more than the limit, and the reference's update moves that loss by more
    than 4 limits, so the comparison can tell."""
    from chipbench import harness

    system, params, _ = toy_system
    stuck = [float(system.loss_fn(params, system.tokens(i))) for i in (0, 1)]
    problems = harness.reference_check(system, stuck)
    assert len(problems) == 1 and problems[0].startswith("second loss differs")
    moved = abs(system.reference["second"]["0"] - system.reference["second_without_update"])
    assert moved / system.reference["first"] > 4 * system.config["reference_tolerance"]["update_relative"]
