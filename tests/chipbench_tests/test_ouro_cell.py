"""The cell ``ouro-2.6b-1chip.ftddp-seq8k`` (PR 62): its configuration against
the published one, what its architecture file counts, its reader on hand-made
traces, a rehearsal under its own overlay, and through the harness's own
comparison the fp8 control of its limits, three programs with one thing wrong
(a pass short, no entropy term, uniform exit weights) and a step that leaves
its state where it was. On the CPU; tier-1 collects it.

    JAX_PLATFORMS=cpu python -m pytest tests/chipbench_tests/test_ouro_cell.py -q
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

CELL, CONFIG = "ouro-2.6b-1chip.ftddp-seq8k", "ouro-2.6b-1chip"
OVERLAY = ROOT / "chipbench/fixtures/rehearsal-ouro.json"
# The keys of the public config.json, as the model-configs guide's catalog row
# has them.
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
    "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}
CUTS = {"num_hidden_layers": 8, "vocab_size": 12288}
# The lists of an ``ftddp`` cell (PR 59's four among them), and the two accepted
# readers that find the attention's flash calls by the name the architecture
# file states.
LISTED = ("tokens_per_s", "ft_host_ms", "quorum_commit_ms", "mfu_pct", "device_idle_pct",
          "host_stall_ms", "ft_idle_ms", "ft_step_host_ms", "trace_overhead_pct",
          "ft_dispatch_host_ms", "ft_adopt_host_ms", "ft_dispatch_execute_ms", "ft_host_us_per_buffer",
          "mixed_attn_time_pct", "mixed_attn_mxu_pct")
OWN = ("exit_loss_time_pct",)


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
    (row,) = [r for r in rows if r["name"] == "Ouro-2.6B"]
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


def test_the_file_keeps_the_asked_optimizer_limits_and_cuts(bench, config):
    """Values, not the notes' wording: what a later re-measurement may rewrite
    is prose, what it may not change unseen is here."""
    assert config["source"].endswith("ByteDance/Ouro-2.6B/blob/main/config.json")
    assert config["exit_entropy_beta"] == 0.05 and config["total_ut_steps"] == 4
    assert config["assumed"] and len(config["departures"]) == 1
    run = config["run"]
    assert set(run) == {"dtype", "norm_dtype", "attention_impl", "scan_layers", "remat",
                        "loss_vocab_chunk", "head_init_scale", "note"}  # no key nothing reads
    assert run["remat"] == "dots" and run["scan_layers"] is True
    assert run["attention_impl"] == "auto" and run["loss_vocab_chunk"] == 4096
    assert run["dtype"] == run["norm_dtype"] == "bfloat16"
    # AdamW's three numbers are the other configurations' (the Mistral, Keye and granite files'
    # to the digit), so the first step is the sign step every other cell's comparison sees.
    mine = {key: config["optimizer"][key] for key in ("name", "learning_rate", "weight_decay", "eps")}
    assert mine == {"name": "adamw", "learning_rate": 3e-4, "weight_decay": 0.1, "eps": 1e-8}
    for other in ("mistral-7b-v0.3-1chip", "keye-vl2-30b-a3b-ep8-1chip", "granite-4.0-h-micro-1chip"):
        theirs = bench.config(other)["optimizer"]
        assert {key: theirs[key] for key in mine} == mine, other
    # The first moment is what a production run keeps, and no cut: one bend fewer than the others.
    assert config["adam_mu_dtype"] == "float32" and "adam_mu_dtype" not in config["reduced"]
    # No cut names a width, the layout stays whole, and a quarter of the rows in whole slabs.
    assert sorted(config["reduced"]) == ["manager_timeout_s", "num_hidden_layers", "vocab_size"]
    assert not [key for key in config["reduced"] if spec.is_width(key)]
    assert len(config["layer_types"]) == 48 and config["vocab_size"] * 4 == 49152
    assert config["vocab_size"] % run["loss_vocab_chunk"] == 0
    assert config["layout"] == {"chips": 1, "groups": 1, "mesh": {"fsdp": 1}}
    # The limits: the first as asked, the second no looser than asked.
    tolerance = config["reference_tolerance"]
    assert tolerance["relative"] == 2**-14 and tolerance["update_relative"] <= 2**-12


def test_the_entries_are_the_ones_the_issue_names(bench):
    """Membership, not places: a later PR appends after these."""
    (entry,) = [c for c in bench.data["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size", "manager_timeout_s"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["source"] == bench.config(CONFIG)["source"]
    (cell,) = [w for w in bench.data["workloads"] if w["config"] == CONFIG]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (CELL, "ftddp-seq8k", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200 and "path-bound" in cell["why"]
    traffic = bench.traffic("ftddp-seq8k")
    assert (traffic["job"], traffic["batch"], traffic["seq"]) == ("ftddp", 1, 8192)
    by_name = {m["name"]: m for m in bench.data["end_to_end"] + bench.data["per_layer"]}
    for name in LISTED:
        assert CELL in by_name[name]["workloads"], name
    # A metric outside these lists may list the cell only if it names its
    # cells by job (PR 59's four list every ``ftddp`` cell): then all of them.
    by_job = {w["name"] for w in bench.data["workloads"] if bench.traffic(w["traffic"])["job"] == "ftddp"}
    for name, metric in by_name.items():
        if name not in LISTED + OWN and CELL in metric.get("workloads", ()):
            assert by_job <= set(metric["workloads"]), name
    # ... which are the lists the granite cell is in, but for its scan's two.
    granite = "granite-4.0-h-micro-1chip.ftddp-seq8k"
    assert {n for n, m in by_name.items() if granite in m.get("workloads", ())} - {"ssd_time_pct", "ssd_roofline_pct"} == set(LISTED)
    (metric,) = [by_name[name] for name in OWN]
    assert metric["workloads"] == [CELL] and metric["unit"] == "%" and metric["better"] == "lower"
    assert (metric["source"], metric["layer"], metric["moves"]) == ("device_trace", "kernels", "tokens_per_s")
    assert spec.problems(bench) == []


def test_a_layer_runs_flash_attention_four_times_a_step_and_the_file_does_not_say_once(bench):
    """``flash_mxu_pct`` counts ``num_hidden_layers`` calls a step: it would read
    four times too high here, so the cell is read by the ``mixed_attn_*`` pair."""
    text = spec.architecture_text(bench, CELL)
    assert "FLASH_ATTENTION_IN_EVERY_LAYER = True" not in text
    for name in ("flash_time_pct", "flash_mxu_pct"):
        (metric,) = [m for m in bench.data["per_layer"] if m["name"] == name]
        assert CELL not in metric["workloads"]


def test_the_reader_spells_no_architectures_names_and_the_reference_shares_nothing(bench):
    text = bench.reader_path("per_layer", OWN[0]).read_text()
    for word in ("ouro", "lm_head", "llama", "4096"):
        assert word not in text, word
    said = spec.architecture_text(bench, CELL)
    imports = [line.strip() for line in said.splitlines() if "import" in line and "torchft_tpu" in line]
    assert imports == ["from torchft_tpu.models.ouro import Ouro, OuroConfig"]  # in build
    assert "from torchft_tpu.ops" not in said and "looped_stack" not in said and "lax.scan" not in said


def test_what_is_counted(architecture, config):
    counts = architecture.parameter_counts(config)
    assert counts["per_layer"] == 4 * 2048**2 + 3 * 2048 * 5632 + 4 * 2048 == 51_388_416
    assert counts["layer_matrices"] == 51_380_224
    assert counts["embedding"] == counts["head"] == 12288 * 2048 == 25_165_824
    assert counts["total"] == 8 * 51_388_416 + 2 * 25_165_824 + 2048 + 2049 == 461_443_073
    assert counts["matmul"] == 8 * 51_380_224 + 25_165_824 == 436_207_616
    # ONE copy however often it runs: the count does not know total_ut_steps.
    assert architecture.parameter_counts({**config, "total_ut_steps": 1}) == counts
    flops = architecture.train_flops_per_token(config, 8192)
    assert flops == 4 * (6 * 436_207_616 + 12 * 8 * 2048 * 8192)
    parts = (6 * 4 * 8 * 51_380_224, 6 * 4 * 25_165_824, 12 * 32 * 2048 * 8192)
    assert sum(parts) == flops and [round(part / 1e9, 2) for part in parts] == [9.87, 0.60, 6.44]
    assert round(flops * 8192 / 1e12, 1) == 138.5  # TFLOP a step
    assert round(flops / counts["total"]) == 37  # operations a token and parameter
    # Seven matmuls x 2 x 128 x 16 over the causal pairs, 32 layer passes a step.
    assert architecture.mixed_attention_flops(config, 1, 8192) == 14 * 2048 * (8192 * 8193 / 2) * 32
    assert round(architecture.mixed_attention_flops(config, 1, 8192) / 1e12, 1) == 30.8
    assert architecture.mixed_attention_flops(config, 2, 4096) == 2 * 14 * 2048 * (4096 * 4097 / 2) * 32


# A step program's ops as a device trace names them: the exits' slabs (logits,
# softmax and its gradient, the head's gradient by slab) and the gradient to an
# exit's state, which the fused loss carries in float32; and what is not the
# exits': a layer's projections, the unit, the stream in the run's dtype, the
# head's whole gradient, a norm's sums.
OPS = [
    ["fusion.40 f32[8192,4096]", 0.30], ["fusion.41 f32[8192,4096]", 0.25], ["fusion.42 bf16[8192,4096]", 0.20],
    ["fusion.43 f32[2048,4096]", 0.15], ["fusion.44 f32[1,8192,4096]", 0.10],
    ["convolution_add_fusion.9 f32[8192,2048]", 0.40],
    ["fusion.50 bf16[8192,2048]", 1.00], ["fusion.51 bf16[1,8192,5632]", 2.00],
    ["fusion.53 f32[2048,12288]", 0.20], ["fusion.54 bf16[8,2048,5632]", 0.30], ["fusion.55 f32[8192]", 0.01],
    ["tpuft__ouro_attention.3 bf16[1,16,8192,128]", 0.90],
]
SLAB_SECONDS = 0.30 + 0.25 + 0.20 + 0.15 + 0.10
EXIT_SECONDS = SLAB_SECONDS + 0.40


def obs_of(config, **more):
    return {"config": config, "batch": 1, "seq": 8192, "steps": 5, "peaks": {"bf16_tflops": 197.0, "hbm_gbps": 819.0}, **more}


def test_the_reader_finds_the_exits_slabs_by_shape(bench, config, architecture):
    trace = {"busy_s": 8.0, "kernels": {}, "ops": OPS}
    assert architecture.exit_loss_shapes(config, 1, 8192) == [
        (None, [8192, 4096]), (None, [2048, 4096]), ("f32", [8192, 2048])]
    # A float32 program's stream is float32 too: there the state's shape says nothing.
    wide = {**config, "run": {**config["run"], "dtype": "float32"}}
    assert architecture.exit_loss_seconds(trace, wide, 1, 8192) == pytest.approx(SLAB_SECONDS)
    assert architecture.exit_loss_seconds(trace, config, 1, 8192) == pytest.approx(EXIT_SECONDS)
    read = bench.reader("per_layer", OWN[0]).read
    assert read(obs_of(config, trace=trace)) == pytest.approx(100 * EXIT_SECONDS / 8.0)
    # Another batch and sequence are other slabs: two sequences of 4096 are the same tokens.
    assert architecture.exit_loss_seconds(trace, config, 2, 4096) == pytest.approx(EXIT_SECONDS)
    assert architecture.exit_loss_seconds(trace, config, 1, 4096) == pytest.approx(0.15)  # the head's slab alone


def test_the_accepted_attention_readers_find_the_flash_calls_by_name(bench, config, architecture):
    """``mixed_attn_time_pct`` / ``mixed_attn_mxu_pct`` (PR 54's readers, unedited) ask the
    architecture file for ``ATTENTION_KERNEL`` and ``mixed_attention_flops``: a traced step
    names the layers' two Mosaic calls after the model's scope, whichever pass runs them."""
    calls = [["tpuft__ouro_attention.3 bf16[1,16,8192,128]", 0.90], ["tpuft__ouro_attention.9 f32[1,16,8192,128]", 1.60]]
    trace = {"busy_s": 8.0, "kernels": {"jit__fused": calls}, "ops": OPS}
    obs = obs_of(config, trace=trace)
    read = lambda name: bench.reader("per_layer", name).read(obs)
    assert read("mixed_attn_time_pct") == pytest.approx(100 * 2.50 / 8.0)
    needed = 5 * architecture.mixed_attention_flops(config, 1, 8192)
    assert read("mixed_attn_mxu_pct") == pytest.approx(100 * needed / 2.50 / 197e12)
    assert read("mixed_attn_mxu_pct") < 100
    without = obs_of(config, trace={"busy_s": 8.0, "kernels": {}, "ops": OPS})
    assert bench.reader("per_layer", "mixed_attn_time_pct").read(without) is None


@pytest.mark.parametrize("case", [
    "no-trace", "a-program-without-such-ops", "no-steps", "another-architecture", "no-architecture-file",
])
def test_the_reader_with_nothing_to_read_returns_nothing(case, bench, config):
    """As on the parent commit, which has no such architecture file, and in
    any other cell: the line leaves the metric out and nothing raises."""
    other = {"busy_s": 6.0, "kernels": {"jit__fused": [["attn.17 bf16[1,32,8192,128]", 1.0]]},
             "ops": [["fusion.9 bf16[8192,2048]", 3.0], ["attn.17 bf16[1,32,8192,128]", 1.0]]}
    obs = {
        "no-trace": obs_of(config, trace=None),
        "a-program-without-such-ops": obs_of(config, trace=other),
        "no-steps": obs_of(config, trace={"busy_s": 0.0, "kernels": {}, "ops": []}, steps=0),
        "another-architecture": obs_of(
            bench.config("mistral-7b-v0.3-1chip"), trace={"busy_s": 8.0, "kernels": {}, "ops": OPS}),
        "no-architecture-file": obs_of(
            {**config, "model_type": "no-such-architecture"}, trace={"busy_s": 8.0, "ops": OPS}),
    }[case]
    assert bench.reader("per_layer", OWN[0]).read(obs) is None


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


def test_the_overlay_keeps_the_cells_passes(bench, config):
    shared = json.loads((ROOT / "chipbench/fixtures/rehearsal.json").read_text())["config"]
    assert "total_ut_steps" not in shared and shared["vocab_size"] % 128 == 0
    mine = json.loads(OVERLAY.read_text())
    assert mine["config"]["num_hidden_layers"] == 2 and "total_ut_steps" not in mine["config"]  # the file's four
    assert mine["config"]["num_attention_heads"] * mine["config"]["head_dim"] == mine["config"]["hidden_size"]
    assert mine["config"]["vocab_size"] % mine["run"]["loss_vocab_chunk"]  # a padded tail slab
    assert set(mine["traffic"]) == {"ftddp-seq8k"}


@pytest.fixture(scope="module")
def toy_system(bench, config):
    """The cell at the overlay's size, its weights, and one run of
    scripts/ouro_check.py's comparisons on them (the reference's three losses,
    the program's own two, the three broken programs' and the fp8 control's),
    once for the tests below."""
    import jax

    from chipbench import harness, reference
    from chipbench.model import System

    toy, overlay = toy_of(config)
    traffic = {**bench.traffic("ftddp-seq8k"), **overlay["traffic"]["ftddp-seq8k"]}
    saved = {name: getattr(reference, name) for name in overlay["reference"]}
    for constant, value in overlay["reference"].items():
        setattr(reference, constant, value)
    try:
        system = System(toy, bench.architecture(toy["model_type"]), traffic, 2**31 + 62)
        params = system.init_params()
        check = spec.load_module(ROOT / "scripts/ouro_check.py")
        system.reference = harness.reference_losses(system, params)
        found = {"program": check.differences(system, check.first_two_losses(system, params, system.loss_fn))}
        for name, loss_fn in check.broken_programs(system).items():
            found[name] = check.differences(system, check.first_two_losses(system, params, loss_fn))
        found["grad_sum"] = check.grad_sum_by_dtype(system, params)
        found["fp8"] = spec.load_module(ROOT / "scripts/keye_selection_check.py").control(system, params)
        found["stuck"] = [float(jax.jit(system.loss_fn)(params, system.tokens(i))) for i in (0, 1)]
        yield system, params, found
    finally:
        for name, value in saved.items():
            setattr(reference, name, value)


def test_the_program_is_correct_and_the_weights_are_the_models_own(toy_system):
    import jax.numpy as jnp

    from torchft_tpu.models.ouro import Ouro

    system, params, found = toy_system
    assert isinstance(system.model.bind({}), Ouro) and system.model.config.loops == 4
    assert found["program"]["problems"] == []
    assert found["program"]["first"] < 1e-6 and found["program"]["second"] < 1e-5
    tree = params["params"]
    assert abs(float(jnp.std(tree["tok_embed"]["embedding"])) * 64**0.5 - 1.0) < 0.1
    assert abs(float(jnp.std(tree["exit_gate"]["kernel"])) * 64**0.5 - 1.0) < 0.3  # lecun-normal
    assert float(tree["exit_gate"]["bias"][0]) == 0.0
    assert sorted(tree["layers"]["block"]) == ["attn", "attn_norm", "attn_post_norm", "mlp", "mlp_norm", "mlp_post_norm"]


@pytest.mark.parametrize("fault", ["three_passes", "no_entropy_term", "uniform_exit_weights"])
def test_a_program_with_one_thing_wrong_is_not_correct(fault, toy_system):
    """The stack run three times, the loss without its entropy term, the exits
    weighed uniformly: each is refused by the harness's own comparison, on the
    first loss already and by a hundred limits or more."""
    system, _, found = toy_system
    got = found[fault]
    assert any(p.startswith("first loss differs") for p in got["problems"]), got
    assert got["first"] > 100 * system.config["reference_tolerance"]["relative"], got


def test_the_gradients_sum_in_either_dtype_is_the_same_program_in_float32(toy_system):
    """At toy size the weights are float32, so widening them is no change: the
    probe's two sides read the same and both are correct."""
    _, _, found = toy_system
    narrow, wide = found["grad_sum"]["bfloat16"], found["grad_sum"]["float32"]
    assert narrow["problems"] == wide["problems"] == []
    assert narrow["second"] == pytest.approx(wide["second"], abs=1e-6)


def test_the_fp8_control_is_not_correct_by_the_harness_own_comparison(toy_system):
    _, _, found = toy_system
    control = found["fp8"]
    assert control["problems"] and all("loss differs" in p for p in control["problems"])
    assert any(p.startswith("first") for p in control["problems"])


def test_a_step_that_returns_its_state_unchanged_is_not_correct(toy_system):
    """The second loss of a program whose update never lands is the loss of
    batch 1 on the seeded weights: the harness's comparison refuses it, and the
    reference's update moves that loss by more than 4 limits, so it can tell."""
    from chipbench import harness

    system, _, found = toy_system
    problems = harness.reference_check(system, found["stuck"])
    assert len(problems) == 1 and problems[0].startswith("second loss differs")
    moved = abs(system.reference["second"]["0"] - system.reference["second_without_update"])
    assert moved / system.reference["first"] > 4 * system.config["reference_tolerance"]["update_relative"]
