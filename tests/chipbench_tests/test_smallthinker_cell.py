"""The cell ``smallthinker-21b-a3b-1chip.ftddp-seq16k`` (PR 54): its
configuration against the published one, what its architecture file counts,
its three readers on hand-made traces, a rehearsal under its own overlay, and
the fp8 control of its limits through the harness's own comparison. On the CPU;
tier-1 collects it.

    JAX_PLATFORMS=cpu python -m pytest tests/chipbench_tests/test_smallthinker_cell.py -q
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

CELL, CONFIG = "smallthinker-21b-a3b-1chip.ftddp-seq16k", "smallthinker-21b-a3b-ep8-1chip"
OVERLAY = ROOT / "chipbench/fixtures/rehearsal-smallthinker.json"
LAYOUT = [0, 1, 1, 1] * 13
# The keys of the public config.json, as the model-configs guide's catalog row
# has them.
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_layout": LAYOUT, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936,
}
CUTS = {"num_hidden_layers": 8, "moe_num_primary_experts": 8, "vocab_size": 18992}
# ``expert_time_pct``: the routed layer's kernels, listed for this cell by PR 64.
LISTED = ("tokens_per_s", "ft_host_ms", "quorum_commit_ms", "mfu_pct", "device_idle_pct",
          "host_stall_ms", "ft_idle_ms", "ft_step_host_ms", "trace_overhead_pct", "expert_time_pct")
OWN = ("mixed_attn_time_pct", "mixed_attn_mxu_pct", "window_attn_mxu_pct")


@pytest.fixture(scope="module")
def bench():
    return spec.Benchmark(ROOT)


@pytest.fixture(scope="module")
def config(bench):
    return bench.config(CONFIG)


@pytest.fixture(scope="module")
def architecture(bench, config):
    return bench.architecture(config["model_type"])


def test_the_catalogs_row_is_the_one_this_file_holds():
    """Where the guide's catalog is installed, its row is what PUBLISHED says."""
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(line) for line in catalog.read_text().splitlines() if line.strip()]
    (row,) = [r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct"]
    assert row["config"] == PUBLISHED
    assert row["source_url"] == spec.Benchmark(ROOT).config(CONFIG)["source"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_a_published_key_is_in_the_file_unchanged_or_is_a_listed_cut(key, bench, config):
    entry = next(c for c in bench.data["configs"] if c["name"] == CONFIG)
    if key in CUTS:
        assert key in entry["reduced"] and key in config["reduced"]
        assert config[key] == CUTS[key] and config["published"][key] == PUBLISHED[key]
    else:
        assert config[key] == PUBLISHED[key] and key not in entry["reduced"]


def test_the_file_says_what_it_assumed_and_where_it_departs(config):
    assert config["source"].endswith("PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json")
    said = " ".join(config["assumed"])
    for word in ("catalog", "model_type", "input_layernorm", "ReGLU", "per-head", "4096 keys",
                 "secondary", "initializer"):
        assert word in said, word
    assert config["model_type"] == "smallthinker" and config["expert_share"] == 0
    # The router's width is not the share: 64 outputs, 6 a token, 8 held.
    assert config["router_width"] == 64 and config["moe_num_active_primary_experts"] == 6
    assert "router_width" in config["about"]
    assert config["run"]["remat"] == "dots" and config["run"]["scan_layers"] is True
    assert config["run"]["attention_impl"] == "auto" and config["run"]["loss_vocab_chunk"] == 4096
    # Chosen for the yardstick, and said so: the two initial scales (the
    # second comparison's movement; the routing the window runs on) and
    # Adam's eps beside the other configurations' learning rate.
    assert config["run"]["head_init_scale"] == 3.0 and "head_init_scale" in said
    assert config["run"]["embedding_init_scale"] == 32.0 and "embedding_init_scale" in said
    optimizer = config["optimizer"]
    assert (optimizer["learning_rate"], optimizer["eps"]) == (3e-4, 1e-5)
    assert "1e-5" in optimizer["note"] and "routing" in optimizer["note"]
    assert len(config["departures"]) == 3 and "norms do not train" in config["departures"][0]
    # No cut names a width, and the two layouts stay whole.
    assert not [key for key in config["reduced"] if spec.is_width(key)]
    assert len(config["sliding_window_layout"]) == len(config["rope_layout"]) == 52
    tolerance = config["reference_tolerance"]
    assert 0 < tolerance["update_relative"] <= tolerance["relative"] <= 2**-12
    for word in ("fp8", "seeds", "2^31"):
        assert word in tolerance["why"], word


def test_the_entries_are_the_ones_the_issue_names(bench):
    (entry,) = [c for c in bench.data["configs"] if c["name"] == CONFIG]  # membership, not the last place
    assert entry["reduced"] == ["num_hidden_layers", "moe_num_primary_experts", "vocab_size",
                                "adam_mu_dtype", "manager_timeout_s"]
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "ftddp-seq16k", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    traffic = bench.traffic("ftddp-seq16k")
    assert (traffic["job"], traffic["batch"], traffic["seq"]) == ("ftddp", 1, 16384)
    assert traffic["seq"] == PUBLISHED["max_position_embeddings"]
    assert (traffic["warmup_units"], traffic["steps_in_flight"], traffic["trace_seconds"]) == (3, 2, 8)
    base = bench.traffic("ftddp-seq8k")
    assert {k: v for k, v in traffic.items() if k not in ("seq", "trace_seconds", "about")} == {
        k: v for k, v in base.items() if k not in ("seq", "trace_seconds", "about")
    }
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
        # This cell brought them, so it is first; later stacks of mixed layers joined the two ``mixed_attn_*``.
        assert metric["workloads"][0] == CELL and metric["unit"] == "%"
        assert (metric["source"], metric["layer"], metric["moves"]) == ("device_trace", "kernels", "tokens_per_s")
    assert by_name["window_attn_mxu_pct"]["workloads"] == [CELL]
    assert by_name["mixed_attn_time_pct"]["better"] == "lower"
    assert by_name["mixed_attn_mxu_pct"]["better"] == by_name["window_attn_mxu_pct"]["better"] == "higher"
    assert spec.problems(bench) == []


def test_the_readers_spell_no_other_architectures_names(bench):
    for name in OWN:
        text = bench.reader_path("per_layer", name).read_text()
        for word in ("intermediate_size", "w_gate", "llama"):
            assert word not in text, (name, word)


def test_what_is_counted(architecture, config):
    counts = architecture.parameter_counts(config)
    assert counts["attention"] == 20_971_520
    assert counts["per_layer"] == 20_971_520 + 163_840 + 8 * 5_898_240 + 2 * 2560 == 68_326_400
    assert counts["experts"] == 8 * 8 * 3 * 2560 * 768
    assert counts["embedding"] == counts["head"] == 48_619_520
    assert counts["total"] == 8 * 68_326_400 + 2 * 48_619_520 + 2560 == 643_852_800
    # 0.75 of a held expert a token on this chip: 6 choices x 8 / 64.
    layer = 20_971_520 + 163_840 + 0.75 * 5_898_240
    assert counts["matmul"] == 8 * layer + 48_619_520 == 253_091_840
    pairs = architecture.attention_pairs
    assert pairs(16384) == 16384 * 16385 // 2 == 134_225_920
    assert pairs(16384, 4096) == sum(min(t + 1, 4096) for t in range(16384)) == 58_722_304
    assert pairs(100, 4096) == pairs(100) == 5050
    flops = architecture.train_flops_per_token(config, 16384)
    assert flops == 6 * 253_091_840 + 12 * 3584 * (2 * 16384 + 6 * 4096)
    assert round(6 * 253_091_840 / 1e9, 2) == 1.52 and round((flops - 6 * 253_091_840) / 1e9, 2) == 2.47
    # Were the window only a mask: 12 x 3584 x 8 x 16384 = 5.64 GFLOP a token.
    assert round(12 * 3584 * 8 * 16384 / 1e9, 2) == 5.64
    # At 1 x 8192 the window removes a quarter of a windowed layer's pairs.
    assert round(1 - pairs(8192, 4096) / pairs(8192), 2) == 0.25
    mixed = architecture.mixed_attention_flops(config, 1, 16384)
    windowed = architecture.window_attention_flops(config, 1, 16384)
    assert windowed == 6 * 14.0 * 58_722_304 * 128 * 28
    assert mixed == windowed + 2 * 14.0 * 134_225_920 * 128 * 28
    assert architecture.mixed_attention_flops(config, 2, 16384) == 2 * mixed


# A step program as the change traces it (my chip run, PR 54: XLA's names): the
# full layers' two calls under their scope's name, the windowed layers' under
# their own, and the routed layer's, which are not attention.
KERNELS = {"jit__fused": [
    ["tpuft__full_attention.21 bf16[1,28,16384,128]", 0.50], ["tpuft__full_attention.20 bf16[1,28,16384,128]", 1.00],
    ["window_attn_fwd.27 bf16[1,28,16384,128]", 0.25], ["window_attn_bwd.33 bf16[1,28,16384,128]", 0.75],
    ["window_attn_fwd.28 bf16[1,28,16384,128]", 0.25], ["window_attn_bwd.34 bf16[1,28,16384,128]", 0.75],
    ["gmm.45 bf16[24576,768]", 0.30], ["tgmm.12 bf16[8,2560,768]", 0.10],
    ["sum_by_token.53 bf16[16384,2560]", 0.20], ["transpose_jvp_sum_by_token__.3 f32[24576,2560]", 0.20],
]}
PEAK = {"bf16_tflops": 197.0}


def test_the_initial_scales_are_the_architecture_files_and_not_the_models(architecture, config):
    """``run.embedding_init_scale`` and ``run.head_init_scale`` multiply the
    embedding and the output head of the model's own initialisation, in
    ``build`` and nowhere in the program: every other leaf is the model's, and
    at 1 and 1 the tree is the model's bit for bit."""
    import jax
    import jax.numpy as jnp

    overlay = json.loads(OVERLAY.read_text())
    toy = {**config, **overlay["config"]}
    toy["run"] = {**config["run"], **overlay["run"]}
    tokens = jnp.zeros((1, 16), jnp.int32)
    init = lambda **scales: architecture.build(
        {**toy, "run": {**toy["run"], **scales}}, 16
    ).init(jax.random.PRNGKey(5), tokens)["params"]
    plain = init(embedding_init_scale=1.0, head_init_scale=1.0)
    from torchft_tpu.models.smallthinker import SmallThinker

    model = architecture.build(toy, 16)
    assert not hasattr(model.config, "head_init_scale")
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), plain,
        SmallThinker(model.config).init(jax.random.PRNGKey(5), tokens)["params"]))
    scaled = init(embedding_init_scale=4.0, head_init_scale=3.0)
    for name, by in (("tok_embed", 4.0), ("lm_head", 3.0)):
        (a,), (b,) = (jax.tree_util.tree_leaves(t[name]) for t in (plain, scaled))
        assert float(jnp.max(jnp.abs(b - by * a))) <= 1e-6 * by * float(jnp.max(jnp.abs(a))), name
    for name in set(plain) - {"tok_embed", "lm_head"}:
        assert jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda a, b: bool(jnp.array_equal(a, b)), plain[name], scaled[name])), name
    assert abs(float(jnp.std(plain["lm_head"]["kernel"])) * toy["hidden_size"] ** 0.5 - 1.0) < 0.15


def obs_of(config, **more):
    return {"config": config, "batch": 1, "seq": 16384, "steps": 5, "peaks": PEAK, **more}


def test_the_readers_read_the_attention_calls_by_name(bench, config, architecture):
    trace = {"busy_s": 8.0, "kernels": KERNELS, "ops": []}
    read = lambda name, obs: bench.reader("per_layer", name).read(obs)
    obs = obs_of(config, trace=trace)
    assert read("mixed_attn_time_pct", obs) == pytest.approx(100 * 3.5 / 8.0)
    assert read("mixed_attn_mxu_pct", obs) == pytest.approx(
        100 * 5 * architecture.mixed_attention_flops(config, 1, 16384) / 3.5 / 197e12)
    assert read("window_attn_mxu_pct", obs) == pytest.approx(
        100 * 5 * architecture.window_attention_flops(config, 1, 16384) / 2.0 / 197e12)
    # The routed layer's calls are Pallas calls and are in neither.
    seconds_of = bench.reader("per_layer", "mixed_attn_time_pct").seconds_of
    assert seconds_of(obs, "EXPERT_KERNEL") == pytest.approx(0.8)
    # ... which ``expert_time_pct`` reads in this cell since PR 64: the grouped
    # product's calls AND the two sums by token, as this file names them.
    assert read("expert_time_pct", obs) == pytest.approx(100 * 0.8 / 8.0)
    assert not [n for n, _ in KERNELS["jit__fused"]
                if architecture.EXPERT_KERNEL.search(n) and architecture.ATTENTION_KERNEL.search(n)]


@pytest.mark.parametrize("name", OWN)
@pytest.mark.parametrize("case", [
    "no-trace", "a-program-without-such-calls", "no-steps", "another-architecture",
])
def test_a_reader_with_nothing_to_read_returns_nothing(name, case, bench, config):
    """As on the parent commit, whose program has no such call, and in any
    other cell: the line leaves the metric out and nothing raises."""
    other = {"busy_s": 6.0, "kernels": {"jit__fused": [
        ["attn.17 bf16[1,32,8192,128]", 1.0], ["gmm.45 bf16[65536,768]", 0.3],
        ["sum_by_token.9 bf16[8192,2048]", 0.2]]}, "ops": [["fusion.9 bf16[8192,4096]", 3.0]]}
    obs = {
        "no-trace": obs_of(config, trace=None),
        "a-program-without-such-calls": obs_of(config, trace=other),
        "no-steps": obs_of(config, trace={"busy_s": 0.0, "kernels": {}, "ops": []}, steps=0),
        "another-architecture": obs_of(
            bench.config("keye-vl2-30b-a3b-ep8-1chip"), trace={"busy_s": 8.0, "kernels": KERNELS}),
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


def test_the_plain_rehearsal_overlay_would_undo_the_cuts(bench):
    """Why the cell has an overlay of its own: the shared one sets a depth of 2
    (no whole period) and a vocabulary of 512, and names another block's keys."""
    shared = json.loads((ROOT / "chipbench/fixtures/rehearsal.json").read_text())["config"]
    assert shared["num_hidden_layers"] == 2 and shared["vocab_size"] == 512
    mine = json.loads(OVERLAY.read_text())
    assert mine["config"]["num_hidden_layers"] % 4 == 0
    assert set(mine["traffic"]) == {"ftddp-seq16k"}


@pytest.fixture(scope="module")
def check_script():
    return spec.load_module(ROOT / "scripts/keye_selection_check.py")


@pytest.mark.parametrize("seed", [7, 54001, 2**31 + 5])
def test_the_fp8_control_is_not_correct_by_the_harness_own_comparison(seed, bench, config, check_script, monkeypatch):
    """The float32 reference with its weights in fp8, through
    ``harness.reference_check`` under the overlay's limits: a problem comes
    back (and the program itself, on the same seed, gives none)."""
    from chipbench import harness, reference
    from chipbench.model import System

    overlay = json.loads(OVERLAY.read_text())
    toy = {**config, **overlay["config"]}
    toy["run"] = {**config["run"], **overlay["run"]}
    traffic = {**bench.traffic("ftddp-seq16k"), **overlay["traffic"]["ftddp-seq16k"]}
    for constant, value in overlay["reference"].items():
        monkeypatch.setattr(reference, constant, value)
    system = System(toy, bench.architecture(toy["model_type"]), traffic, seed)
    params = system.init_params()
    control = check_script.control(system, params)
    assert control["problems"] and all("loss differs" in p for p in control["problems"])
    sound = [float(system.loss_fn(params, system.tokens(0)))]
    assert not [p for p in harness.reference_check(system, sound) if p.startswith("first")]
