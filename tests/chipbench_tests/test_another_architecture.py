"""A cell of another architecture is listed by files and entries alone, and
EVERY test of this directory still passes on that BENCHMARK.json (PR 40).

The two older twins (``test_an_architecture_is_added_as_files``,
``test_a_sliced_vocabulary_is_added_as_files``) build a copy, ask
``spec.problems`` and rehearse the cell; neither ran the OTHER tests against
its copy, and one of those pinned three metric lists to the cells of its day.
This one builds what a ``model_config`` PR brings for a model that is not a
dense all-attention stack, and then calls every test of this directory that
reads BENCHMARK.json and starts no process (those that take ``bench_root``) on
the copy, in this process. On the CPU; tier-1 collects it.

    JAX_PLATFORMS=cpu python -m pytest tests/chipbench_tests/test_another_architecture.py -q
"""

from __future__ import annotations

import inspect
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import test_chipbench  # noqa: E402
import test_reduced  # noqa: E402
import test_span_readers  # noqa: E402
from chipbench import flops, spec  # noqa: E402
from test_chipbench import HERE, copy_benchmark, run_cell  # noqa: E402
from test_span_readers import DILOCO, FTDDP, PLAIN, SEQ8K  # noqa: E402

CONFIG, CELL, TRAFFIC, METRIC = "twokinds-1chip", "twokinds.ftddp-toy", "ftddp-toy", "expert_flops_pct"
# The lists a new cell of the ``ftddp`` job is appended to, and those it stays
# out of: the flash kernels' (its stack is not attention in every layer), the
# codecs' and the outer sync's (the ``diloco`` job's).
LISTED = ("tokens_per_s", "ft_host_ms", "quorum_commit_ms", "mfu_pct", "device_idle_pct",
          "host_stall_ms", "ft_idle_ms")
LEFT_OUT = ("flash_time_pct", "flash_mxu_pct", "codec_gbps", "outer_sync_ms", "outer_sync_idle_ms")

TWOKINDS_ARCHITECTURE = '''
"""twokinds: a stack the benchmark has never seen, of two kinds of layer in
turn: a gated expert layer (a softmax router over ``num_experts`` experts of
``intermediate_size``, every expert weighed by its gate) and causal attention
(``num_attention_heads`` heads of ``head_dim``, no positions). Program's model
and float32 reference in one file; neither has a Pallas call."""
import jax
import jax.numpy as jnp

from chipbench import reference

KINDS = ("experts", "attention")


def kind_of(layer):
    return KINDS[layer % len(KINDS)]


def attention_layers(config):
    return sum(kind_of(i) == "attention" for i in range(config["num_hidden_layers"]))


class Model:
    def __init__(self, config):
        self.config = config
        self.dtype = jnp.dtype(config["run"]["dtype"])

    def init(self, key, tokens):
        c = self.config
        d, f, e = c["hidden_size"], c["intermediate_size"], c["num_experts"]
        h, k, vocab = c["num_attention_heads"], c["head_dim"], c["vocab_size"]
        keys = iter(jax.random.split(key, 2 + 4 * c["num_hidden_layers"]))
        normal = lambda shape, fan: (jax.random.normal(next(keys), shape) * fan ** -0.5).astype(self.dtype)
        layers = []
        for i in range(c["num_hidden_layers"]):
            if kind_of(i) == "attention":
                layers.append({"scale": jnp.ones((d,), self.dtype), "wq": normal((d, h, k), d),
                               "wk": normal((d, h, k), d), "wv": normal((d, h, k), d),
                               "wo": normal((h, k, d), h * k)})
            else:
                layers.append({"scale": jnp.ones((d,), self.dtype), "router": normal((d, e), d),
                               "w_in": normal((e, d, f), d), "w_out": normal((e, f, d), f)})
        return {"params": {"embed": normal((vocab, d), 1), "layers": layers,
                           "scale": jnp.ones((d,), self.dtype), "head": normal((d, vocab), d)}}

    def apply(self, params, inputs, targets=None):
        p, eps = params["params"], self.config["rms_norm_eps"]
        x = p["embed"][inputs]
        for i, w in enumerate(p["layers"]):
            y = reference.rms_norm(x, w["scale"], eps)
            if kind_of(i) == "attention":
                q, k, v = (jnp.einsum("bsd,dhk->bshk", y, w[n]) for n in ("wq", "wk", "wv"))
                scores = jnp.einsum("bshk,bthk->bhst", q, k) * q.shape[-1] ** -0.5
                causal = jnp.tril(jnp.ones(scores.shape[-2:], bool))
                probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
                x = x + jnp.einsum("bshk,hkd->bsd", jnp.einsum("bhst,bthk->bshk", probs, v), w["wo"])
            else:
                gate = jax.nn.softmax(y @ w["router"], axis=-1)
                hidden = jax.nn.silu(jnp.einsum("bsd,edf->bsef", y, w["w_in"]))
                x = x + jnp.einsum("bse,bsed->bsd", gate, jnp.einsum("bsef,efd->bsed", hidden, w["w_out"]))
        x = reference.rms_norm(x, p["scale"], eps)
        logp = jax.nn.log_softmax((x @ p["head"]).astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def build(config, seq):
    return Model(config)


def sequence_loss(params, tokens, config, recompute=False):
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params["params"])
    eps = float(config["rms_norm_eps"])
    x = p["embed"][tokens[:-1]]
    for i, w in enumerate(p["layers"]):
        y = reference.rms_norm(x, w["scale"], eps)
        if kind_of(i) == "attention":
            q, k, v = (jnp.einsum("sd,dhk->shk", y, w[n]) for n in ("wq", "wk", "wv"))
            x = x + jnp.einsum("shk,hkd->sd", reference.causal_attention(q, k, v), w["wo"])
        else:
            gate = jax.nn.softmax(y @ w["router"], axis=-1)
            for e in range(config["num_experts"]):  # one expert at a time
                x = x + gate[:, e:e + 1] * (jax.nn.silu(y @ w["w_in"][e]) @ w["w_out"][e])
    return reference.next_token_loss_sum(reference.rms_norm(x, p["scale"], eps), p["head"], tokens[1:])


def parameter_counts(config):
    d, f, e, vocab = (config[k] for k in ("hidden_size", "intermediate_size", "num_experts", "vocab_size"))
    mixers = attention_layers(config)
    attention = 4 * d * config["num_attention_heads"] * config["head_dim"]
    experts = e * (d + 2 * d * f)  # every expert is weighed, so every one is computed
    routed = config["num_hidden_layers"] - mixers
    return {"total": mixers * (attention + d) + routed * (experts + d) + 2 * vocab * d + d,
            "experts": routed * experts,
            "matmul": mixers * attention + routed * experts + vocab * d}


def train_flops_per_token(config, seq):
    """6 * N_matmul, and the scores of the layers that ARE attention."""
    width = config["num_attention_heads"] * config["head_dim"]
    return 6.0 * parameter_counts(config)["matmul"] + 12 * attention_layers(config) * width * seq
'''

READER = '''
"""expert_flops_pct: the expert layers' share of the operations a trained token
needs, from the architecture's own counts: the file of the cell's
``model_type``, beside this directory, and no key of a configuration spelled
here."""
from pathlib import Path

from chipbench.spec import load_module


def read(obs):
    here = Path(__file__).resolve().parents[1]
    counts = load_module(here / "architectures" / (obs["config"]["model_type"] + ".py")).parameter_counts(obs["config"])
    return 100.0 * 6.0 * counts["experts"] / obs["flops_per_token"]
'''


def files_of(copy: Path):
    """Every file of the copy's chipbench/ and tests/chipbench_tests/, as it is."""
    return {
        p: p.read_bytes() for d in ("chipbench", "tests/chipbench_tests") for p in (copy / d).rglob("*")
        if p.is_file() and "__pycache__" not in p.parts
    }


def list_a_fifth_cell(copy: Path, flash_lists=(), says: str = "") -> None:
    """In ``copy``, what a ``model_config`` PR brings, in a directory of its
    own: an architecture file of two kinds of layer (plus the line ``says``), a
    configuration that holds a share of the depth, the experts and the
    vocabulary, with ``published`` beside them, a traffic mix of the ``ftddp``
    job, an overlay for its rehearsal, a per-layer metric with its reader,
    appended LAST, and the cell, appended to ``LISTED`` (and ``flash_lists``)."""
    copy_benchmark(copy)
    extra = copy / "fifthbench"
    for sub in ("configs", "traffic", "architectures", "layer_metrics"):
        (extra / sub).mkdir(parents=True)
    (extra / "architectures/twokinds.py").write_text(TWOKINDS_ARCHITECTURE + says)
    (extra / f"layer_metrics/{METRIC}.py").write_text(READER)
    config = json.loads((copy / "chipbench/configs/mistral-7b-v0.3-1chip.json").read_text())
    config.update(name=CONFIG, model_type="twokinds", num_hidden_layers=4, num_experts=8, vocab_size=64)
    config["published"] = {"num_hidden_layers": 16, "num_experts": 64, "vocab_size": 512}
    config["reduced"].update(num_experts="one of eight chips that share a layer", vocab_size="an eighth")
    (extra / f"configs/{CONFIG}.json").write_text(json.dumps(config))
    ftddp = json.loads((copy / "chipbench/traffic/ftddp.json").read_text())
    (extra / f"traffic/{TRAFFIC}.json").write_text(json.dumps({**ftddp, "batch": 2, "seq": 64}))
    # chipbench/fixtures/rehearsal.json sets vocab_size 512, which would undo the cut.
    overlay = json.loads((HERE / "rehearsal.json").read_text())
    del overlay["config"]["vocab_size"]
    overlay["config"].update(num_hidden_layers=2, num_experts=4)
    (extra / "rehearsal.json").write_text(json.dumps(overlay))

    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    data["paths"].append("fifthbench")
    data["configs"].append({
        "name": CONFIG, "source": "https://example.org/x", "why": "test",
        "file": f"fifthbench/configs/{CONFIG}.json",
        "reduced": ["num_hidden_layers", "num_experts", "vocab_size", "adam_mu_dtype", "manager_timeout_s"],
    })
    data["workloads"].append({"name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1, "why": "test"})
    for metric in data["end_to_end"] + data["per_layer"]:
        if metric["name"] in LISTED + tuple(flash_lists):
            metric["workloads"].append(CELL)
    data["per_layer"].append({
        "name": METRIC, "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "model + in-slice mesh", "moves": "tokens_per_s", "workloads": [CELL],
    })
    (copy / "BENCHMARK.json").write_text(json.dumps(data))


class Fifth:
    def __init__(self, copy: Path) -> None:
        list_a_fifth_cell(copy)
        self.root, self.before = copy, files_of(copy)

    def unedited(self) -> bool:
        return files_of(self.root) == self.before


@pytest.fixture(scope="module")
def fifth(tmp_path_factory) -> Fifth:
    return Fifth(tmp_path_factory.mktemp("fifth") / "repo")


def test_a_cell_of_another_architecture_is_listed_as_files(fifth):
    """The entries are sound, the cell reports what its lists say, its reader
    finds the architecture's counts, and the rehearsed cell prints the contract
    line through the FT-DDP step: ``correct``, both reference checks, the three
    end-to-end metrics. No file the benchmark had was edited."""
    bench, tree = spec.Benchmark(fifth.root), spec.Benchmark(ROOT)
    assert spec.problems(bench) == []
    per_layer = [m["name"] for m in bench.data["per_layer"]]
    assert per_layer[-1] == METRIC and per_layer[:-1] == [m["name"] for m in tree.data["per_layer"]]
    names_of = lambda b, cell, group: [m["name"] for m in b.metrics_of(cell, group)]
    assert names_of(bench, CELL, "end_to_end") == ["tokens_per_s", "peak_hbm_gib", "setup_s"]
    mine = set(names_of(bench, CELL, "per_layer"))
    assert set(LISTED[1:]) | {METRIC, "compile_s"} <= mine and not set(LEFT_OUT) & mine
    for cell in (w["name"] for w in tree.data["workloads"]):
        for group in ("end_to_end", "per_layer"):  # the old cells report what they did
            assert names_of(bench, cell, group) == names_of(tree, cell, group)

    config = bench.config(CONFIG)
    architecture = bench.architecture(config["model_type"])
    assert architecture.__file__ == str(fifth.root / "fifthbench/architectures/twokinds.py")
    obs = {"config": config, "flops_per_token": architecture.train_flops_per_token(config, 64)}
    assert 0 < bench.reader("per_layer", METRIC).read(obs) < 100
    # Why it stays out of the flash lists: that count takes every layer for
    # attention, and here one layer in two is: twice the need.
    mixers = {**config, "num_hidden_layers": architecture.attention_layers(config)}
    assert flops.flash_attention_flops(config, 1, 64) == 2 * flops.flash_attention_flops(mixers, 1, 64)

    done = run_cell(
        CELL, "--trace", "0", "--rehearse", str(fifth.root / "fifthbench/rehearsal.json"),
        rehearse=False, root=fifth.root,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert line["rehearsal"] is True
    assert set(line["metrics"]) == {"tokens_per_s", "peak_hbm_gib", "setup_s"}
    assert "reference: first loss" in done.stderr and "reference: second loss" in done.stderr
    assert fifth.unedited()


def cases_of(test):
    """(id, keyword arguments) of each case of a test, from its ``parametrize``
    mark; one case without arguments where it has none."""
    marks = [m for m in getattr(test, "pytestmark", []) if m.name == "parametrize"]
    if not marks:
        return [(test.__name__, {})]
    (mark,) = marks
    names = [n.strip() for n in mark.args[0].split(",")]
    out = []
    for n, case in enumerate(mark.args[1]):
        values = case.values if hasattr(case, "values") else (case if len(names) > 1 else (case,))
        out.append((f"{test.__name__}-{getattr(case, 'id', None) or n}", dict(zip(names, values))))
    return out


STATIC = [
    pytest.param(test, given, id=case)
    for module in (test_chipbench, test_span_readers, test_reduced)
    for name, test in sorted(vars(module).items())
    if name.startswith("test_") and inspect.isfunction(test) and test.__module__ == module.__name__
    and "bench_root" in inspect.signature(test).parameters
    for case, given in cases_of(test)
]


def call_on(root: Path, test, given, tmp_path: Path) -> None:
    """``test(**given)`` with ``root`` for its ``bench_root`` (and the other
    fixtures it takes), as pytest would call it."""
    wants = inspect.signature(test).parameters
    with pytest.MonkeyPatch.context() as patch:
        fixtures = {"bench_root": root, "tmp_path": tmp_path, "monkeypatch": patch}
        unknown = set(wants) - set(given) - set(fixtures)
        assert not unknown, f"{test.__name__} takes {unknown}: teach call_on the fixture"
        test(**given, **{k: v for k, v in fixtures.items() if k in wants})


def test_static_tests_are_found():
    """The sweep below is of something: the rule-stating test, the soundness
    test, the readers', the cuts', the reference's."""
    names = {p.values[0].__name__ for p in STATIC}
    assert {"test_span_metrics_are_listed_with_their_cells_and_nothing_else_moved",
            "test_benchmark_json_is_sound", "test_span_reader", "test_flash_mxu_pct_reads_the_recorded_trace",
            "test_reduced_names_no_width_and_every_cut_is_written_down",
            "test_a_cell_a_traffic_mix_a_job_and_a_metric_are_added_as_files",
            "test_reference_agrees_with_the_program_in_float32"} <= names
    assert len(STATIC) >= 40


@pytest.mark.parametrize("test,given", STATIC)
def test_every_static_test_passes_on_the_copy(test, given, fifth, tmp_path):
    """Each test of this directory that reads BENCHMARK.json and starts no
    process, on the copy that lists the fifth cell: none pins a list to the
    cells of today."""
    call_on(fifth.root, test, given, tmp_path)
    assert fifth.unedited()


def edit_entries(copy: Path, edit) -> None:
    """A copy with the tree's own entries, ``edit`` applied to its per-layer
    metrics by name."""
    copy_benchmark(copy)
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    edit({m["name"]: m for m in data["per_layer"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(data))


def unlist(cell: str, *metrics: str):
    return lambda m: [m[name]["workloads"].remove(cell) for name in metrics]


def enlist(cell: str, metric: str):
    return lambda m: m[metric]["workloads"].append(cell)


@pytest.mark.parametrize("edit", [
    pytest.param(unlist(SEQ8K, "ft_idle_ms"), id="ft_idle_ms-loses-seq8k"),
    pytest.param(enlist(DILOCO, "ft_idle_ms"), id="ft_idle_ms-in-a-diloco-cell"),
    pytest.param(enlist(FTDDP, "outer_sync_idle_ms"), id="outer_sync_idle_ms-in-an-ftddp-cell"),
    pytest.param(unlist(DILOCO, "outer_sync_idle_ms"), id="outer_sync_idle_ms-loses-its-cell"),
    pytest.param(unlist(SEQ8K, "flash_time_pct"), id="flash-lists-differ"),
    pytest.param(unlist(PLAIN, "flash_time_pct", "flash_mxu_pct"), id="flash-lists-lose-a-dense-cell"),
    pytest.param(lambda m: m["flash_mxu_pct"].pop("layer"), id="flash_mxu_pct-loses-a-field"),
    pytest.param(lambda m: m["outer_sync_idle_ms"].pop("better"), id="outer_sync_idle_ms-loses-a-field"),
    pytest.param(lambda m: m["ft_idle_ms"].update(source="host_clock"), id="ft_idle_ms-from-another-source"),
])
def test_the_rules_still_guard_todays_entries(edit, tmp_path):
    """The rule-stating test lets a fifth cell in and nothing of today's out:
    on the tree's own entries with one thing taken away, it fails."""
    rule = test_span_readers.test_span_metrics_are_listed_with_their_cells_and_nothing_else_moved
    edit_entries(tmp_path / "repo", edit)
    with pytest.raises((AssertionError, KeyError)):
        rule(bench_root=tmp_path / "repo")
    edit_entries(tmp_path / "sound", lambda m: None)
    rule(bench_root=tmp_path / "sound")  # and passes on the same copy unedited


@pytest.mark.parametrize("flash_lists,says,want", [
    (["flash_mxu_pct"], "", 1), (["flash_time_pct"], "", 1),
    (["flash_time_pct", "flash_mxu_pct"], "", 2),
    (["flash_time_pct", "flash_mxu_pct"], "\nFLASH_ATTENTION_IN_EVERY_LAYER = False\n", 2),
    (["flash_time_pct", "flash_mxu_pct"], "\n# FLASH_ATTENTION_IN_EVERY_LAYER = True\n", 2),
    (["flash_time_pct", "flash_mxu_pct"], "\nFLASH_ATTENTION_IN_EVERY_LAYER = True\n", 0),
    ([], "", 0),
], ids=["mxu-alone", "time-alone", "both", "says-false", "says-it-in-a-comment", "says-true", "in-neither"])
def test_a_flash_list_takes_a_cell_only_where_its_architecture_says_so(flash_lists, says, want, tmp_path):
    """The two flash readers say ``ARCHITECTURE_SAYS = "FLASH_ATTENTION_IN_EVERY_LAYER"``
    and the Mistral architecture has the line; the fifth cell's does not, so a
    PR that appends it to every list it sees is told before anything runs:
    one named problem a list, and no cell of that copy gives a result."""
    copy = tmp_path / "repo"
    list_a_fifth_cell(copy, flash_lists, says)
    found = spec.problems(spec.Benchmark(copy))
    assert len(found) == want, found
    for problem, metric in zip(found, flash_lists):
        assert f"metric {metric} lists {CELL}" in problem, found
        assert "`FLASH_ATTENTION_IN_EVERY_LAYER = True`" in problem, found
