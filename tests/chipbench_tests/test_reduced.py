"""Tests of what a configuration's ``reduced`` may name (PR 36): a width never,
the vocabulary's rows yes, and every cut written down in the configuration's
file where ``spec.problems`` can see it. On the CPU; tier-1 collects them.

    JAX_PLATFORMS=cpu python -m pytest tests/chipbench_tests/test_reduced.py -q
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import spec  # noqa: E402
from test_chipbench import (  # noqa: E402
    HERE, THROWAWAY_ARCHITECTURE, bench_root, copy_benchmark, list_cell, run_cell,  # noqa: F401
)

CONFIG, CELL = "cut-1chip", "cut.plain"
# The throwaway's cut as the guide's section 4 has it: an eighth of the rows.
HELD, PUBLISHED = 64, 512


def list_a_cut(
    copy: Path, reduced, sizes=None, written=None, published=None, traffic="plain", root: Path = ROOT
) -> None:
    """A copy of the benchmark (``root``'s) with one more configuration in a
    directory of its own, the Mistral file's keys but for ``sizes``, its
    ``reduced`` object naming ``written`` and its ``published`` object holding
    ``published`` too, listed with ``reduced`` as a cell of ``traffic``."""
    copy_benchmark(copy, root)
    (copy / "morebench/configs").mkdir(parents=True)
    config = json.loads((copy / "chipbench/configs/mistral-7b-v0.3-1chip.json").read_text())
    config.update(name=CONFIG, **(sizes or {}))
    config["reduced"].update({key: "test" for key in written or ()})
    config["published"].update(published or {})
    (copy / f"morebench/configs/{CONFIG}.json").write_text(json.dumps(config))
    list_cell(copy, CELL, CONFIG, traffic, configs=[{
        "name": CONFIG, "source": "https://example.org/x", "why": "test",
        "file": f"morebench/configs/{CONFIG}.json", "reduced": list(reduced),
    }], root=root)


def a_key(key: str, problem: bool):
    """One more key beside the depth, written in the file's ``reduced`` object
    too, so that only what the key IS can make it a problem."""
    return pytest.param(
        ["num_hidden_layers", key], {}, [key], {}, [repr(key), "width"] if problem else None,
        id=f"{key}-{'refused' if problem else 'allowed'}",
    )


SLICED = {"vocab_size": HELD}


@pytest.mark.parametrize("reduced,sizes,written,published,want", [
    # A width is refused by what it is, whatever its letters.
    a_key("hidden_size", True), a_key("intermediate_size", True),
    a_key("moe_intermediate_size", True), a_key("head_dim", True), a_key("v_head_dim", True),
    a_key("kv_lora_rank", True), a_key("q_lora_rank", True),
    a_key("sliding_window", True), a_key("mamba_d_state", True),
    # Depth, a dtype, a timeout, and the counts a chip holds a share of.
    a_key("num_hidden_layers", False), a_key("adam_mu_dtype", False),
    a_key("manager_timeout_s", False), a_key("n_routed_experts", False),
    a_key("num_experts", False), a_key("num_attention_heads", False),
    # The vocabulary counts rows: an eighth of the published count, written down.
    pytest.param(["num_hidden_layers", "vocab_size"], SLICED, ["vocab_size"],
                 {"vocab_size": PUBLISHED}, None, id="vocab_size-an-eighth-allowed"),
    pytest.param(["num_hidden_layers", "num_key_value_heads"], {}, [], {},
                 ["'num_key_value_heads'", "`reduced` object"], id="cut-not-written-in-the-file"),
    pytest.param(["num_hidden_layers"], {"num_hidden_layers": 32}, [], {},
                 ["'num_hidden_layers'", "published value 32"], id="published-value-kept"),
    pytest.param(["vocab_size"], SLICED, ["vocab_size"], {},
                 ["'vocab_size'", "published.vocab_size"], id="vocab_size-without-published"),
    pytest.param(["vocab_size"], {"vocab_size": HELD - 1}, ["vocab_size"], {"vocab_size": PUBLISHED},
                 ["vocab_size 63", "eighth", "(64)"], id="vocab_size-under-an-eighth"),
    pytest.param(["vocab_size"], {"vocab_size": PUBLISHED}, ["vocab_size"], {"vocab_size": PUBLISHED},
                 ["'vocab_size'", "published value 512"], id="vocab_size-whole"),
    pytest.param(["vocab_size"], {"vocab_size": PUBLISHED + 1}, ["vocab_size"], {"vocab_size": PUBLISHED},
                 ["vocab_size 513", "fewer than all"], id="vocab_size-over-the-whole"),
])
def test_reduced_names_no_width_and_every_cut_is_written_down(
    reduced, sizes, written, published, want, tmp_path, bench_root
):
    """``want``: what the ONE problem says beside the configuration's name;
    None where the entry is sound."""
    copy = tmp_path / "repo"
    list_a_cut(copy, reduced, sizes, written, published, root=bench_root)
    found = spec.problems(spec.Benchmark(copy))
    if want is None:
        assert found == []
        return
    assert len(found) == 1, found
    assert f"configuration {CONFIG}" in found[0], found
    for words in want:
        assert words in found[0], found


@pytest.mark.parametrize("reduced,sizes,published", [
    (["num_hidden_layers", "vocab_size"], {"vocab_size": HELD - 1}, {"vocab_size": PUBLISHED}),
    (["num_hidden_layers", "vocab_size"], SLICED, {}),
    (["num_hidden_layers", "vocab_size", "sliding_window"], SLICED, {"vocab_size": PUBLISHED}),
], ids=["under-an-eighth", "no-published-count", "a-width-beside-it"])
def test_an_unsound_cut_ends_every_run_with_no_result(reduced, sizes, published, tmp_path):
    """One problem in the list and no cell of the copy runs: the names, no
    traceback, no line."""
    copy = tmp_path / "repo"
    list_a_cut(copy, reduced, sizes, ["vocab_size", "sliding_window"], published)
    assert len(spec.problems(spec.Benchmark(copy))) == 1
    for workload in (CELL, "mistral7b-1chip.plain"):
        done = run_cell(workload, "--trace", "0", root=copy)
        assert done.returncode != 0 and "Traceback" not in done.stderr
        assert "no result: BENCHMARK.json is unsound" in done.stderr and CONFIG in done.stderr
        assert not any(l.startswith("{") for l in done.stdout.splitlines())


def test_a_sliced_vocabulary_is_added_as_files(tmp_path):
    """The twin of ``test_an_architecture_is_added_as_files`` with a cut: an
    architecture in a directory of its own whose configuration holds 64 of a
    published 512 rows of the vocabulary, the key in both ``reduced`` places.
    The entries are sound, the cell runs through the plain job to ``correct``
    (under an overlay of the test's own: chipbench/fixtures/rehearsal.json sets
    ``vocab_size`` 512 and would undo the cut), the traffic draws its ids from
    the slice, the program's tables have the slice's rows, and no file of
    chipbench/ was edited."""
    copy = tmp_path / "repo"
    list_a_cut(
        copy, ["num_hidden_layers", "vocab_size"], {"model_type": "bagofwords", **SLICED},
        ["vocab_size"], {"vocab_size": PUBLISHED}, traffic="plain-toy",
    )
    before = {p: p.read_bytes() for p in (copy / "chipbench").rglob("*") if p.is_file()}
    extra = copy / "morebench"
    for sub in ("traffic", "architectures"):
        (extra / sub).mkdir()
    (extra / "architectures/bagofwords.py").write_text(THROWAWAY_ARCHITECTURE)
    traffic = {**json.loads((copy / "chipbench/traffic/plain.json").read_text()), "batch": 2, "seq": 64}
    (extra / "traffic/plain-toy.json").write_text(json.dumps(traffic))
    overlay = json.loads((HERE / "rehearsal.json").read_text())
    assert overlay["config"].pop("vocab_size") == PUBLISHED
    (extra / "rehearsal.json").write_text(json.dumps(overlay))

    bench = spec.Benchmark(copy)
    assert spec.problems(bench) == []
    entry = next(c for c in bench.data["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    config = bench.config(CONFIG)
    assert (config["vocab_size"], config["published"]["vocab_size"]) == (HELD, PUBLISHED)

    done = run_cell(
        CELL, "--trace", "0", "--rehearse", str(extra / "rehearsal.json"), rehearse=False, root=copy
    )
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert line["rehearsal"] is True
    assert "reference: first loss" in done.stderr and "reference: second loss" in done.stderr

    # What the run built, as run.py lays the overlay over the file.
    import numpy as np

    from chipbench.model import System

    config = {**config, **overlay["config"], "run": {**config["run"], **overlay["run"]}}
    assert config["vocab_size"] == HELD
    architecture = bench.architecture(config["model_type"])
    drawn = set()
    for seed in (0, 7, 2**31 + 7):
        system = System(config, architecture, traffic, seed)
        for step in range(5):
            ids = np.asarray(system.tokens(step))
            assert ids.shape == (2, 65) and ids.min() >= 0 and ids.max() < HELD
            drawn.update(ids.ravel().tolist())
    assert len(drawn) == HELD, "every row of the slice is drawn, and no other"
    params = system.init_params()["params"]
    assert params["embed"].shape[0] == HELD and params["head"].shape[1] == HELD
    assert {p: p.read_bytes() for p in before} == before
