"""tpuft_check (torchft_tpu.analysis) tier-1 suite.

Per-rule positive/negative fixture tests (tests/fixtures/analysis/), the
suppression + baseline machinery, the CLI contract (one-line findings,
exit code), and the load-bearing guarantee: the shipped package scans
clean — CLAUDE.md's invariants hold as enforced properties.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from torchft_tpu.analysis import (
    ALL_RULES,
    RULES_BY_ID,
    apply_baseline,
    run_analysis,
    save_baseline,
)
from torchft_tpu.analysis.core import REPO_ROOT

FIXTURES = Path(__file__).resolve().parent / "fixtures" / "analysis"
ABSENT_REFERENCE = Path("/nonexistent/tpuft-reference")


def scan(name: str, rules=None, reference_root=ABSENT_REFERENCE):
    return run_analysis(
        paths=[FIXTURES / name], rules=rules, reference_root=reference_root
    )


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# per-rule positive / negative fixtures
# ---------------------------------------------------------------------------


def test_r1_violation_fixture() -> None:
    # Unguarded thread target + lambda callback + unguarded heal/recv
    # worker (the heal-plane shape: a joiner's checkpoint fetch thread
    # must funnel donor-death/checksum/watchdog failures) + unguarded
    # serve-child supervisor watcher (the sidecar shape: child death must
    # funnel into report_error, not kill the watcher thread). Golden
    # count updated DELIBERATELY with the serve-child subsystem — the
    # new shape is pinned, not baselined away.
    findings = scan("r1_violation.py", rules=["step-boundary-escape"])
    assert len(findings) == 4
    assert rules_of(findings) == ["step-boundary-escape"]
    lines = sorted(f.line for f in findings)
    assert any("thread target" in f.message for f in findings)
    assert any("lambda" in f.message for f in findings)
    assert any("recv_worker" in f.message for f in findings)
    assert any("watch_child" in f.message for f in findings)
    assert all(f.file.endswith("r1_violation.py") for f in findings)
    assert lines == [10, 16, 29, 46]


def test_r1_clean_fixture() -> None:
    assert scan("r1_clean.py") == []


def test_r2_violation_fixture() -> None:
    findings = scan("r2_violation.py", rules=["op-worker-self-wait"])
    assert len(findings) == 2  # .then callback wait + op-worker submit wait
    assert {f.line for f in findings} == {12, 20}


def test_r2_clean_fixture() -> None:
    assert scan("r2_clean.py") == []


def test_r3_violation_fixture() -> None:
    findings = scan("r3_violation.py", rules=["lock-discipline"])
    messages = [f.message for f in findings]
    # Two unlocked mutations (params + opt_state lines) and one barrier
    # inside the lock.
    assert sum("without the state-dict writer" in m for m in messages) == 2
    assert sum("barrier" in m for m in messages) == 1


def test_r3_clean_fixture() -> None:
    assert scan("r3_clean.py") == []


def test_r3_trace_violation_fixture() -> None:
    """The trace-plane lock invariant: journal recording sites never hold
    the state-dict lock across a commit barrier. A tracing span wrapped
    around a barrier inside the writer is still a barrier inside the
    writer, and a journal append before an unlocked rebind is not a
    lock."""
    findings = scan("r3_trace_violation.py", rules=["lock-discipline"])
    messages = [f.message for f in findings]
    assert sum("barrier" in m for m in messages) == 1
    assert sum("without the state-dict writer" in m for m in messages) == 1


def test_r3_trace_clean_fixture() -> None:
    """Recording around the barrier (and inside the locked adopt) is the
    shipped pattern — a lock-free deque append, clean under R3."""
    assert scan("r3_trace_clean.py") == []


def test_r4_violation_fixture() -> None:
    findings = scan("r4_violation.py", rules=["unjitted-optax"])
    assert len(findings) == 2
    assert any(".update()" in f.message for f in findings)
    assert any("apply_updates" in f.message for f in findings)


def test_r4_clean_fixture() -> None:
    assert scan("r4_clean.py") == []


def test_r5_violation_fixture() -> None:
    findings = scan("r5_violation.py", rules=["replica-axis-in-mesh"])
    assert len(findings) == 1
    assert "replica" in findings[0].message


def test_r5_clean_fixture() -> None:
    assert scan("r5_clean.py") == []


def test_r5_zero_violation_fixture() -> None:
    # Shard-spec-shaped code (the ZeRO plane, torchft_tpu/zero.py)
    # leaking the replica axis into a Mesh: exactly ONE finding, at the
    # Mesh construction — the downstream spec dicts naming "replica" as
    # data are not Mesh axes and must not fire. Golden count added
    # DELIBERATELY with the ZeRO subsystem: the new shard-plane shape is
    # pinned, not baselined away.
    findings = scan("r5_zero_violation.py", rules=["replica-axis-in-mesh"])
    assert len(findings) == 1
    assert "replica" in findings[0].message
    assert findings[0].file.endswith("r5_zero_violation.py")


def test_r5_zero_clean_fixture() -> None:
    # The real plane's shape: range bookkeeping + an intra-slice Mesh.
    assert scan("r5_zero_clean.py") == []


def test_r6_violation_parse_level() -> None:
    # Reference snapshot absent: only the parse-level (inverted range)
    # finding fires; reference citations skip cleanly.
    findings = scan("r6_violation.py", rules=["citation-lint"])
    assert len(findings) == 1
    assert "inverted" in findings[0].message


def test_r6_violation_resolves_against_reference(tmp_path) -> None:
    ref = tmp_path / "reference"
    (ref / "torchft").mkdir(parents=True)
    (ref / "torchft" / "manager.py").write_text("\n".join(f"# {i}" for i in range(10)))
    findings = scan(
        "r6_violation.py", rules=["citation-lint"], reference_root=ref
    )
    messages = sorted(f.message for f in findings)
    assert len(findings) == 3
    assert any("inverted" in m for m in messages)
    assert any("manager.py:999" in m and "stale" in m for m in messages)
    assert any("nosuch_module.py:3" in m and "resolves nowhere" in m for m in messages)


def test_r7_violation_fixture() -> None:
    # The manager's quorum-path shape with the drain REMOVED: a wire
    # reconfigure, a donor checkpoint send, and a sidecar heal staging,
    # all reachable inside an undrained speculative window — three
    # findings, one per unsafe call. Golden count added DELIBERATELY with
    # the depth-N window generalization: the speculation-discipline shape
    # is pinned, not baselined away.
    findings = scan("r7_pipeline_violation.py", rules=["speculation-discipline"])
    assert len(findings) == 3
    assert rules_of(findings) == ["speculation-discipline"]
    messages = sorted(f.message for f in findings)
    assert sum("pg.configure" in m for m in messages) == 1
    assert sum("send_checkpoint" in m for m in messages) == 1
    assert sum("stage" in m and "send_checkpoint" not in m for m in messages) == 1
    assert all("drain" in m for m in messages)


def test_r7_clean_fixture() -> None:
    # Both drain shapes (the inline quorum-change-hooks loop and the named
    # helper) lexically precede every unsafe call — clean under all rules.
    assert scan("r7_pipeline_clean.py") == []


def test_r7_publish_violation_fixture() -> None:
    # The serving-plane extension: a committed-weights publish reachable
    # with the window undrained is the reader-facing twin of an undrained
    # donor send — one finding at the publish call.
    findings = scan("r7_publish_violation.py", rules=["speculation-discipline"])
    assert len(findings) == 1
    assert rules_of(findings) == ["speculation-discipline"]
    assert "publish" in findings[0].message
    assert "drain" in findings[0].message


def test_r7_publish_clean_fixture() -> None:
    # The manager's _maybe_publish shape: drain lexically precedes the
    # state sample + publish — clean under all rules.
    assert scan("r7_publish_clean.py") == []


def test_r6_clean_fixture(tmp_path) -> None:
    # Clean with the snapshot absent...
    assert scan("r6_clean.py") == []
    # ...and with a synthetic snapshot present.
    ref = tmp_path / "reference"
    (ref / "torchft").mkdir(parents=True)
    (ref / "torchft" / "manager.py").write_text("\n".join(f"# {i}" for i in range(10)))
    assert scan("r6_clean.py", reference_root=ref) == []


def test_r9_violation_fixture() -> None:
    # The taint pass: a relay-shaped meta pull with expect_crc=None adopted
    # into self._current, a raw fetch deserialized unverified, and the
    # derived state swapped in — three findings, each naming its source.
    findings = scan("r9_violation.py", rules=["verify-before-adopt"])
    assert len(findings) == 3
    assert rules_of(findings) == ["verify-before-adopt"]
    assert sorted(f.line for f in findings) == [17, 21, 22]
    messages = sorted(f.message for f in findings)
    assert sum("self._current" in m for m in messages) == 1
    assert sum("load_state_dict" in m for m in messages) == 1
    assert sum("self._version" in m for m in messages) == 1
    assert all("_fetch_failover" in m or "fetch_bytes" in m for m in messages)


def test_r9_clean_fixture() -> None:
    # CRC+size compare, digest fence, verifying-fetch kwarg, and codec
    # decode_state all cleanse before the swap — clean under ALL rules.
    assert scan("r9_clean.py") == []


def test_r10_violation_fixture() -> None:
    findings = scan("r10_violation.py", rules=["era-fence"])
    assert len(findings) == 1
    assert findings[0].line == 6
    assert "quorum_id" in findings[0].message


def test_r10_clean_fixture() -> None:
    # The fenced handler passes; the non-checkpoint handler is out of the
    # rule's bind entirely — clean under ALL rules.
    assert scan("r10_clean.py") == []


def test_r11_violation_fixture() -> None:
    findings = scan("r11_violation.py", rules=["stale-suppression"])
    assert len(findings) == 2
    assert {f.line for f in findings} == {6, 11}
    messages = sorted(f.message for f in findings)
    assert sum("no longer matches" in m for m in messages) == 1
    assert sum("unknown rule" in m for m in messages) == 1


def test_r11_clean_fixture() -> None:
    # A live suppression: its rule still fires at the covered line, so
    # the whole-file scan (R5 suppressed, R11 satisfied) is empty.
    assert scan("r11_clean.py") == []


def test_module_cache_shares_ast_and_invalidates_on_edit(tmp_path) -> None:
    """Satellite: one parse per (file, mtime) shared across rules and
    re-scans; an edited file re-parses rather than serving stale findings."""
    import os

    from torchft_tpu.analysis.core import load_module

    target = tmp_path / "cached.py"
    target.write_text("x = 1\n")
    first = load_module(target)
    assert first is not None and load_module(target) is first
    # Same content, bumped mtime: the cache key is (mtime, size), so this
    # re-parses — correctness over micro-optimality.
    target.write_text("y = 2\n")
    os.utime(target, (1, 1))
    second = load_module(target)
    assert second is not None and second is not first
    assert "y = 2" in second.source


# ---------------------------------------------------------------------------
# suppressions + baseline
# ---------------------------------------------------------------------------


def test_inline_suppression_needs_reason() -> None:
    findings = scan("r5_suppressed.py")
    # The justified violation is suppressed; the reason-less one surfaces
    # BOTH as a malformed suppression and as the un-suppressed violation.
    assert rules_of(findings) == ["replica-axis-in-mesh", "suppression"]
    assert len(findings) == 2
    by_rule = {f.rule: f for f in findings}
    assert "missing its reason" in by_rule["suppression"].message
    assert by_rule["replica-axis-in-mesh"].line == 13


def test_baseline_roundtrip(tmp_path) -> None:
    baseline = tmp_path / "baseline.json"
    findings = scan("r5_violation.py")
    assert findings
    save_baseline(findings, baseline)
    payload = json.loads(baseline.read_text())
    assert payload["findings"]
    fresh, suppressed = apply_baseline(findings, baseline)
    assert fresh == []
    assert suppressed == len(findings)
    # A new finding (different fingerprint) is NOT masked by the baseline.
    other = scan("r3_violation.py")
    fresh, _ = apply_baseline(other, baseline)
    assert fresh == other


# ---------------------------------------------------------------------------
# the shipped tree is clean + CLI contract
# ---------------------------------------------------------------------------


def test_package_scans_clean() -> None:
    """CLAUDE.md's invariants hold over torchft_tpu/ with an EMPTY baseline
    (reference resolution pinned absent so the result is deterministic on
    boxes with and without the snapshot)."""
    findings = run_analysis(reference_root=ABSENT_REFERENCE)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_rule_registry_covers_r1_to_r11() -> None:
    assert len(ALL_RULES) == 11
    assert set(RULES_BY_ID) == {
        "step-boundary-escape",
        "op-worker-self-wait",
        "lock-discipline",
        "unjitted-optax",
        "replica-axis-in-mesh",
        "citation-lint",
        "speculation-discipline",
        "metric-doc-drift",
        "verify-before-adopt",
        "era-fence",
        "stale-suppression",
    }


def _run_cli(*args: str, env_extra=None):
    import os

    env = dict(os.environ)
    env["TPUFT_ANALYSIS_REFERENCE"] = str(ABSENT_REFERENCE)
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-m", "torchft_tpu.analysis", *args],
        capture_output=True,
        text=True,
        cwd=str(REPO_ROOT),
        env=env,
        timeout=120,
    )


@pytest.mark.slow
def test_cli_exit_codes() -> None:
    clean = _run_cli()
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert "0 finding(s)" in clean.stdout

    dirty = _run_cli(str(FIXTURES / "r5_violation.py"))
    assert dirty.returncode == 1
    assert "replica-axis-in-mesh" in dirty.stdout

    listing = _run_cli("--list-rules")
    assert listing.returncode == 0
    for rule in RULES_BY_ID:
        assert rule in listing.stdout


def test_cli_inprocess_contract() -> None:
    """The same contract as test_cli_exit_codes without subprocess cost
    (kept unconditionally in tier-1)."""
    from torchft_tpu.analysis.__main__ import main

    import os

    old = os.environ.get("TPUFT_ANALYSIS_REFERENCE")
    os.environ["TPUFT_ANALYSIS_REFERENCE"] = str(ABSENT_REFERENCE)
    try:
        assert main([]) == 0
        assert main([str(FIXTURES / "r5_violation.py")]) == 1
        assert main(["--list-rules"]) == 0
        assert main(["--rules", "bogus-rule"]) == 2
    finally:
        if old is None:
            os.environ.pop("TPUFT_ANALYSIS_REFERENCE", None)
        else:
            os.environ["TPUFT_ANALYSIS_REFERENCE"] = old


# ---------------------------------------------------------------------------
# the layering of models/ over ops/ (an ``ast`` walk, nothing imported)
# ---------------------------------------------------------------------------


def _imports(path: Path):
    """(module, name) of every import in a file, function bodies included;
    ``import a.b`` gives (a.b, None)."""
    import ast

    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from ((alias.name, None) for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            module = "." * node.level + (node.module or "")
            yield from ((module, alias.name) for alias in node.names)


def test_ops_import_nothing_of_models() -> None:
    """A kernel's oracle, its dispatch under a mesh and the choice of kernel
    are ops/'s own (ops/attention.py): no module there reaches up into a
    model file, at import time or inside a function."""
    package = REPO_ROOT / "torchft_tpu"
    reaching = [
        f"{path.name}: {module}"
        for path in sorted((package / "ops").glob("*.py"))
        for module, name in _imports(path)
        if "models" in f"{module}.{name}".split(".")
    ]
    assert reaching == []


@pytest.mark.parametrize("model", ["keye.py", "llama.py"])
def test_a_model_file_imports_no_private_name_and_makes_no_platform_choice(model) -> None:
    """What a model shares with another is public (models/decoder.py), and
    which kernel runs where is ops/'s to say: a model file imports no
    underscore name from anywhere in the package, and none of the names a
    kernel choice is made with."""
    chosen_with = {"on_tpu", "flash_attention", "select_keys", "shard_map", "checkpoint_name"}
    names = [
        (module, name)
        for module, name in _imports(REPO_ROOT / "torchft_tpu/models" / model)
        if module.startswith(("torchft_tpu", ".", "jax"))
    ]
    assert names, "the walk found no import at all"
    assert [n for n in names if (n[1] or "").startswith("_")] == []
    assert [n for n in names if n[1] in chosen_with] == []
