"""BENCHMARK.json and the files it names: loading, discovery by name, checks.

Everything that belongs to one configuration, one traffic mix, one job or one
per-layer metric is a file of its own, found by the name BENCHMARK.json gives
it. A later PR adds a cell or a metric by adding files and entries, and edits
no file that is here:

    configuration  <dir>/configs/<config>.json    (names its ``model_type``)
    architecture   <dir>/architectures/<model_type>.py  (the program's model,
                   its float32 reference and its operation count)
    traffic mix    <dir>/traffic/<traffic>.json   (names its job)
    job            <dir>/jobs/<job>.py
    layer metric   <dir>/layer_metrics/<name>.py  (a module with ``read(obs)``)
    end-to-end     <dir>/end_to_end/<name>.py     (the same)

``<dir>`` is any directory of BENCHMARK.json's ``paths``, searched in order,
so a later PR may bring a directory of its own.

A layer metric whose count or kernel names fit one kind of architecture only
says so on a line of its file, ``ARCHITECTURE_SAYS = "SOME_NAME"``, and lists a
cell only where the cell's architecture file has the line ``SOME_NAME = True``.

``reduced`` may list depth, a dtype or a timeout the cell bends, and the counts
a chip holds a share of (experts, heads, rows of the vocabulary: ``vocab_size``,
an eighth of them at least); never a width. A cut is written in two places: the
entry's ``reduced`` list, and the file's ``reduced`` and ``published`` objects.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {
    "command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
    "per_layer",
}
# May stand beside them: ``"trace_in_run": true`` says that the per-layer
# numbers are taken by ``--trace 2``, in the run that measures.
OPTIONAL_TOP_KEYS = {"trace_in_run"}
# What ``reduced`` never names. The model-configs guide, section 4: "No width
# is ever cut. How many heads, experts or rows of the vocabulary are held here
# may be the chip's share". Its widths are the hidden, head, feed-forward and
# expert sizes and the "window and state sizes"; ``vocab_size`` counts rows, so
# its letters ``size`` name no width.
WIDTH_ENDINGS = ("_dim", "_rank")
WIDTH_WORDS = ("size", "window", "state")
ROW_COUNTS = ("vocab_size",)
# Read as text, so that ``problems`` imports no reader and no architecture.
READER_ASKS = re.compile(r'^ARCHITECTURE_SAYS = "([A-Z][A-Z0-9_]*)"$', re.M)


class SpecError(Exception):
    """BENCHMARK.json, or a file it names, is missing or malformed."""


class Benchmark:
    """BENCHMARK.json of one checkout, and the files found by name under it."""

    def __init__(self, root: Path) -> None:
        self.root = Path(root)
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise SpecError(f"no BENCHMARK.json in {self.root}")
        self.data: Dict[str, Any] = json.loads(path.read_text())
        self.dirs: List[Path] = [self.root / p for p in self.data.get("paths", [])]

    # -- discovery -----------------------------------------------------------

    def find(self, kind: str, filename: str) -> Path:
        for d in self.dirs:
            candidate = d / kind / filename
            if candidate.is_file():
                return candidate
        raise SpecError(
            f"no {kind}/{filename} under any of {[str(d) for d in self.dirs]}"
        )

    def cell(self, workload: str) -> Dict[str, Any]:
        for cell in self.data["workloads"]:
            if cell["name"] == workload:
                return cell
        raise SpecError(f"workload {workload!r} is not in BENCHMARK.json")

    def config(self, name: str) -> Dict[str, Any]:
        for entry in self.data["configs"]:
            if entry["name"] == name:
                return json.loads((self.root / entry["file"]).read_text())
        raise SpecError(f"configuration {name!r} is not in BENCHMARK.json")

    def architecture(self, model_type: str) -> ModuleType:
        """The module that is all the benchmark knows of one block."""
        return load_module(self.find("architectures", f"{model_type}.py"))

    def traffic(self, name: str) -> Dict[str, Any]:
        return json.loads(self.find("traffic", f"{name}.json").read_text())

    def job(self, name: str) -> ModuleType:
        return load_module(self.find("jobs", f"{name}.py"))

    def reader_path(self, group: str, metric: str) -> Path:
        kind = "end_to_end" if group == "end_to_end" else "layer_metrics"
        return self.find(kind, f"{metric}.py")

    def reader(self, group: str, metric: str) -> ModuleType:
        """The module whose ``read(obs)`` gives an ``end_to_end`` or
        ``per_layer`` metric."""
        return load_module(self.reader_path(group, metric))

    def metrics_of(self, workload: str, group: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports: all
        that list it under ``workloads``, and all that have no such key."""
        return [
            m for m in self.data[group]
            if "workloads" not in m or workload in m["workloads"]
        ]


def load_module(path: Path) -> ModuleType:
    """Imports a file by path: metric names hold dots and dashes, which an
    ``import`` statement could not spell."""
    safe = re.sub(r"[^A-Za-z0-9_]", "_", f"chipbench_file_{path.parent.name}_{path.stem}")
    spec = importlib.util.spec_from_file_location(safe, path)
    if spec is None or spec.loader is None:
        raise SpecError(f"cannot import {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- checks ------------------------------------------------------------------


def is_width(key: str) -> bool:
    """Whether a key of a configuration names a width, by what it is."""
    return key not in ROW_COUNTS and (
        key.endswith(WIDTH_ENDINGS) or any(word in key for word in WIDTH_WORDS)
    )


def cut_problems(key: str, sizes: Dict[str, Any]) -> List[str]:
    """What is wrong with one allowed key of an entry's ``reduced`` as the
    configuration's file ``sizes`` writes the cut down; empty when it is honest."""
    written, published = sizes.get("reduced"), sizes.get("published")
    published = published if isinstance(published, dict) else {}
    out = []
    if not isinstance(written, dict) or key not in written:
        out.append(f"reduced key {key!r} is not in the file's own `reduced` object")
    if key in published and sizes.get(key) == published[key]:
        out.append(f"reduced key {key!r} holds its published value {published[key]!r}")
    if key == "vocab_size":
        held, total = sizes.get(key), published.get(key)
        if not isinstance(held, int) or not isinstance(total, int):
            out.append(
                "reduced key 'vocab_size' needs the held count and `published.vocab_size`"
                f" as whole numbers, not {held!r} and {total!r}"
            )
        elif not (eighth := -(-total // 8)) <= held <= total:  # equal: said above
            out.append(
                f"vocab_size {held} of a published {total}: a chip holds at least an"
                f" eighth of the rows ({eighth}) and fewer than all"
            )
    return out


def architecture_text(bench: Benchmark, workload: str) -> Optional[str]:
    """The text of the architecture file of a cell's configuration; None where
    one of the files on the way is missing or malformed, which ``problems``
    says under the configuration's name."""
    try:
        model_type = bench.config(bench.cell(workload)["config"])["model_type"]
        return bench.find("architectures", f"{model_type}.py").read_text()
    except (SpecError, OSError, ValueError, KeyError, TypeError):
        return None


def problems(bench: Benchmark) -> List[str]:
    """Every way this BENCHMARK.json breaks its contract that can be seen
    without running anything; empty when it is sound."""
    data, out = bench.data, []

    def bad(msg: str) -> None:
        out.append(msg)

    if set(data) - OPTIONAL_TOP_KEYS != TOP_KEYS:
        bad(f"top-level keys {sorted(data)} != {sorted(TOP_KEYS)}")
        return out
    if not isinstance(data.get("trace_in_run", False), bool):
        bad(f"trace_in_run must be true or false, not {data['trace_in_run']!r}")
    if not (isinstance(data["run_seconds"], int) and 1 <= data["run_seconds"] <= 51):
        bad("run_seconds must be a whole number from 1 to 51")
    for p in data["paths"]:
        if p.startswith("/") or ".." in Path(p).parts or not (bench.root / p).is_dir():
            bad(f"path {p!r} is not a directory inside the checkout")
    for word in data["command"]:
        if word.startswith("/") or ".." in Path(word).parts:
            bad(f"command word {word!r} leaves the checkout")
        if "/" in word and not any(
            Path(word).parts[: len(Path(p).parts)] == Path(p).parts for p in data["paths"]
        ):
            bad(f"command word {word!r} names a file outside paths")

    def check_names(entries: List[Dict[str, Any]], what: str) -> None:
        seen = set()
        for e in entries:
            if not NAME.match(e.get("name", "")):
                bad(f"{what} name {e.get('name')!r} has a character outside the allowed set")
            if e.get("name") in seen:
                bad(f"{what} name {e.get('name')!r} appears twice")
            seen.add(e.get("name"))

    check_names(data["configs"], "configuration")
    check_names(data["workloads"], "workload")
    check_names(data["end_to_end"] + data["per_layer"], "metric")

    config_names = {c["name"] for c in data["configs"]}
    files = set()
    for c in data["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad(f"configuration {c['name']}: keys {sorted(c)}")
        path = bench.root / c["file"]
        if not path.is_file() or not any(d in path.parents for d in bench.dirs):
            bad(f"configuration {c['name']}: file {c['file']} is not under paths")
        if c["file"] in files:
            bad(f"configuration file {c['file']} used twice")
        files.add(c["file"])
        sizes: Dict[str, Any] = {}
        if path.is_file():
            try:
                sizes = json.loads(path.read_text())
            except ValueError:
                sizes = None
            if not isinstance(sizes, dict):
                bad(f"configuration {c['name']}: file {c['file']} holds no JSON object")
                sizes = {}
        for key in c["reduced"]:
            if not NAME.match(key) or is_width(key):
                bad(f"configuration {c['name']}: reduced key {key!r} is a width or misnamed")
            elif sizes:
                for fault in cut_problems(key, sizes):
                    bad(f"configuration {c['name']}: {fault}")
        if not any(w["config"] == c["name"] for w in data["workloads"]):
            bad(f"configuration {c['name']} is used by no cell")
        if sizes:
            model_type = sizes.get("model_type")
            try:
                bench.find("architectures", f"{model_type}.py")
            except SpecError as e:
                bad(f"configuration {c['name']}: model_type {model_type!r}: {e}")

    pairs = set()
    for w in data["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad(f"workload {w['name']}: keys {sorted(w)}")
        if w["config"] not in config_names:
            bad(f"workload {w['name']}: configuration {w['config']!r} is not defined")
        if w["chips"] not in (1, 4):
            bad(f"workload {w['name']}: chips {w['chips']}")
        if not (1 <= len(w["why"]) <= 200) or "\n" in w["why"] or "\t" in w["why"]:
            bad(f"workload {w['name']}: why must be one line of 1 to 200 characters")
        if (w["config"], w["traffic"]) in pairs:
            bad(f"pair ({w['config']}, {w['traffic']}) appears twice")
        pairs.add((w["config"], w["traffic"]))
        if not NAME.match(w["traffic"]):
            bad(f"workload {w['name']}: traffic name {w['traffic']!r}")
        try:
            traffic = bench.traffic(w["traffic"])
            bench.find("jobs", f"{traffic['job']}.py")
        except (SpecError, KeyError) as e:
            bad(f"workload {w['name']}: {e}")
    four = sum(1 for w in data["workloads"] if w["chips"] == 4)
    if four > max(1, len(data["workloads"]) // 4):
        bad(f"{four} four-chip cells of {len(data['workloads'])}")

    workload_names = [w["name"] for w in data["workloads"]]
    e2e = {m["name"]: m for m in data["end_to_end"]}
    if "setup_s" not in e2e:
        bad("end_to_end has no setup_s")

    def cells_of(m: Dict[str, Any]) -> List[str]:
        return m.get("workloads", workload_names)

    for m in data["end_to_end"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "bound", "source"}:
            bad(f"metric {m['name']}: keys {sorted(m)}")
        if m.get("source") not in ("host_clock", "device_trace"):
            bad(f"end-to-end metric {m['name']}: source {m.get('source')!r}")
        if not (isinstance(m.get("bound"), (int, float)) and 0 < m["bound"] <= 0.1):
            bad(f"metric {m['name']}: bound {m.get('bound')!r}")
    for m in data["per_layer"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "source", "layer", "moves"}:
            bad(f"metric {m['name']}: keys {sorted(m)}")
        if m.get("source") not in SOURCES:
            bad(f"metric {m['name']}: source {m.get('source')!r}")
        moved = e2e.get(m.get("moves"))
        if moved is None:
            bad(f"metric {m['name']}: moves {m.get('moves')!r}, which is no end-to-end metric")
        else:
            missing = [c for c in cells_of(m) if c not in cells_of(moved)]
            if missing:
                bad(f"metric {m['name']} moves {moved['name']}, which {missing} do not report")
    for group in ("end_to_end", "per_layer"):
        for m in data[group]:
            try:
                asks = READER_ASKS.findall(bench.reader_path(group, m["name"]).read_text())
            except SpecError as e:
                bad(str(e))
                continue
            for cell in cells_of(m) if asks else ():
                said = architecture_text(bench, cell)
                for ask in asks:
                    if said is not None and not re.search(rf"^{ask} = True$", said, re.M):
                        bad(
                            f"metric {m['name']} lists {cell}, whose architecture file does"
                            f" not say `{ask} = True`: the metric's file says what that"
                            " promises; a cell that cannot stays out of the list"
                        )
    for m in data["end_to_end"] + data["per_layer"]:
        if not UNIT.match(m.get("unit", "")):
            bad(f"metric {m['name']}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad(f"metric {m['name']}: better {m.get('better')!r}")
        for c in m.get("workloads", []):
            if c not in workload_names:
                bad(f"metric {m['name']} lists unknown workload {c!r}")
    for w in workload_names:
        mine = [m["name"] for m in bench.metrics_of(w, "end_to_end")]
        if "setup_s" not in mine or len(mine) < 2:
            bad(f"workload {w} reports end-to-end metrics {mine}")
        if not bench.metrics_of(w, "per_layer"):
            bad(f"workload {w} reports no per-layer metric")
    return out


def result_line(
    correct: bool, attempted: int, failed: int,
    metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
    breakdown: Optional[Dict[str, Any]] = None, rehearsal: bool = False,
) -> str:
    """The one JSON object a run prints last: exactly the contract's keys
    (a rehearsal's line says so, and is never a chip result)."""
    line: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
        "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    if rehearsal:
        line["rehearsal"] = True
    return json.dumps(line)
