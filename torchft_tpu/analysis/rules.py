"""tpuft_check rules: CLAUDE.md invariants as AST properties.

R1–R8 are deliberately *lexical*: each proves what can be proven from one
function's source order and flags the rest, so a clean run is a real
guarantee at the granularity the rule states (and the runtime lockcheck
covers the interleavings the AST cannot see). R9–R11 (registered here,
implemented in :mod:`torchft_tpu.analysis.dataflow`) add an
intraprocedural dataflow layer over the same shared per-file ASTs.
Scoping: rules whose invariant binds specific layers consult
``Module.rel``; files outside the package (test fixtures, explicit CLI
paths) are always in scope, which is how the per-rule fixture tests
drive them.

| id                  | invariant (CLAUDE.md anchor)                        |
|---------------------|-----------------------------------------------------|
| step-boundary-escape| comm-layer worker threads / work callbacks funnel   |
|                     | errors (report_error / a Future / an error bucket), |
|                     | never raise past the step boundary                  |
| op-worker-self-wait | nothing that runs ON the PG op-worker thread may    |
|                     | wait on PG work (parallel/collectives.py:42 pool)   |
| lock-discipline     | registered-state mutations hold the RWLock writer;  |
|                     | commit barriers run provably outside it             |
| unjitted-optax      | optax updates go through one jitted dispatch        |
|                     | (optim.make_jit_update)                             |
| replica-axis-in-mesh| the replica axis is never a jax Mesh dim            |
| citation-lint       | docstring ``file.py:line`` citations parse and      |
|                     | resolve (reference tree when present)               |
| speculation-        | no pg.configure / send_checkpoint / sidecar staging |
| discipline          | / serving publish reachable inside an undrained     |
|                     | speculative window                                  |
| metric-doc-drift    | every emitted tpuft_* metric name has a METRICS.md  |
|                     | table row and every row a live emission site        |
| verify-before-adopt | wire bytes pass a CRC/digest/era sanitizer before   |
|                     | any adoption sink (taint pass, dataflow.py)         |
| era-fence           | checkpoint-serving route handlers consult the       |
|                     | staged quorum_id/era before answering               |
| stale-suppression   | every ``tpuft: allow`` comment still covers a live  |
|                     | finding of its rule                                 |
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from torchft_tpu.analysis import dataflow
from torchft_tpu.analysis.core import Finding, Module

__all__ = ["Rule", "ALL_RULES", "RULES_BY_ID"]


@dataclass(frozen=True)
class Rule:
    id: str
    summary: str
    anchor: str  # CLAUDE.md / code anchor the invariant comes from
    checker: Callable[..., List[Finding]]

    def check(self, module: Module, reference_root: Optional[Path] = None) -> List[Finding]:
        return self.checker(module, reference_root=reference_root)


def _finding(module: Module, rule: str, node_line: int, message: str) -> Finding:
    return Finding(
        rule=rule,
        file=module.rel,
        line=node_line,
        message=message,
        context=module.line_at(node_line),
    )


def _func_defs(tree: ast.AST) -> Iterable[ast.AST]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _terminal_name(node: ast.AST) -> Optional[str]:
    """Rightmost identifier of a Name / attribute chain (``a.b.c`` -> "c",
    ``self._epoch`` -> "_epoch")."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


def _resolve_local_callable(
    module: Module, node: ast.AST
) -> Optional[ast.AST]:
    """Maps a Name / ``self.<method>`` reference to a def in this module;
    lambdas resolve to themselves."""
    if isinstance(node, ast.Lambda):
        return node
    name: Optional[str] = None
    if isinstance(node, ast.Name):
        name = node.id
    elif (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        name = node.attr
    if name is None:
        return None
    for fn in _func_defs(module.tree):
        if fn.name == name:  # type: ignore[union-attr]
            return fn
    return None


def _enclosing_functions(module: Module, node: ast.AST) -> List[ast.AST]:
    """Innermost-first chain of function defs containing ``node``."""
    chain: List[ast.AST] = []
    cursor = module.parents.get(node)
    while cursor is not None:
        if isinstance(cursor, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            chain.append(cursor)
        cursor = module.parents.get(cursor)
    return chain


# ---------------------------------------------------------------------------
# R1 step-boundary-escape
# ---------------------------------------------------------------------------

_R1_SCOPE_PREFIXES = ("torchft_tpu/parallel/", "torchft_tpu/checkpointing/")
_R1_SCOPE_FILES = ("torchft_tpu/ddp.py",)

# A handler "funnels" when its body visibly routes the error somewhere the
# step boundary can observe: the manager's error state, a Future, an error
# bucket, or at minimum the log (worker loops that must survive).
_R1_FUNNEL_CALLS = {
    "report_error",
    "set_exception",
    "with_error_handler",
    "exception",  # logger.exception
    "append",  # error-bucket pattern (accept_err.append(e), ...)
    "put",  # error queues
    "send",  # pipe-based error replies (parallel/baby.py)
    "record",  # flight recorder
}


def _handler_funnels(handler: ast.ExceptHandler) -> bool:
    for node in ast.walk(handler):
        if isinstance(node, ast.Call):
            name = _terminal_name(node.func)
            if name in _R1_FUNNEL_CALLS:
                return True
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) else [node.target]
            for target in targets:
                tname = _terminal_name(target)
                if tname and "err" in tname.lower():
                    return True
    return False


def _handler_catches_broadly(handler: ast.ExceptHandler) -> bool:
    if handler.type is None:
        return True
    names = []
    probes = (
        handler.type.elts if isinstance(handler.type, ast.Tuple) else [handler.type]
    )
    for probe in probes:
        name = _terminal_name(probe)
        if name:
            names.append(name)
    return any(name in ("Exception", "BaseException") for name in names)


def _guarded_line_spans(fn: ast.AST) -> List[Tuple[int, int]]:
    """Line spans of try-bodies whose handlers both catch broadly and
    funnel — code inside them cannot raise past the worker."""
    spans: List[Tuple[int, int]] = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Try):
            if any(
                _handler_catches_broadly(h) and _handler_funnels(h)
                for h in node.handlers
            ):
                # The whole try statement counts: the handlers ARE the
                # funnel, and their own calls (err.append, logger) are the
                # mechanism, not an escape.
                spans.append(
                    (node.lineno, getattr(node, "end_lineno", node.lineno))
                )
    return spans


def _check_r1(module: Module, reference_root: Optional[Path] = None) -> List[Finding]:
    if module.in_package:
        if not (
            module.rel in _R1_SCOPE_FILES
            or any(module.rel.startswith(p) for p in _R1_SCOPE_PREFIXES)
        ):
            return []
    findings: List[Finding] = []
    # Collect dispatch targets: thread entry points and Work/Future done
    # callbacks. (Callables handed to executor.submit are excluded: the
    # returned Future captures their exception, which IS the funnel.)
    targets: List[Tuple[ast.AST, str]] = []
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        fname = _terminal_name(node.func)
        if fname == "Thread":
            for kw in node.keywords:
                if kw.arg == "target":
                    resolved = _resolve_local_callable(module, kw.value)
                    if resolved is not None:
                        targets.append((resolved, "thread target"))
        elif fname == "add_done_callback" and node.args:
            resolved = _resolve_local_callable(module, node.args[0])
            if resolved is not None:
                targets.append((resolved, "done-callback"))
    seen: Set[int] = set()
    for fn, kind in targets:
        if id(fn) in seen:
            continue
        seen.add(id(fn))
        if isinstance(fn, ast.Lambda):
            calls = [n for n in ast.walk(fn) if isinstance(n, ast.Call)]
            if calls:
                findings.append(
                    _finding(
                        module,
                        "step-boundary-escape",
                        fn.lineno,
                        f"lambda used as {kind} cannot funnel its errors; "
                        "use a def with a try/except routing into "
                        "report_error / a Future / an error bucket",
                    )
                )
            continue
        spans = _guarded_line_spans(fn)
        offending: Optional[ast.Call] = None
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            if any(lo <= node.lineno <= hi for lo, hi in spans):
                continue
            # Skip calls living in NESTED defs (they run when called, on
            # whoever calls them — not necessarily this worker).
            enclosing = _enclosing_functions(module, node)
            if enclosing and enclosing[0] is not fn:
                continue
            offending = node
            break
        if offending is not None:
            findings.append(
                _finding(
                    module,
                    "step-boundary-escape",
                    offending.lineno,
                    f"{getattr(fn, 'name', '<lambda>')} runs as a {kind} but "
                    "this call is outside any try/except that funnels errors "
                    "(report_error / Future.set_exception / error bucket / "
                    "logger.exception) — an exception here escapes the step "
                    "boundary (manager.py report_error contract)",
                )
            )
    return findings


# ---------------------------------------------------------------------------
# R2 op-worker-self-wait
# ---------------------------------------------------------------------------

_R2_OP_WORKER_SUBMIT_RECEIVERS = {"epoch", "_epoch"}


def _check_r2(module: Module, reference_root: Optional[Path] = None) -> List[Finding]:
    findings: List[Finding] = []

    def flag_waits(fn: ast.AST, context: str, allow_receiver: Optional[str]) -> None:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            if _terminal_name(node.func) not in ("wait", "result"):
                continue
            if not isinstance(node.func, ast.Attribute):
                continue
            receiver = _terminal_name(node.func.value)
            if allow_receiver is not None and receiver == allow_receiver:
                # The callback's own (already-completed) future parameter.
                continue
            enclosing = _enclosing_functions(module, node)
            if enclosing and enclosing[0] is not fn:
                continue
            findings.append(
                _finding(
                    module,
                    "op-worker-self-wait",
                    node.lineno,
                    f"{context} must not block on .{_terminal_name(node.func)}(): "
                    "it runs on the single PG op-worker thread, and waiting "
                    "there on work this group enqueues deadlocks the worker "
                    "(parallel/collectives.py:42 — run pipelines on their own "
                    "pool)",
                )
            )

    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        fname = _terminal_name(node.func)
        if fname in ("then", "add_done_callback") and node.args:
            resolved = _resolve_local_callable(module, node.args[0])
            if resolved is None:
                continue
            first_param: Optional[str] = None
            args_node = getattr(resolved, "args", None)
            if args_node is not None and args_node.args:
                first_param = args_node.args[0].arg
            flag_waits(
                resolved,
                f"callback passed to .{fname}()",
                allow_receiver=first_param,
            )
        elif fname == "submit" and isinstance(node.func, ast.Attribute):
            receiver = _terminal_name(node.func.value)
            if receiver in _R2_OP_WORKER_SUBMIT_RECEIVERS and node.args:
                resolved = _resolve_local_callable(module, node.args[0])
                if resolved is not None:
                    flag_waits(
                        resolved,
                        "callable submitted to the PG op-worker",
                        allow_receiver=None,
                    )
    return findings


# ---------------------------------------------------------------------------
# R3 lock-discipline
# ---------------------------------------------------------------------------

# Attributes that hold state registered with the manager (the state-dict
# registry the RWLock guards): Optimizer/LocalSGD/DiLoCo/_Fragment owned
# state. Assigning them without the writer tears a concurrent checkpoint.
_R3_REGISTERED_ATTRS = {
    "params",
    "opt_state",
    "inner_opt_state",
    "outer_opt_state",
    "backup",
    "_leaves",
}
_R3_ACQUIRES = {"disallow_state_dict_read", "w_acquire", "w_lock"}
_R3_RELEASES = {"allow_state_dict_read", "w_release"}
_R3_BARRIERS = {"should_commit", "should_commit_async", "speculative_commit_async"}


def _check_r3(module: Module, reference_root: Optional[Path] = None) -> List[Finding]:
    findings: List[Finding] = []
    for fn in _func_defs(module.tree):
        name = fn.name  # type: ignore[union-attr]
        if name == "__init__":
            continue  # construction precedes sharing
        events: List[Tuple[int, str, ast.AST]] = []
        for node in ast.walk(fn):
            enclosing = _enclosing_functions(module, node)
            if enclosing and enclosing[0] is not fn:
                continue  # nested defs run on their caller's schedule
            if isinstance(node, ast.Call):
                cname = _terminal_name(node.func)
                if cname in _R3_ACQUIRES:
                    events.append((node.lineno, "acquire", node))
                    if cname == "w_lock":
                        # `with x.w_lock():` — lexical release at the end
                        # of the with body.
                        parent = module.parents.get(node)
                        grand = module.parents.get(parent) if parent is not None else None
                        for probe in (parent, grand):
                            if isinstance(probe, ast.With):
                                events.append(
                                    (getattr(probe, "end_lineno", node.lineno), "release", node)
                                )
                                break
                elif cname in _R3_RELEASES:
                    events.append((node.lineno, "release", node))
                elif cname in _R3_BARRIERS:
                    events.append((node.lineno, "barrier", node))
                elif cname == "result" and isinstance(node.func, ast.Attribute):
                    receiver = _terminal_name(node.func.value) or ""
                    if "commit" in receiver:
                        events.append((node.lineno, "barrier", node))
            elif isinstance(node, ast.Assign):
                for target in node.targets:
                    elts = target.elts if isinstance(target, ast.Tuple) else [target]
                    for elt in elts:
                        if (
                            isinstance(elt, ast.Attribute)
                            and isinstance(elt.value, ast.Name)
                            and elt.value.id == "self"
                            and elt.attr in _R3_REGISTERED_ATTRS
                        ):
                            events.append((node.lineno, "mutate", node))
                            break
        if not events:
            continue
        events.sort(key=lambda e: e[0])
        depth = 0
        for lineno, kind, _node in events:
            if kind == "acquire":
                depth += 1
            elif kind == "release":
                depth = max(0, depth - 1)
            elif kind == "mutate" and depth == 0:
                findings.append(
                    _finding(
                        module,
                        "lock-discipline",
                        lineno,
                        f"{name} rebinds registered state without the "
                        "state-dict writer (manager.disallow_state_dict_read) "
                        "— a concurrent checkpoint capture can read a torn "
                        "params/opt pair (manager.py RWLock registry)",
                    )
                )
            elif kind == "barrier" and depth > 0:
                findings.append(
                    _finding(
                        module,
                        "lock-discipline",
                        lineno,
                        f"{name} reaches a commit barrier while lexically "
                        "inside the state-dict write lock — barriers must "
                        "run unlocked (they may heal, and peer serves need "
                        "the read lock meanwhile)",
                    )
                )
    return findings


# ---------------------------------------------------------------------------
# R4 unjitted-optax
# ---------------------------------------------------------------------------

_R4_TX_NAMES = {
    "tx",
    "_tx",
    "inner_tx",
    "_inner_tx",
    "outer_tx",
    "_outer_tx",
}


def _jitted_names(module: Module) -> Set[str]:
    """Function names that get jax.jit-wrapped anywhere in the module."""
    jitted: Set[str] = set()
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Call) and _terminal_name(node.func) == "jit":
            for arg in node.args:
                if isinstance(arg, ast.Name):
                    jitted.add(arg.id)
    return jitted


def _has_jit_decorator(fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        probe = dec.func if isinstance(dec, ast.Call) else dec
        if _terminal_name(probe) == "jit":
            return True
        if isinstance(dec, ast.Call):
            for arg in dec.args:
                if _terminal_name(arg) == "jit":
                    return True
    return False


def _check_r4(module: Module, reference_root: Optional[Path] = None) -> List[Finding]:
    findings: List[Finding] = []
    jitted = _jitted_names(module)

    def in_jitted_context(node: ast.AST) -> bool:
        for fn in _enclosing_functions(module, node):
            name = getattr(fn, "name", None)
            if name is None:
                continue
            if name in jitted or name.startswith("make_jit") or _has_jit_decorator(fn):
                return True
        return False

    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        is_tx_update = (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "update"
            and _terminal_name(node.func.value) in _R4_TX_NAMES
        )
        is_apply_updates = (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "apply_updates"
            and _terminal_name(node.func.value) == "optax"
        )
        if not (is_tx_update or is_apply_updates):
            continue
        if in_jitted_context(node):
            continue
        what = "optimizer transform .update()" if is_tx_update else "optax.apply_updates"
        findings.append(
            _finding(
                module,
                "unjitted-optax",
                node.lineno,
                f"{what} dispatched outside a jitted step — unjitted optax "
                "issues hundreds of tiny device ops, one dispatch each; "
                "route through optim.make_jit_update / "
                "make_jit_fused_step",
            )
        )
    return findings


# ---------------------------------------------------------------------------
# R5 replica-axis-in-mesh
# ---------------------------------------------------------------------------

_R5_RESERVED_AXES = {"replica", "replicas", "dp_replica"}


def _literal_axis_names(node: ast.AST) -> Optional[List[str]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        names: List[str] = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                names.append(elt.value)
            else:
                return None  # non-literal member: cannot prove
        return names
    return None


def _check_r5(module: Module, reference_root: Optional[Path] = None) -> List[Finding]:
    findings: List[Finding] = []
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        fname = _terminal_name(node.func)
        if fname not in ("Mesh", "make_mesh"):
            continue
        axis_arg: Optional[ast.AST] = None
        if len(node.args) >= 2:
            axis_arg = node.args[1]
        for kw in node.keywords:
            if kw.arg == "axis_names":
                axis_arg = kw.value
        if axis_arg is None:
            continue
        names = _literal_axis_names(axis_arg)
        if not names:
            continue
        bad = [n for n in names if n in _R5_RESERVED_AXES]
        if bad:
            findings.append(
                _finding(
                    module,
                    "replica-axis-in-mesh",
                    node.lineno,
                    f"Mesh axis names {bad} include the replica axis: the "
                    "replica dimension must stay OUT of the jax mesh so "
                    "membership changes never recompile XLA programs "
                    "(parallel/mesh.py FTMesh contract)",
                )
            )
    return findings


# ---------------------------------------------------------------------------
# R6 citation-lint
# ---------------------------------------------------------------------------

_CITATION_RE = re.compile(
    r"(?P<path>[A-Za-z_][\w./-]*\.(?:py|rs|h|cc|cpp|proto))"
    r":(?P<line>\d+)(?:-(?P<end>\d+))?"
)


def _docstrings(module: Module) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    for node in ast.walk(module.tree):
        if isinstance(
            node, (ast.Module, ast.ClassDef, ast.FunctionDef, ast.AsyncFunctionDef)
        ):
            body = getattr(node, "body", [])
            if (
                body
                and isinstance(body[0], ast.Expr)
                and isinstance(body[0].value, ast.Constant)
                and isinstance(body[0].value.value, str)
            ):
                out.append((body[0].lineno, body[0].value.value))
    return out


def _file_line_count(path: Path) -> Optional[int]:
    try:
        with path.open("rb") as fh:
            return sum(1 for _ in fh)
    except OSError:
        return None


def _resolve_citation(
    cited: str, module: Module, reference_root: Path, is_reference: bool
) -> Tuple[Optional[Path], bool]:
    """(resolved file, resolution_was_attempted).

    Citations marked ``is_reference`` (the docstring says "reference"
    nearby — the CLAUDE.md citation convention) resolve ONLY against the
    reference snapshot, and are skipped cleanly when it is absent: a
    same-named repo file must not shadow the reference's line numbering.
    Repo-internal citations resolve against the repo immediately."""
    from torchft_tpu.analysis.core import PACKAGE_ROOT, REPO_ROOT

    if cited.startswith("/"):
        p = Path(cited)
        if str(p).startswith(str(reference_root)) and not reference_root.exists():
            return None, False  # snapshot absent: cannot disprove
        return (p if p.exists() else None), True
    if is_reference:
        if not reference_root.exists():
            return None, False
        for sub in ("torchft", "", "src"):
            candidate = reference_root / sub / cited
            if candidate.exists():
                return candidate, True
        return None, True
    for base in (PACKAGE_ROOT, REPO_ROOT, module.path.parent):
        candidate = base / cited
        if candidate.exists():
            return candidate, True
    if reference_root.exists():
        for sub in ("", "torchft", "src"):
            candidate = reference_root / sub / cited
            if candidate.exists():
                return candidate, True
        return None, True
    return None, False


def _check_r6(module: Module, reference_root: Optional[Path] = None) -> List[Finding]:
    assert reference_root is not None
    findings: List[Finding] = []
    for start_line, text in _docstrings(module):
        for match in _CITATION_RE.finditer(text):
            cited = match.group("path")
            line_no = int(match.group("line"))
            end_no = int(match.group("end")) if match.group("end") else None
            # Docstring line offset: count newlines before the match.
            at_line = start_line + text[: match.start()].count("\n")
            token = match.group(0)
            if end_no is not None and end_no < line_no:
                findings.append(
                    _finding(
                        module,
                        "citation-lint",
                        at_line,
                        f"citation {token!r} has an inverted line range",
                    )
                )
                continue
            preceding = text[max(0, match.start() - 200) : match.start()]
            is_reference = "reference" in preceding.lower()
            resolved, attempted = _resolve_citation(
                cited, module, reference_root, is_reference
            )
            if resolved is None:
                if attempted:
                    findings.append(
                        _finding(
                            module,
                            "citation-lint",
                            at_line,
                            f"citation {token!r} resolves nowhere (repo or "
                            f"reference snapshot at {reference_root})",
                        )
                    )
                # Resolution not attempted (reference snapshot absent):
                # skip cleanly — cannot disprove.
                continue
            count = _file_line_count(resolved)
            if count is not None and line_no > count:
                findings.append(
                    _finding(
                        module,
                        "citation-lint",
                        at_line,
                        f"citation {token!r} is stale: {resolved.name} has "
                        f"only {count} lines",
                    )
                )
    return findings


# ---------------------------------------------------------------------------
# R7 speculation-discipline
# ---------------------------------------------------------------------------

# The invariant (CLAUDE.md pipelined-commit paragraph): a joiner must never
# heal from — and the wire must never reconfigure under — an undrained
# speculative window. Lexically: inside any function that reconfigures the
# replica PG, serves a donor checkpoint, or stages a heal-serving sidecar
# snapshot, a window drain must come FIRST. Scoped to the manager (the one
# place those calls legitimately live on the quorum path); fixtures and
# explicit CLI paths are always in scope.
_R7_SCOPE_FILES = ("torchft_tpu/manager.py",)
_R7_DRAIN_CALLS = {
    "_run_quorum_drain_hooks",
    "_drain_pipeline_for_quorum_change",
    "flush_pipeline",
}
_R7_HOOK_ITER_MARK = "quorum_change_hook"
_R7_PG_RECEIVERS = {"pg", "_pg"}
# stage = sidecar heal-part staging; publish = the serving plane's
# committed-weights publication (Manager._maybe_publish) — a publish
# sampling an undrained window would hand READERS speculative state,
# the serving twin of a donor send doing the same to a joiner.
_R7_UNSAFE_CALLS = {"send_checkpoint", "stage", "publish"}


def _check_r7(module: Module, reference_root: Optional[Path] = None) -> List[Finding]:
    if module.in_package and module.rel not in _R7_SCOPE_FILES:
        return []
    findings: List[Finding] = []
    for fn in _func_defs(module.tree):
        drains: List[int] = []
        unsafe: List[Tuple[int, str]] = []
        for node in ast.walk(fn):
            enclosing = _enclosing_functions(module, node)
            if enclosing and enclosing[0] is not fn:
                continue  # nested defs run on their caller's schedule
            if isinstance(node, ast.For):
                # The manager's inline drain shape: iterating the
                # registered quorum-change hooks and calling each.
                iter_name = _terminal_name(node.iter) or ""
                if _R7_HOOK_ITER_MARK in iter_name and any(
                    isinstance(inner, ast.Call) for inner in ast.walk(node)
                ):
                    drains.append(node.lineno)
                continue
            if not isinstance(node, ast.Call):
                continue
            cname = _terminal_name(node.func)
            if cname in _R7_DRAIN_CALLS:
                drains.append(node.lineno)
            elif (
                cname == "configure"
                and isinstance(node.func, ast.Attribute)
                and _terminal_name(node.func.value) in _R7_PG_RECEIVERS
            ):
                unsafe.append((node.lineno, "pg.configure (wire reconfigure)"))
            elif cname in _R7_UNSAFE_CALLS:
                label = (
                    "publish (serving-plane publication)"
                    if cname == "publish"
                    else f"{cname} (donor/heal staging)"
                )
                unsafe.append((node.lineno, label))
        for lineno, what in unsafe:
            if any(drain_line < lineno for drain_line in drains):
                continue
            findings.append(
                _finding(
                    module,
                    "speculation-discipline",
                    lineno,
                    f"{fn.name} reaches {what} with no speculative-window "  # type: ignore[union-attr]
                    "drain before it: a membership change or donor send "
                    "inside an undrained commit-pipeline window lets a "
                    "joiner heal from (or the wire reconfigure under) "
                    "uncommitted speculative state — drain first "
                    "(Manager._run_quorum_drain_hooks; CLAUDE.md pipelined-"
                    "commit invariant)",
                )
            )
    return findings


# --- R8: metric-doc-drift ---------------------------------------------------
# METRICS.md is the canonical metric registry (metrics.py module docstring):
# every metric name the package emits must have a table row, and every table
# row must correspond to a live emission site — else dashboards, the
# benchmark's counter readers, and fleet_status cells silently drift from
# the code.
# Anchored at torchft_tpu/metrics.py so the repo-wide scan runs exactly once
# per analysis (the rule is a whole-tree property, not a per-module one);
# findings anchor at the offending emission site / METRICS.md row, so the
# baseline is the sanctioned escape hatch for legacy gaps.
_R8_SCOPE_FILE = "torchft_tpu/metrics.py"
_R8_DOC_FILE = "METRICS.md"
_R8_EMIT_RE = re.compile(
    r"(?:metrics\.(?:inc|observe|set_gauge|timer|counter|gauge|histogram)\(\s*"
    # tracing.PHASES: a phase's histogram is emitted by tracing.phase().
    r'|\bhistogram=)"(tpuft_[a-z0-9_]+)"'
)
_R8_ROW_RE = re.compile(r"\| `(tpuft_[a-z0-9_]+)` \|")


def _check_r8(module: Module, reference_root: Optional[Path] = None) -> List[Finding]:
    if module.rel != _R8_SCOPE_FILE:
        return []
    from torchft_tpu.analysis import core

    repo = core.REPO_ROOT
    findings: List[Finding] = []
    emitted: Dict[str, Tuple[str, int, str]] = {}
    for py in sorted((repo / "torchft_tpu").rglob("*.py")):
        if "__pycache__" in py.parts or py.name == "tpuft_pb2.py":
            continue
        try:
            text = py.read_text(encoding="utf-8")
        except OSError:
            continue
        names = set(_R8_EMIT_RE.findall(text))
        if not names:
            continue
        rel = py.relative_to(repo).as_posix()
        file_lines = text.splitlines()
        for name in names:
            if name in emitted:
                continue
            anchor, context = 1, ""
            for lineno, line in enumerate(file_lines, start=1):
                if f'"{name}"' in line:
                    anchor, context = lineno, line.strip()
                    break
            emitted[name] = (rel, anchor, context)

    doc_path = repo / _R8_DOC_FILE
    if not doc_path.exists():
        return [
            Finding(
                rule="metric-doc-drift",
                file=_R8_DOC_FILE,
                line=1,
                message=(
                    f"{_R8_DOC_FILE} is missing: it is the canonical metric "
                    f"registry for {len(emitted)} emitted metric name(s)"
                ),
                context=_R8_DOC_FILE,
            )
        ]
    table: Dict[str, Tuple[int, str]] = {}
    for lineno, line in enumerate(doc_path.read_text().splitlines(), start=1):
        for name in _R8_ROW_RE.findall(line):
            table.setdefault(name, (lineno, line.strip()))

    for name in sorted(set(emitted) - set(table)):
        rel, lineno, context = emitted[name]
        findings.append(
            Finding(
                rule="metric-doc-drift",
                file=rel,
                line=lineno,
                message=(
                    f"metric {name} is emitted here but has no METRICS.md "
                    "row — document it (name, kind, labels, emitted-from, "
                    "meaning) or dashboards silently drift from the code"
                ),
                context=context or name,
            )
        )
    for name in sorted(set(table) - set(emitted)):
        lineno, context = table[name]
        findings.append(
            Finding(
                rule="metric-doc-drift",
                file=_R8_DOC_FILE,
                line=lineno,
                message=(
                    f"METRICS.md documents {name} but no live emission site "
                    "remains in torchft_tpu/ — delete the row or restore the "
                    "metric"
                ),
                context=context or name,
            )
        )
    return findings


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

ALL_RULES: Sequence[Rule] = (
    Rule(
        id="step-boundary-escape",
        summary="comm-layer worker threads and done-callbacks funnel errors, never raise",
        anchor="CLAUDE.md 'Comm-layer errors funnel into Manager.report_error'",
        checker=_check_r1,
    ),
    Rule(
        id="op-worker-self-wait",
        summary="nothing running on the PG op-worker thread waits on PG work",
        anchor="parallel/collectives.py:42 (dedicated pipeline pool)",
        checker=_check_r2,
    ),
    Rule(
        id="lock-discipline",
        summary="registered-state mutations hold the writer; barriers run unlocked",
        anchor="CLAUDE.md 'mutations take the state-dict write lock; commit barriers run unlocked'",
        checker=_check_r3,
    ),
    Rule(
        id="unjitted-optax",
        summary="optax updates go through one jitted dispatch",
        anchor="CLAUDE.md 'Optax updates must go through one jitted dispatch'",
        checker=_check_r4,
    ),
    Rule(
        id="replica-axis-in-mesh",
        summary="the replica axis is never a jax Mesh dimension",
        anchor="CLAUDE.md 'The replica axis is NOT a jax mesh dim'",
        checker=_check_r5,
    ),
    Rule(
        id="citation-lint",
        summary="docstring file.py:line citations parse and resolve",
        anchor="CLAUDE.md conventions ('Docstrings cite reference behavior')",
        checker=_check_r6,
    ),
    Rule(
        id="speculation-discipline",
        summary="no pg.configure / donor send / heal staging / serving publish inside an undrained speculative window",
        anchor="CLAUDE.md 'quorum membership changes drain the FULL window ... BEFORE pg.configure / any donor send'",
        checker=_check_r7,
    ),
    Rule(
        id="metric-doc-drift",
        summary="every emitted tpuft_* metric has a METRICS.md row and vice versa",
        anchor="metrics.py module docstring ('canonical metric names ... tabulated in METRICS.md')",
        checker=_check_r8,
    ),
    Rule(
        id="verify-before-adopt",
        summary="wire bytes pass a CRC/digest/era sanitizer before any adoption sink",
        anchor="CLAUDE.md 'Corrupt/stale/stalled donors funnel into report_error — never adopted state'",
        checker=dataflow.check_verify_before_adopt,
    ),
    Rule(
        id="era-fence",
        summary="checkpoint-serving route handlers consult the staged quorum_id/era",
        anchor="CLAUDE.md 'quorum-era tags on meta and chunk URLs' (http_transport do_GET 409 fence)",
        checker=dataflow.check_era_fence,
    ),
    Rule(
        id="stale-suppression",
        summary="every tpuft allow comment still covers a live finding of its rule",
        anchor="core.py suppression contract (the inventory must not rot)",
        checker=dataflow.check_stale_suppression,
    ),
)

RULES_BY_ID: Dict[str, Rule] = {rule.id: rule for rule in ALL_RULES}
