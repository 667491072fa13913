"""R12: composition-matrix enforcement — the capability lattice must be
explicit, loud, and extractable.

The feature axes of this framework (residency x layout x learner-kind x
parallelism x linear x quantized x boosting) do not all combine, and the
repo's policy since PR 7/8/11 is: an unsupported combination must either
**error in config validation** (naming both knobs) or **demote loudly**
(a ``log.warning`` naming both knobs). What nothing checked until ISSUE
14 is that this lattice STAYS closed as new axes land — the next
``cfg.tree_layout = "gather"`` hidden in an ``if cfg.use_quantized_grad:``
branch with no warning would silently change semantics, exactly the bug
class the hand-written sites exist to prevent.

Two finding classes:

- **R12a — silent demotion.** A write to a config *axis knob* (the
  composition axes below) inside a function body, where the innermost
  enclosing ``if`` branch (or, with no branch, the whole function)
  contains no ``log.warning``/``log.error``/``log.fatal``/``raise``: the
  requested configuration is being changed behind the caller's back.
  ``__init__``/``set_params``-style plumbing and ``config.py`` itself
  (declaration, alias + validation normalization) are exempt.
- **R12b — half-named demotion.** A demotion message (``log.warning`` /
  ``log.info`` whose static text matches a demotion phrase: "not
  supported", "does not support", "falling back", "fall back", "not
  applied") that names fewer than TWO axis knobs — the reader learns what
  was demoted but not which combination forced it. A knob is "named" by
  appearing in the static string parts, by a config-attribute argument
  (``config.tree_learner``), or by an argument variable spelled
  ``*blocker*``/``*knob*`` (a list of knob names built elsewhere).

The same extraction that powers R12 renders the **capability matrix**
(``extract_matrix``): every error cell from ``config.py`` validation
messages, every demote cell from warning sites, and every
``supports_* = False`` learner opt-out flag, each with its source
location — ``tools/gen_capability_matrix.py`` writes it to
``docs/capability-matrix.md`` and ``--check``s it in G0, so the
documented lattice can never drift from the code (the gen_params_doc
pattern applied to composition).
"""
from __future__ import annotations

import ast
import dataclasses
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from ..core import (Finding, ModuleContext, PackageIndex, Rule, call_name,
                    dotted_name, register_rule, _is_config_receiver)

# the composition axes: config knobs whose values select a feature axis.
# A write to one of these outside config.py IS a demotion; a pair of them
# in one demotion/error message IS a lattice cell.
AXIS_KNOBS = (
    "linear_tree",        # constant vs piece-wise linear leaves
    "use_quantized_grad",  # full-precision vs int8 gradient histograms
    "data_residency",     # hbm vs stream (out-of-core)
    "tree_layout",        # gather vs sorted physical row order
    "tree_learner",       # serial / feature / data / voting parallelism
    "boosting",           # gbdt / dart / rf
    "tpu_fused_learner",  # whole-tree fused program vs host loop
)

_DEMOTION_PHRASES = ("not supported", "does not support", "falling back",
                     "fall back", "not applied", "device-resident")
_ERROR_PHRASES = ("requires", "cannot", "must", "needs", "not supported",
                  "incompatible", "disable")
# a demotion CONTINUES running with changed behavior — warning/info. A
# log.error/log.fatal/raise is a hard stop: an error cell, not a demote
# cell, and naming the one offending knob+value is already actionable
_LOG_DEMOTE_TAILS = frozenset({"warning", "info"})
_LOUD_TAILS = frozenset({"warning", "error", "fatal"})
# dynamic message arguments that ARE lists of knob names built elsewhere
# (learner blocker lists, gbdt not_applied/host_only accumulators): they
# name the demoted side at runtime, so they count as one knob mention
_KNOB_LIST_NAMES = re.compile(
    r"blocker|knob|not_applied|host_only|unsupported|reasons")

# functions that legitimately write config knobs without being demotions:
# construction/els plumbing and explicit setter surfaces
_EXEMPT_FUNCS = frozenset({"__init__", "__post_init__", "set_params",
                           "update", "_apply_aliases", "reset_parameter"})

# supports_<flag> class attributes -> the axis knob the flag gates
SUPPORTS_FLAG_AXES = {
    "supports_stream": "data_residency",
    "supports_sorted_layout": "tree_layout",
}


@dataclasses.dataclass(frozen=True)
class MatrixCell:
    """One extracted capability-lattice fact."""
    knob_a: str                          # sorted pair
    knob_b: str
    kind: str                            # "error" / "demote"
    path: str
    line: int
    detail: str                          # message excerpt / flag owner


def _static_text(node: ast.AST) -> str:
    """Concatenated static string content of a Constant/JoinedStr/BinOp
    message expression ('' when nothing static)."""
    parts: List[str] = []
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            parts.append(n.value)
    return " ".join(parts)


_WORD_RE = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def _mentioned_knobs(call: ast.Call, index: PackageIndex) -> List[str]:
    """Knob mentions in a demotion message: the invariant is that the log
    line names, in KNOB SPELLING, both the demoted feature and the
    combination that forced it. A mention is (a) an axis knob or any
    declared ``Config`` field appearing as a whole word in the static
    text ("cegb" does not count — "cegb_tradeoff" does), (b) a
    config-attribute argument (``config.tree_learner``), or (c) a
    variable argument spelled like a knob list (``blocker_knobs``,
    ``not_applied``, ``host_only``)."""
    text = " ".join(_static_text(a) for a in call.args)
    words = set(_WORD_RE.findall(text))
    fields = set(index.config_fields) | set(AXIS_KNOBS)
    out = {w for w in words if w in fields}
    for a in call.args:
        d = dotted_name(a)
        tail = d.rsplit(".", 1)[-1] if d else ""
        if tail in fields and _is_config_receiver(
                d.rsplit(".", 1)[0] if "." in d else ""):
            out.add(tail)
        # a knob-list variable may sit inside a join() call — search the
        # whole argument expression, not just its top-level name
        for n in ast.walk(a):
            if isinstance(n, ast.Name) \
                    and _KNOB_LIST_NAMES.search(n.id.lower()):
                out.add(f"<{n.id}>")     # dynamic knob list: counts as one
    return sorted(out)


def _is_loud_call(node: ast.AST) -> bool:
    if isinstance(node, ast.Raise):
        return True
    if isinstance(node, ast.Call):
        name = call_name(node)
        head, _, tail = name.rpartition(".")
        return tail in _LOUD_TAILS and (
            head in ("log", "logger", "logging") or head.endswith(".log"))
    return False


def _branch_scope(ctx: ModuleContext, node: ast.AST) -> Optional[ast.AST]:
    """The innermost enclosing If (branch granularity), else the enclosing
    function, else None (module level — config declarations)."""
    for a in ctx.ancestors(node):
        if isinstance(a, ast.If):
            return a
        if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return a
    return None


def _scope_is_loud(scope: ast.AST) -> bool:
    for n in ast.walk(scope):
        if _is_loud_call(n):
            return True
    return False


def _is_demotion_message(call: ast.Call) -> bool:
    name = call_name(call)
    head, _, tail = name.rpartition(".")
    if tail not in _LOG_DEMOTE_TAILS or head not in ("log", "logger",
                                                     "logging"):
        return False
    text = " ".join(_static_text(a) for a in call.args)
    return any(p in text for p in _DEMOTION_PHRASES)


def _is_config_module(ctx: ModuleContext, index: PackageIndex) -> bool:
    return index.config_module is not None \
        and ctx.relpath == index.config_module


@register_rule
class CompositionMatrixRule(Rule):
    id = "R12"
    severity = "error"
    description = ("composition-matrix enforcement: a feature-axis knob "
                   "demoted silently (no warning/raise in the branch), or "
                   "a demotion message naming fewer than two axis knobs")

    def check(self, ctx: ModuleContext, index: PackageIndex
              ) -> Iterator[Finding]:
        config_mod = _is_config_module(ctx, index)
        # R12a: silent axis-knob writes (demotions) outside config.py
        if not config_mod:
            for node in ctx.nodes(ast.Assign, ast.AugAssign):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    if not (isinstance(t, ast.Attribute)
                            and t.attr in AXIS_KNOBS):
                        continue
                    recv = dotted_name(t.value)
                    if not _is_config_receiver(recv):
                        continue
                    # a demotion never turns a feature ON: writes of the
                    # literal True are request plumbing (e.g. honoring a
                    # dataset-level linear_tree param), not downgrades
                    if isinstance(node, ast.Assign) and isinstance(
                            node.value, ast.Constant) \
                            and node.value.value is True:
                        continue
                    funcs = ctx.enclosing_functions(node)
                    if not funcs or any(f.name in _EXEMPT_FUNCS
                                        for f in funcs):
                        continue
                    scope = _branch_scope(ctx, node)
                    if scope is None or _scope_is_loud(scope):
                        continue
                    yield ctx.finding(
                        self, node,
                        f"axis knob '{t.attr}' is rewritten here with no "
                        f"log.warning/raise in the enclosing branch — a "
                        f"SILENT demotion: the caller's requested "
                        f"configuration changes semantics without a "
                        f"trace; demote loudly (warning naming both "
                        f"knobs) or make the combination a config error")
        # R12b: demotion messages that name fewer than two axis knobs
        for call in ctx.nodes(ast.Call):
            if not _is_demotion_message(call):
                continue
            knobs = _mentioned_knobs(call, index)
            if len(knobs) >= 2:
                continue
            named = f"only '{knobs[0]}'" if knobs else "no axis knob"
            yield ctx.finding(
                self, call,
                f"demotion message names {named}: the reader learns what "
                f"was demoted but not which combination forced it — name "
                f"BOTH axes of the unsupported pair "
                f"(e.g. 'data_residency=stream is not supported with "
                f"tree_learner=data') so the finding is actionable from "
                f"the log line alone")


# ---------------------------------------------------------------------------
# capability-matrix extraction (tools/gen_capability_matrix.py)
# ---------------------------------------------------------------------------
def _pairs(knobs: Sequence[str]) -> List[Tuple[str, str]]:
    real = [k for k in knobs if not k.startswith("<")]
    out = []
    for i, a in enumerate(real):
        for b in real[i + 1:]:
            out.append(tuple(sorted((a, b))))
    return out


def extract_matrix(contexts: Sequence[ModuleContext],
                   index: PackageIndex) -> List[MatrixCell]:
    """Every statically extractable capability-lattice cell, sorted."""
    cells: Dict[Tuple[str, str, str, str, int], MatrixCell] = {}

    def add(a: str, b: str, kind: str, path: str, line: int,
            detail: str) -> None:
        key = (a, b, kind, path, line)
        cells.setdefault(key, MatrixCell(a, b, kind, path, line,
                                         " ".join(detail.split())[:160]))

    for ctx in contexts:
        config_mod = _is_config_module(ctx, index)
        for call in ctx.nodes(ast.Call):
            if _is_demotion_message(call):
                knobs = _mentioned_knobs(call, index)
                for (a, b) in _pairs(knobs):
                    add(a, b, "demote", ctx.relpath, call.lineno,
                        _static_text(call.args[0]) if call.args else "")
        if config_mod:
            # validation error cells: any static string in config.py (a
            # check tuple message, a log.fatal) naming >= 2 axis knobs
            # with an error phrase
            for node in ctx.nodes(ast.Constant, ast.JoinedStr):
                text = _static_text(node)
                if not text or not any(p in text for p in _ERROR_PHRASES):
                    continue
                knobs = [k for k in AXIS_KNOBS if k in text]
                for (a, b) in _pairs(knobs):
                    add(a, b, "error", ctx.relpath, node.lineno, text)
        # supports_* learner opt-out flags: class-body assigns to False
        for cls in ctx.nodes(ast.ClassDef):
            for item in cls.body:
                if not (isinstance(item, ast.Assign)
                        and len(item.targets) == 1
                        and isinstance(item.targets[0], ast.Name)):
                    continue
                flag = item.targets[0].id
                axis = SUPPORTS_FLAG_AXES.get(flag)
                if axis is None or not (
                        isinstance(item.value, ast.Constant)
                        and item.value.value is False):
                    continue
                a, b = sorted((axis, "tree_learner"))
                add(a, b, "demote", ctx.relpath, item.lineno,
                    f"{cls.name}.{flag} = False (learner opts out; "
                    f"resolver falls back loudly)")
    return sorted(cells.values(),
                  key=lambda c: (c.knob_a, c.knob_b, c.kind, c.path,
                                 c.line))


def render_matrix(cells: Sequence[MatrixCell]) -> str:
    """docs/capability-matrix.md content (deterministic)."""
    lines = [
        "# Capability matrix (generated)",
        "",
        "Statically extracted composition lattice: every axis pair with "
        "an explicit **error** (config validation refuses the combination)"
        " or **demote** (training falls back loudly) cell, with the "
        "source of truth for each. Axis pairs not listed compose freely.",
        "",
        "Generated by `python tools/gen_capability_matrix.py` from the "
        "graftlint semantic index (rule R12, "
        "`lambdagap_tpu/analysis/rules/r12_composition.py`); drift is a "
        "G0 gate failure (`--check`). Do not edit by hand.",
        "",
        "| axis A | axis B | behavior | where | note |",
        "|---|---|---|---|---|",
    ]
    seen = set()
    for c in cells:
        note = c.detail.replace("|", "\\|")
        # line numbers deliberately omitted: the doc must only change when
        # the LATTICE changes, not when unrelated edits shift a file
        row = (f"| `{c.knob_a}` | `{c.knob_b}` | {c.kind} | "
               f"`{c.path}` | {note} |")
        if row not in seen:
            seen.add(row)
            lines.append(row)
    lines.append("")
    lines.append(f"{len(cells)} cell(s); axes audited: "
                 + ", ".join(f"`{k}`" for k in AXIS_KNOBS) + ".")
    lines.append("")
    lines += render_metric_forms()
    return "\n".join(lines)


def metric_forms() -> List[Tuple[str, str, bool]]:
    """``(metric name, module, has a device form)`` of every registered
    metric class, read from ``lambdagap_tpu/metrics/*.py`` without
    importing them: a class has a device form when it, or a base class of
    its module, defines ``eval_device`` (``metrics/base.py``)."""
    import glob
    import os
    root = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "metrics")
    out = []
    for path in sorted(glob.glob(os.path.join(root, "*.py"))):
        with open(path, "r", encoding="utf-8") as f:
            tree = ast.parse(f.read())
        classes = {n.name: n for n in tree.body
                   if isinstance(n, ast.ClassDef)}

        def on_device(cls) -> bool:
            if any(isinstance(n, ast.FunctionDef) and n.name == "eval_device"
                   for n in cls.body):
                return True
            return any(isinstance(b, ast.Name) and b.id in classes
                       and on_device(classes[b.id]) for b in cls.bases)
        for cls in classes.values():
            registered = any(isinstance(d, ast.Name)
                             and d.id == "register_metric"
                             for d in cls.decorator_list)
            names = [n.value.value for n in cls.body
                     if isinstance(n, ast.Assign)
                     and any(isinstance(t, ast.Name) and t.id == "name"
                             for t in n.targets)
                     and isinstance(n.value, ast.Constant)]
            if registered and names:
                out.append((names[0], "metrics/" + os.path.basename(path),
                            on_device(cls)))
    return sorted(out)


def render_metric_forms() -> List[str]:
    """The doc's second table: where each metric is evaluated."""
    lines = [
        "## Where a metric is evaluated",
        "",
        "With a validation set attached (or a training metric asked for) "
        "the scores stay on the device. A metric with a **device** form "
        "(`Metric.eval_device`) is computed there and only its values are "
        "read back, 4 bytes each; a **host** metric has the set's scores "
        "read back whole, once an evaluation, and runs its float64 numpy "
        "statement (`Metric.eval`), which every metric keeps and the tests "
        "hold the device forms to. Extracted from `lambdagap_tpu/metrics/`.",
        "",
        "| metric | where | evaluated on |",
        "|---|---|---|",
    ]
    for name, path, device in metric_forms():
        lines.append(f"| `{name}` | `{path}` | "
                     f"{'device' if device else 'host'} |")
    lines.append("")
    return lines
