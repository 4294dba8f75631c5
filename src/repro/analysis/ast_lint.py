"""Source/AST lint for retrace and bitwise hazards in ``src/repro/``.

Static companion to the jaxpr rules: some contracts (the PR 5 bitwise
reciprocal-multiply fix, the PR 8 ``acc_dtype`` threading) are idioms in
the SOURCE, invisible once traced.  :func:`lint_tree` walks every
``.py`` under a root; :func:`lint_source` lints one string (the
self-test plants use it).

Rules (ids match ``docs/analysis.md``):

  * ``host-in-trace``  -- host materialization (``.item()`` /
    ``.tolist()`` / ``float(jnp...)`` / ``jax.device_get``) in a
    function that also does device compute: breaks under jit and forces
    a device sync when eager.
  * ``tracer-branch``  -- ``if``/``while`` on a value produced by a
    ``jnp.``/``jax.`` call in the same function: a retrace/ConcretizationError
    hazard (warning severity -- data flow is approximated).
  * ``broadcast-div``  -- dividing by a ``[..., None]``-shaped operand
    instead of multiplying by a precomputed ``(V, 1)`` reciprocal; the
    PR 5 bitwise-equality rule, now enforced.
  * ``acc-dtype``      -- a Pallas ``pltpu.VMEM``/``SMEM`` scratch whose
    dtype is a literal instead of the threaded ``acc_dtype`` name: the
    kernel would silently pin its accumulator precision.
  * ``grid-arity``     -- a literal ``grid=`` tuple whose length differs
    from a ``BlockSpec`` index_map lambda's arity in the same
    ``pallas_call``: statically incompatible block/grid specs.

Suppression pragmas (per-rule, see ``docs/analysis.md``):
``# analysis: allow(rule-id)`` on the offending line or the line above;
``# analysis: allow-file(rule-id)`` anywhere in the file.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, List, Optional, Set

from repro.analysis.report import AnalysisReport

_ALLOW_LINE = re.compile(r"#\s*analysis:\s*allow\(([a-z0-9\-,\s]+)\)")
_ALLOW_FILE = re.compile(r"#\s*analysis:\s*allow-file\(([a-z0-9\-,\s]+)\)")

#: host materialization calls (dotted suffixes / names)
_HOST_ATTRS = (".item", ".tolist")
_HOST_CALLS = ("jax.device_get",)

#: rough signature of device compute: calls under these prefixes
_DEVICE_PREFIXES = ("jnp.", "jax.lax.", "jax.nn.", "jax.ops.", "lax.",
                    "pl.", "pltpu.")


#: the ``host-in-trace`` fix: keep host work at plan build, and aggregate
#: through the trace-pure planned entry points
_HOST_IN_TRACE_FIX = (
    "move the host work out of the traced function, to plan-build time; "
    "aggregate through the trace-pure seg_agg_planned -- via a plan from "
    "build_plan, plan_for_conv, or plan_for_phases (each owns a blocked "
    "layout)")


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of a call target ('' when not a name)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _parse_pragmas(src: str):
    """(file-level allowed rules, line -> allowed rules) from pragmas."""
    file_rules: Set[str] = set()
    line_rules: Dict[int, Set[str]] = {}
    for i, line in enumerate(src.splitlines(), start=1):
        m = _ALLOW_FILE.search(line)
        if m:
            file_rules |= {r.strip() for r in m.group(1).split(",")}
        m = _ALLOW_LINE.search(line)
        if m:
            rules = {r.strip() for r in m.group(1).split(",")}
            line_rules.setdefault(i, set()).update(rules)
            line_rules.setdefault(i + 1, set()).update(rules)
    return file_rules, line_rules


class _FileLint:
    """One file's AST pass; collects findings through the pragma filter."""

    def __init__(self, src: str, filename: str, report: AnalysisReport):
        self.src = src
        self.filename = filename
        self.report = report
        self.file_allow, self.line_allow = _parse_pragmas(src)

    def add(self, rule: str, severity: str, line: int, message: str,
            detail: str = "") -> None:
        if rule in self.file_allow or rule in self.line_allow.get(line, ()):
            return
        self.report.add(rule, severity, f"{self.filename}:{line}", message,
                        detail)

    # -- per-function rules -------------------------------------------------

    def _segment(self, node: ast.AST) -> str:
        return ast.get_source_segment(self.src, node) or ""

    def check_function(self, fn: ast.FunctionDef) -> None:
        device_compute = False
        host_sites: List = []  # (line, label, needs_device_compute)
        jnp_names: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                name = _dotted(node.func)
                if name.startswith(_DEVICE_PREFIXES) or \
                        "segment_sum" in name or "pallas_call" in name:
                    device_compute = True
                if name in _HOST_CALLS:
                    host_sites.append((node.lineno, name, False))
                elif name in ("float", "int") and node.args:
                    seg = self._segment(node.args[0])
                    if "jnp." in seg or "jax." in seg:
                        host_sites.append(
                            (node.lineno, f"{name}({seg[:40]}...)", False))
                elif isinstance(node.func, ast.Attribute) and \
                        node.func.attr in ("item", "tolist"):
                    host_sites.append(
                        (node.lineno, f".{node.func.attr}()", True))
                elif name in ("np.asarray", "numpy.asarray") and node.args:
                    seg = self._segment(node.args[0])
                    if "device_get" in seg:
                        host_sites.append((node.lineno, name, False))
            elif isinstance(node, ast.Assign) and \
                    len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name) and \
                    isinstance(node.value, ast.Call):
                vname = _dotted(node.value.func)
                if vname.startswith(("jnp.", "jax.")):
                    jnp_names.add(node.targets[0].id)
        for line, label, needs_dc in host_sites:
            if needs_dc and not device_compute:
                continue
            self.add("host-in-trace", "error", line,
                     f"host materialization {label} in a traced/compute "
                     "scope",
                     f"in function {fn.name!r}; {_HOST_IN_TRACE_FIX}")
        self._check_tracer_branch(fn, jnp_names)

    def _check_tracer_branch(self, fn: ast.FunctionDef,
                             jnp_names: Set[str]) -> None:
        def suspect(test: ast.AST) -> Optional[str]:
            if isinstance(test, ast.Name) and test.id in jnp_names:
                return test.id
            if isinstance(test, ast.Compare):
                if any(isinstance(op, (ast.Is, ast.IsNot))
                       for op in test.ops):
                    return None
                if isinstance(test.left, ast.Name) and \
                        test.left.id in jnp_names:
                    return test.left.id
            if isinstance(test, ast.UnaryOp) and \
                    isinstance(test.op, ast.Not):
                return suspect(test.operand)
            if isinstance(test, ast.BoolOp):
                for v in test.values:
                    s = suspect(v)
                    if s:
                        return s
            return None

        for node in ast.walk(fn):
            if isinstance(node, (ast.If, ast.While)):
                name = suspect(node.test)
                if name:
                    self.add("tracer-branch", "warning", node.lineno,
                             f"Python branch on {name!r}, a value produced "
                             "by a jnp/jax call",
                             "retrace / ConcretizationTypeError hazard "
                             f"in {fn.name!r}")

    # -- whole-tree rules ---------------------------------------------------

    def check_broadcast_div(self, tree: ast.AST) -> None:
        def is_expand(node: ast.AST) -> bool:
            # matches  expr[..., None]  /  expr[:, None]
            if not isinstance(node, ast.Subscript):
                return False
            sl = node.slice
            if isinstance(sl, ast.Tuple):
                return any(isinstance(e, ast.Constant) and e.value is None
                           for e in sl.elts)
            return False

        for node in ast.walk(tree):
            if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Div) \
                    and is_expand(node.right) \
                    and not isinstance(node.left, ast.Constant):
                self.add("broadcast-div", "error", node.lineno,
                         "broadcast division by a [..., None] operand",
                         "precompute the (V, 1) reciprocal and multiply "
                         "(the PR 5 bitwise rule)")

    def check_pallas(self, tree: ast.AST) -> None:
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func)
            if name.endswith(("VMEM", "SMEM")) and len(node.args) >= 2:
                dt = node.args[1]
                if not (isinstance(dt, ast.Name) and dt.id == "acc_dtype"):
                    self.add("acc-dtype", "error", node.lineno,
                             f"Pallas scratch dtype is a literal "
                             f"({self._segment(dt)[:40]}), not the "
                             "threaded acc_dtype",
                             "reduced-dtype plans would silently keep "
                             "this accumulator pinned")
            if name.endswith("pallas_call"):
                self._check_grid_arity(node)

    def _check_grid_arity(self, call: ast.Call) -> None:
        grid_len = None
        for kw in call.keywords:
            if kw.arg == "grid" and isinstance(kw.value, ast.Tuple):
                grid_len = len(kw.value.elts)
        if grid_len is None:
            return  # grid is dynamic/expr -- not statically provable
        for node in ast.walk(call):
            if isinstance(node, ast.Call) and \
                    _dotted(node.func).endswith("BlockSpec"):
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    if isinstance(arg, ast.Lambda):
                        arity = len(arg.args.args)
                        if arity != grid_len:
                            self.add("grid-arity", "error", node.lineno,
                                     f"BlockSpec index_map takes {arity} "
                                     f"arg(s) but grid has {grid_len} "
                                     "dimension(s)",
                                     "block/grid specs statically "
                                     "incompatible")

    def run(self) -> None:
        tree = ast.parse(self.src, filename=self.filename)
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.check_function(node)
        self.check_broadcast_div(tree)
        self.check_pallas(tree)


def lint_source(src: str, filename: str = "<string>",
                report: Optional[AnalysisReport] = None) -> AnalysisReport:
    """Run every AST rule over one source string; returns the report.

    Suppression pragma comments are honored: ``# analysis: allow(rule)``
    covers its own line and the next, ``# analysis: allow-file(rule)``
    the whole file.  Used directly by the self-test plants so a seeded
    violation travels the same detection path as shipped source.
    """
    report = report if report is not None else AnalysisReport()
    _FileLint(src, filename, report).run()
    return report


def lint_file(path, report: Optional[AnalysisReport] = None
              ) -> AnalysisReport:
    """Lint one ``.py`` file from disk (path shown in findings)."""
    p = Path(path)
    return lint_source(p.read_text(), str(p), report)


def lint_tree(root, report: Optional[AnalysisReport] = None
              ) -> AnalysisReport:
    """Lint every ``*.py`` under ``root`` (the shipped-tree gate:
    ``scripts/analyze.py`` points this at ``src/repro/``)."""
    report = report if report is not None else AnalysisReport()
    for p in sorted(Path(root).rglob("*.py")):
        lint_file(p, report)
    return report
