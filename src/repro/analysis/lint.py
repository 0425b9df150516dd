"""AST lint for the failure modes this codebase actually has.

Rules (suppress with ``# analysis: allow(<rule>)`` on the flagged line or
the line directly above — every suppression must carry an inline
justification, which the CI gate reviews by diff):

* ``host-sync`` — device->host synchronisation inside the serving /
  search hot loops: ``.item()``, ``jax.device_get``, ``np.asarray`` /
  ``np.array`` of device values, ``int()/float()/bool()`` of device
  values, and Python ``if``/``while`` tests on device values (implicit
  ``__bool__`` blocks on the device).  Scoped to the configured hot
  functions so host-side numpy plumbing does not false-positive.
* ``tracer-branch`` — Python-level ``if``/``while`` whose test involves
  ``jnp.``/``jax.`` values inside kernel/datapath files: under ``jit``
  these either fail to trace or silently bake one branch in.
* ``float-int-path`` — float contamination in the designated integer
  golden-path functions (``horner_body``, ``apply_shift``, ``concat_add``,
  ``trunc_shift``, ``ppa_eval_block``, ``select_coeffs_sweep``,
  ``select_coeffs_bisect``, ``horner_int``, ``ppa_eval_ref``): true
  division, ``float()`` casts, float literals, ``*.float32``-family
  dtypes.  The bit-exactness contract says these bodies are ``* + >> <<``
  on integers only.
* ``nondet-iter`` — iteration over unordered producers (``glob``,
  ``iterdir``, ``listdir``, ``set(...)``) without ``sorted(...)`` in the
  store/compile modules, where iteration order can feed
  ``CompileJob.key()`` / ``table_identity`` or on-disk merge results.

The per-function taint tracking is deliberately tiny: names assigned from
expressions mentioning ``jnp.``/``jax.`` or calling a jit/decode/prefill
-named function are device-valued; device-ness propagates through
assignments.  That is enough to catch every real sync in this repo with
zero false positives on host-side numpy code (tests pin both directions).
"""

from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set

__all__ = ["Finding", "lint_file", "lint_paths", "DEFAULT_LINT_TARGETS",
           "jaxpr_golden_check"]

_ALLOW_RE = re.compile(
    r"#.*?analysis:\s*allow\(([a-z\-]+(?:\s*,\s*[a-z\-]+)*)\)")
#: expressions mentioning these are device-valued
_DEVICE_RE = re.compile(r"\bjnp\.|\bjax\.")
#: calls to names matching this return device values (jitted entry points)
_DEVICE_CALL_RE = re.compile(r"jit|prefill|_decode")
_FLOAT_DTYPE_RE = re.compile(r"\.(float16|float32|float64|bfloat16)\b")

#: integer golden-path functions under the float-int-path contract
GOLDEN_PATH_FUNCTIONS = frozenset({
    "horner_body", "apply_shift", "concat_add", "trunc_shift",
    "ppa_eval_block", "select_coeffs_sweep", "select_coeffs_bisect",
    "horner_int", "ppa_eval_ref",
})

#: hot functions under the host-sync contract, per file suffix
HOT_FUNCTIONS: Dict[str, Set[str]] = {
    "serve/engine.py": {"_admit", "_admit_serial", "_sample_rows", "_sample",
                        "step"},
    "core/searchspace.py": {"eval_block", "eval_block_multi",
                            "eval_block_batch", "flush"},
}

#: file suffixes under the tracer-branch contract
TRACED_FILE_SUFFIXES = ("kernels/body.py", "kernels/ref.py",
                        "kernels/fused.py", "kernels/ppa.py",
                        "kernels/softmax_ppa.py", "core/datapath.py")

#: file suffixes under the nondet-iter contract
KEYED_FILE_SUFFIXES = ("compiler/store.py", "compiler/compile.py")

#: default lint scope — the paths the CI gate runs over
DEFAULT_LINT_TARGETS = (
    "src/repro/kernels",
    "src/repro/serve/engine.py",
    "src/repro/core/searchspace.py",
    "src/repro/core/datapath.py",
    "src/repro/compiler/store.py",
    "src/repro/compiler/compile.py",
)


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    rule: str
    message: str

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def describe(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _allowed_rules(lines: Sequence[str], lineno: int,
                   spans: Sequence[tuple] = ()) -> Set[str]:
    """Suppressions active at 1-based ``lineno``: on the line itself, the
    line above, or the first line (or line above it) of the innermost
    statement containing it — so one comment covers a multi-line call."""
    candidates = {lineno, lineno - 1}
    containing = [s for s in spans if s[0] <= lineno <= s[1]]
    if containing:
        start = max(containing, key=lambda s: (s[0], -s[1]))[0]
        candidates.update({start, start - 1})
    rules: Set[str] = set()
    for ln in candidates:
        if 1 <= ln <= len(lines):
            m = _ALLOW_RE.search(lines[ln - 1])
            if m:
                rules.update(r.strip() for r in m.group(1).split(","))
    return rules


def _src(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:       # pragma: no cover - unparse failure
        return ""


class _FunctionLinter:
    """Per-function rule pass with the tiny device-taint dataflow."""

    def __init__(self, path: str, fn: ast.FunctionDef, rules: Set[str]):
        self.path = path
        self.fn = fn
        self.rules = rules
        self.tainted: Set[str] = set()
        self.findings: List[Finding] = []

    def _emit(self, node: ast.AST, rule: str, message: str):
        self.findings.append(Finding(self.path, node.lineno, rule, message))

    def _is_device(self, node: ast.AST) -> bool:
        """Does this expression evaluate to a device (jax) value?

        Calls are a taint *boundary*: a call is device-valued iff its
        callee is a jnp./jax. symbol, a jit/prefill/_decode-named entry
        point, or a tainted local — an unknown host function launders its
        arguments' device-ness (returning numpy is the norm here; the
        callee's own body is linted separately).  This is what keeps
        ``int(sampled[j])`` quiet after ``sampled = self._sample_rows(
        device_logits, ...)`` while still catching every real sync."""
        if isinstance(node, ast.Call):
            callee = _src(node.func)
            if _DEVICE_RE.search(callee) or _DEVICE_CALL_RE.search(callee):
                return True
            return isinstance(node.func, ast.Name) \
                and node.func.id in self.tainted
        if isinstance(node, ast.Name):
            return node.id in self.tainted or node.id in ("jnp", "jax")
        return any(self._is_device(c) for c in ast.iter_child_nodes(node))

    def _taint_targets(self, targets: Iterable[ast.AST]):
        # only plain-name (and unpacked-tuple) targets: a store to
        # self.attr / x[i] must NOT taint `self` / `x` themselves
        stack = list(targets)
        while stack:
            t = stack.pop()
            if isinstance(t, ast.Name):
                self.tainted.add(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                stack.extend(t.elts)
            elif isinstance(t, ast.Starred):
                stack.append(t.value)

    def run(self) -> List[Finding]:
        # pass 1: device-taint to fixpoint (ast.walk is not source-ordered,
        # so a single pass could check a use before its def taints it)
        changed = True
        while changed:
            changed = False
            for node in ast.walk(self.fn):
                if isinstance(node, ast.Assign):
                    targets, value = node.targets, node.value
                elif isinstance(node, (ast.AnnAssign, ast.AugAssign)) \
                        and node.value is not None:
                    targets, value = [node.target], node.value
                else:
                    continue
                if self._is_device(value):
                    before = len(self.tainted)
                    self._taint_targets(targets)
                    changed |= len(self.tainted) != before
        # pass 2: rule checks with the final taint set
        for node in ast.walk(self.fn):
            if "host-sync" in self.rules:
                self._check_host_sync(node)
            if "float-int-path" in self.rules:
                self._check_float(node)
        return self.findings

    def _check_host_sync(self, node: ast.AST):
        if isinstance(node, ast.Call):
            callee = _src(node.func)
            if callee.endswith(".item") and self._is_device(node.func):
                self._emit(node, "host-sync",
                           f"`{_src(node)[:60]}` syncs device->host")
            elif callee in ("jax.device_get", "jax.block_until_ready"):
                self._emit(node, "host-sync", f"`{callee}` blocks on device")
            elif callee in ("np.asarray", "np.array", "numpy.asarray",
                            "numpy.array", "int", "float", "bool") \
                    and node.args and self._is_device(node.args[0]):
                self._emit(node, "host-sync",
                           f"`{callee}(...)` of a device value syncs "
                           "device->host")
        elif isinstance(node, (ast.If, ast.While)) \
                and self._is_device(node.test):
            self._emit(node, "host-sync",
                       "branching on a device value syncs via __bool__")

    def _check_float(self, node: ast.AST):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Div):
            self._emit(node, "float-int-path",
                       "true division produces floats in an integer "
                       "golden path")
        elif isinstance(node, ast.Call) and _src(node.func) == "float":
            self._emit(node, "float-int-path",
                       "float() cast in an integer golden path")
        elif isinstance(node, ast.Constant) and isinstance(node.value, float):
            self._emit(node, "float-int-path",
                       f"float literal {node.value!r} in an integer "
                       "golden path")
        elif isinstance(node, ast.Attribute) \
                and _FLOAT_DTYPE_RE.search("." + node.attr):
            self._emit(node, "float-int-path",
                       f"float dtype `.{node.attr}` in an integer "
                       "golden path")


def _iter_functions(tree: ast.Module):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _check_nondet_iter(path: str, tree: ast.Module) -> List[Finding]:
    findings = []
    unordered = {"glob", "iglob", "iterdir", "listdir", "set"}
    for node in ast.walk(tree):
        if not isinstance(node, (ast.For, ast.comprehension)):
            continue
        it = node.iter
        if isinstance(it, ast.Call):
            callee = _src(it.func)
            name = callee.rsplit(".", 1)[-1]
            if name in unordered:
                line = getattr(node, "lineno", it.lineno)
                findings.append(Finding(
                    path, line, "nondet-iter",
                    f"iterating `{callee}(...)` without sorted() — order "
                    "may feed cache keys / merge results"))
    return findings


def lint_file(path: str | Path) -> List[Finding]:
    """Lint one python file with every rule whose scope matches it."""
    path = Path(path)
    src = path.read_text()
    lines = src.splitlines()
    tree = ast.parse(src, filename=str(path))
    posix = path.as_posix()
    rel = posix.split("src/repro/")[-1] if "src/repro/" in posix else posix

    findings: List[Finding] = []
    hot = next((fns for suf, fns in HOT_FUNCTIONS.items()
                if rel.endswith(suf)), set())
    traced = rel.endswith(TRACED_FILE_SUFFIXES)

    for fn in _iter_functions(tree):
        rules: Set[str] = set()
        if fn.name in hot:
            rules.add("host-sync")
        if fn.name in GOLDEN_PATH_FUNCTIONS:
            rules.add("float-int-path")
        if rules:
            findings.extend(_FunctionLinter(str(path), fn, rules).run())

    if traced:
        for node in ast.walk(tree):
            if isinstance(node, (ast.If, ast.While)) \
                    and _DEVICE_RE.search(_src(node.test)):
                findings.append(Finding(
                    str(path), node.lineno, "tracer-branch",
                    "Python branch on a traced value — fails or bakes one "
                    "branch in under jit"))

    if rel.endswith(KEYED_FILE_SUFFIXES):
        findings.extend(_check_nondet_iter(str(path), tree))

    spans = [(n.lineno, n.end_lineno or n.lineno)
             for n in ast.walk(tree)
             if isinstance(n, ast.stmt) and hasattr(n, "lineno")]
    return [f for f in findings
            if f.rule not in _allowed_rules(lines, f.line, spans)]


def lint_paths(paths: Optional[Sequence[str | Path]] = None,
               root: Optional[Path] = None) -> List[Finding]:
    """Lint files/directories (default: the CI gate scope)."""
    root = root or Path.cwd()
    targets = [Path(p) for p in (paths or DEFAULT_LINT_TARGETS)]
    findings: List[Finding] = []
    for t in targets:
        t = t if t.is_absolute() else root / t
        files = sorted(t.rglob("*.py")) if t.is_dir() else [t]
        for f in files:
            if f.exists():
                findings.extend(lint_file(f))
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def jaxpr_golden_check(shape=(8,)):
    """Trace the jnp reference op and assert its jaxpr stays float-free.

    Complements the AST rule with a semantic check: after tracing
    ``ppa_eval_ref`` on int32 inputs, no equation output may carry a
    floating dtype.  Returns the offending dtype strings (empty = clean).
    Requires jax; callers gate on availability.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..kernels.ref import ppa_eval_ref
    from ..core.datapath import DatapathPlan, FWLConfig

    cfg = FWLConfig(w_in=7, w_out=7, w_a=(7,), w_o=(7,), w_b=7)
    plan = DatapathPlan.from_config(cfg)
    x = jnp.zeros(shape, dtype=jnp.int32)
    starts = jnp.asarray(np.array([0, 4], dtype=np.int32))
    coefs = jnp.zeros((2, 2), dtype=jnp.int32)      # (S, n+1)
    jaxpr = jax.make_jaxpr(
        lambda xx: ppa_eval_ref(xx, starts, coefs, plan))(x)
    bad = []
    for eqn in jaxpr.jaxpr.eqns:
        for v in eqn.outvars:
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and np.issubdtype(dt, np.floating):
                bad.append(f"{eqn.primitive.name}: {dt}")
    return bad
