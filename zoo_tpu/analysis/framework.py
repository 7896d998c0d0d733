# zoo-lint: jax-free
"""The zoo-lint pass framework: findings, passes, context, allowlist.

A *pass* inspects the tree (parsed ASTs, doc pages, or compiled HLO
text) and returns :class:`Finding`\\ s — each carries a rule id, a
``file:line`` anchor, a human message and a fix hint. Findings are
keyed by ``(rule, file, detail)`` (never by line number, which shifts
under unrelated edits) so the allowlist file survives refactors.

The allowlist (``zoo_lint_allow.txt`` at the repo root) grandfathers
violations that are *deliberate*; every entry must carry a one-line
justification after ``#``. The suite starts green: a new violation is
a build failure naming its offender, an intentional exemption is one
reviewed line.

Everything here is stdlib-only and jax-free — the lint runner is
itself under the purity contract it enforces.
"""

from __future__ import annotations

import ast
import dataclasses
import fnmatch
import io
import json
import os
import re
import tokenize
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "Finding", "Context", "Pass", "register_pass", "all_passes",
    "get_pass", "run_passes", "AllowEntry", "load_allowlist",
    "apply_allowlist", "findings_json", "LintError", "MARKER_RE",
    "function_marked", "module_markers",
]

ALLOWLIST_FILE = "zoo_lint_allow.txt"

#: ``# zoo-lint: <marker>`` — machine-readable contract declarations
#: (``jax-free`` on a module; ``config-parse`` on a module or above a
#: ``def``). Replaces docstring prose as the thing tooling reads.
MARKER_RE = re.compile(r"#\s*zoo-lint:\s*([a-z0-9-]+)")


class LintError(AssertionError):
    """Strict-mode failure: non-allowlisted findings. The message
    lists every offender with ``file:line`` and rule id."""


@dataclasses.dataclass
class Finding:
    """One contract violation.

    ``detail`` is the stable identity inside the file (a knob name, a
    ``Class.attr``, a metric family) — the allowlist matches on it, so
    a finding's key survives the file being reflowed.
    """

    rule: str
    file: str
    line: int
    message: str
    hint: str = ""
    detail: str = ""

    @property
    def key(self) -> str:
        return f"{self.rule} {self.file} {self.detail or '-'}"

    def format(self) -> str:
        s = f"{self.file}:{self.line}: [{self.rule}] {self.message}"
        if self.hint:
            s += f"\n    fix: {self.hint}"
        return s


class Context:
    """Shared state for one lint run over a repo checkout.

    Parses each source file once (``ast_of``/``source_of`` are
    cached); passes see repo-relative POSIX paths. ``py_files`` is
    the library surface (``zoo_tpu/``); ``aux_py_files`` adds the
    entry-point surface (``scripts/``, ``__graft_entry__.py``) that
    knob-usage scans also cover.
    """

    def __init__(self, root: str,
                 allowlist_path: Optional[str] = None):
        self.root = os.path.abspath(root)
        self.allowlist_path = allowlist_path if allowlist_path \
            is not None else os.path.join(self.root, ALLOWLIST_FILE)
        self._src: Dict[str, str] = {}
        self._ast: Dict[str, ast.Module] = {}

    # -- file discovery ----------------------------------------------------
    def _walk_py(self, rel_dir: str) -> List[str]:
        out = []
        base = os.path.join(self.root, rel_dir)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    rel = os.path.relpath(os.path.join(dirpath, fn),
                                          self.root)
                    out.append(rel.replace(os.sep, "/"))
        return sorted(out)

    def py_files(self) -> List[str]:
        """Library modules under ``zoo_tpu/``."""
        return self._walk_py("zoo_tpu")

    def aux_py_files(self) -> List[str]:
        """Entry points outside the library: ``scripts/`` and
        ``__graft_entry__.py`` (knob reads there count as usage;
        parse-site discipline is not enforced on them)."""
        out = self._walk_py("scripts") if os.path.isdir(
            os.path.join(self.root, "scripts")) else []
        if os.path.exists(os.path.join(self.root, "__graft_entry__.py")):
            out.append("__graft_entry__.py")
        return out

    # -- cached access -----------------------------------------------------
    def source_of(self, rel: str) -> str:
        if rel not in self._src:
            with open(os.path.join(self.root, rel), "r",
                      encoding="utf-8", errors="replace") as f:
                self._src[rel] = f.read()
        return self._src[rel]

    def ast_of(self, rel: str) -> Optional[ast.Module]:
        if rel not in self._ast:
            try:
                self._ast[rel] = ast.parse(self.source_of(rel),
                                           filename=rel)
            except SyntaxError:
                self._ast[rel] = None
        return self._ast[rel]

    def exists(self, rel: str) -> bool:
        return os.path.exists(os.path.join(self.root, rel))

    def module_name(self, rel: str) -> str:
        """Dotted module name for a repo-relative path."""
        name = rel[:-3] if rel.endswith(".py") else rel
        if name.endswith("/__init__"):
            name = name[: -len("/__init__")]
        return name.replace("/", ".")

    def module_path(self, dotted: str) -> Optional[str]:
        """Repo-relative path for a dotted module name, or None if it
        is not a module in this tree."""
        base = dotted.replace(".", "/")
        for cand in (base + ".py", base + "/__init__.py"):
            if self.exists(cand):
                return cand
        return None


# -- marker helpers ---------------------------------------------------------

def module_markers(src: str) -> Dict[str, int]:
    """``{marker: first line}`` for module-level ``# zoo-lint:``
    markers — comment-only lines outside any indentation."""
    out: Dict[str, int] = {}
    for i, line in enumerate(src.splitlines(), 1):
        stripped = line.strip()
        if not stripped.startswith("#"):
            continue
        m = MARKER_RE.search(stripped)
        if m and not line[:1].isspace():
            out.setdefault(m.group(1), i)
    return out


def function_marked(src_lines: Sequence[str], node: ast.AST,
                    marker: str) -> bool:
    """Whether a ``def`` carries ``# zoo-lint: <marker>`` on its own
    line, a decorator line, or the line immediately above."""
    first = min([node.lineno] + [d.lineno for d in
                                 getattr(node, "decorator_list", [])])
    lo = max(0, first - 2)  # 0-based slice start: one line above
    hi = getattr(node, "body", [node])[0].lineno - 1  # up to first stmt
    for line in src_lines[lo:hi]:
        m = MARKER_RE.search(line)
        if m and m.group(1) == marker:
            return True
    return False


def iter_comments(src: str):
    """``(line, comment_text)`` for every comment token — trailing
    comments included (``ast`` drops them; ``tokenize`` keeps them)."""
    try:
        toks = tokenize.generate_tokens(io.StringIO(src).readline)
        for tok in toks:
            if tok.type == tokenize.COMMENT:
                yield tok.start[0], tok.string
    except tokenize.TokenError:
        return


# -- pass registry ----------------------------------------------------------

class Pass:
    """One lint pass. Subclasses set ``name``, ``rules`` and
    implement :meth:`run`."""

    name: str = ""
    rules: Tuple[str, ...] = ()
    doc: str = ""

    def run(self, ctx: Context) -> List[Finding]:
        raise NotImplementedError


_PASSES: Dict[str, Pass] = {}


def register_pass(cls_or_obj) -> Pass:
    obj = cls_or_obj() if isinstance(cls_or_obj, type) else cls_or_obj
    if not obj.name:
        raise ValueError("pass needs a name")
    _PASSES[obj.name] = obj
    return obj


def all_passes() -> Dict[str, Pass]:
    # importing the pass modules registers them
    from zoo_tpu.analysis import knob_pass, locks, purity, telemetry  # noqa: F401
    return dict(_PASSES)


def get_pass(name: str) -> Pass:
    passes = all_passes()
    if name not in passes:
        raise KeyError(f"unknown pass {name!r} "
                       f"(available: {sorted(passes)})")
    return passes[name]


def run_passes(ctx: Context,
               names: Optional[Iterable[str]] = None) -> List[Finding]:
    passes = all_passes()
    chosen = sorted(passes) if names is None else list(names)
    findings: List[Finding] = []
    for name in chosen:
        if name not in passes:
            raise KeyError(f"unknown pass {name!r}")
        findings.extend(passes[name].run(ctx))
    findings.sort(key=lambda f: (f.file, f.line, f.rule, f.detail))
    return findings


# -- allowlist --------------------------------------------------------------

@dataclasses.dataclass
class AllowEntry:
    """One grandfathered violation: ``RULE file detail  # why``.
    ``detail`` may be ``*`` (any detail in that file) or a glob."""

    rule: str
    file: str
    detail: str
    why: str
    line: int
    used: bool = False

    def matches(self, f: Finding) -> bool:
        return (self.rule == f.rule and self.file == f.file
                and fnmatch.fnmatchcase(f.detail or "-", self.detail))


def load_allowlist(path: str) -> List[AllowEntry]:
    entries: List[AllowEntry] = []
    if not os.path.exists(path):
        return entries
    with open(path, "r", encoding="utf-8") as fh:
        for i, raw in enumerate(fh, 1):
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            if "#" not in line:
                raise LintError(
                    f"{path}:{i}: allowlist entries need a one-line "
                    "justification after '#'")
            spec, why = line.split("#", 1)
            parts = spec.split()
            if len(parts) != 3:
                raise LintError(
                    f"{path}:{i}: expected 'RULE file detail  # why', "
                    f"got {line!r}")
            entries.append(AllowEntry(parts[0], parts[1], parts[2],
                                      why.strip(), i))
    return entries


def apply_allowlist(findings: List[Finding],
                    entries: List[AllowEntry]
                    ) -> Tuple[List[Finding], List[Finding]]:
    """``(active, suppressed)``; marks matched entries ``used`` so
    stale entries can be reported."""
    active, suppressed = [], []
    for f in findings:
        hit = next((e for e in entries if e.matches(f)), None)
        if hit is None:
            active.append(f)
        else:
            hit.used = True
            suppressed.append(f)
    return active, suppressed


def findings_json(active: List[Finding], suppressed: List[Finding],
                  meta: Optional[dict] = None) -> str:
    """Machine-readable findings report (written beside the
    ``BENCH_*.json`` trajectory files so lint debt is trackable
    across PRs)."""
    def row(f: Finding):
        return {"rule": f.rule, "file": f.file, "line": f.line,
                "detail": f.detail, "message": f.message,
                "hint": f.hint}

    by_rule: Dict[str, int] = {}
    for f in active:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    return json.dumps(
        {"meta": meta or {},
         "active": [row(f) for f in active],
         "suppressed": [row(f) for f in suppressed],
         "active_by_rule": by_rule,
         "n_active": len(active),
         "n_suppressed": len(suppressed)},
        indent=1, sort_keys=True)
