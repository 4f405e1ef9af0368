"""Lint engine: file walking, suppressions, baselining, reporting.

The analyzer has two kinds of checks (see ``repro.analysis``):

* **AST rules** (``repro.analysis.rules``) run here, file by file.  A
  rule is a pure function ``check(tree, src, relpath, ctx) ->
  [Violation]``; the engine owns everything around it — which files are
  scanned, which findings are suppressed inline, which are grandfathered
  in the baseline, and how the result is rendered/exit-coded.
* **Executable checks** (``repro.analysis.schema`` — the live pytree
  manifest; ``repro.analysis.trace_audit`` — compile-count and jaxpr
  audits) import the package under test and report through the same
  :class:`Violation` shape so one CLI aggregates both.

Suppression policy (DESIGN.md §10): a finding is silenced by

    ``# repro-lint: disable=rule-name — <one-line justification>``

on the offending line or the line directly above it.  Several rules may
be listed comma-separated; ``disable-file=rule-name`` anywhere in the
file silences the rule for the whole file.  Suppressions are the
*documented-intent* channel — every one should say why the flagged
pattern is safe.  The baseline file is the *grandfathering* channel for
pre-existing debt: violations whose fingerprint appears in it are
reported as baselined (not failures), so the gate only fails on NEW
violations.  Fingerprints hash the rule, the file path, and the source
line *text* (not the line number), so unrelated edits above a
grandfathered finding do not un-baseline it.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
from typing import Iterable, List, Optional, Sequence, Tuple

_SUPPRESS_RE = re.compile(
    r"#\s*repro-lint:\s*(disable|disable-file)\s*=\s*([A-Za-z0-9_\-, ]+)"
)


@dataclasses.dataclass(frozen=True)
class Violation:
    """One finding: a rule, a location, and an explanation."""

    rule: str
    path: str  # posix-style path, relative to the scan root when possible
    line: int  # 1-based; 0 for file-level / runtime findings
    col: int
    message: str
    source: str = ""  # the stripped offending source line ("" for runtime)

    def fingerprint(self) -> str:
        """Stable identity for baselining: rule + path + line TEXT.

        Line numbers drift under unrelated edits; the source text of the
        offending line (plus an occurrence-independent rule/path key)
        survives them.
        """
        key = f"{self.rule}|{self.path}|{self.source.strip()}"
        return hashlib.sha256(key.encode()).hexdigest()[:16]

    def format(self) -> str:
        loc = f"{self.path}:{self.line}:{self.col}"
        return f"{loc}: [{self.rule}] {self.message}"


@dataclasses.dataclass(frozen=True)
class LintConfig:
    """Repo-invariant analyzer configuration (defaults fit this repo).

    ``traced_packages`` scope the ``host-sync-in-trace`` rule: only files
    whose path contains one of these directory names hold traced solver
    code.  ``host_side_allowlist`` carves out files inside those packages
    that are *genuinely* host-side (checkpoint IO, fault-injection
    instrumentation built on ``io_callback``).
    """

    traced_packages: Tuple[str, ...] = ("core", "kernels")
    host_side_allowlist: Tuple[str, ...] = (
        "checkpoint/",
        "faults.py",  # io_callback-based chaos instrumentation (host-counted)
    )
    ops_module: str = "kernels/ops.py"
    ref_module_name: str = "ref"
    tests_dir_name: str = "tests"
    kernel_impls: Tuple[str, ...] = (
        "pallas",
        "interpret",
        "reference",
        "chunked",
    )
    # Dataclasses matching this name pattern — or carrying one of the
    # registration decorators below — are jit-STATIC config: they must be
    # frozen (hashable) and hold no array leaves.
    static_spec_pattern: str = r".*(Spec|Strategy)$"
    static_spec_decorators: Tuple[str, ...] = ("_register_strategy",)


@dataclasses.dataclass
class FileSuppressions:
    """Parsed ``# repro-lint:`` directives of one file."""

    by_line: dict  # line number -> set of rule names (or {"all"})
    file_level: set  # rule names silenced for the whole file

    def matches(self, rule: str, line: int) -> bool:
        if rule in self.file_level or "all" in self.file_level:
            return True
        for ln in (line, line - 1):
            rules = self.by_line.get(ln)
            if rules and (rule in rules or "all" in rules):
                return True
        return False


def parse_suppressions(src: str) -> FileSuppressions:
    by_line: dict = {}
    file_level: set = set()
    lines = src.splitlines()
    for i, text in enumerate(lines, start=1):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        kind, names = m.groups()
        # The rule list ends at the first token that is not a rule name —
        # trailing justifications ("— static python int") are free-form.
        rules = {r.strip() for r in names.split(",") if r.strip()}
        if kind == "disable-file":
            file_level |= rules
            continue
        by_line.setdefault(i, set()).update(rules)
        # A directive opening a comment block covers the whole block plus
        # the first code line after it, so multi-line justifications work:
        #     # repro-lint: disable=rule — because
        #     # ...continued rationale...
        #     offending_statement()
        j = i
        while j < len(lines) and lines[j].lstrip().startswith("#"):
            j += 1
            by_line.setdefault(j, set()).update(rules)
    return FileSuppressions(by_line=by_line, file_level=file_level)


@dataclasses.dataclass
class LintResult:
    violations: List[Violation]  # new findings (fail the gate)
    baselined: List[Violation]  # grandfathered findings (reported, pass)
    suppressed: int  # count of inline-suppressed findings
    files_scanned: int = 0

    @property
    def ok(self) -> bool:
        return not self.violations


def iter_python_files(paths: Sequence[str]) -> Iterable[Tuple[str, str]]:
    """Yield ``(abspath, relpath)`` for every ``.py`` under ``paths``.

    ``relpath`` is posix-style and relative to the scanned root (or to
    the file's directory for a single-file path), so fingerprints are
    machine-independent.
    """
    for root in paths:
        root = os.path.abspath(root)
        if os.path.isfile(root):
            if root.endswith(".py"):
                yield root, os.path.basename(root)
            continue
        base = os.path.dirname(root)
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = sorted(
                d
                for d in dirnames
                if d not in ("__pycache__", ".git", ".tmp")
            )
            for name in sorted(filenames):
                if not name.endswith(".py"):
                    continue
                ap = os.path.join(dirpath, name)
                rp = os.path.relpath(ap, base).replace(os.sep, "/")
                yield ap, rp


def load_baseline(path: Optional[str]) -> set:
    """Fingerprints grandfathered by ``baseline.json`` (empty if absent)."""
    if path is None or not os.path.exists(path):
        return set()
    with open(path) as f:
        data = json.load(f)
    return {entry["fingerprint"] for entry in data.get("violations", [])}


def write_baseline(path: str, violations: Sequence[Violation]) -> None:
    data = {
        "_comment": (
            "Grandfathered repro-lint findings: pre-existing violations "
            "the gate tolerates.  New code must not add entries here — "
            "fix the finding or suppress it inline with a justification "
            "(# repro-lint: disable=rule — why)."
        ),
        "violations": [
            {
                "rule": v.rule,
                "path": v.path,
                "fingerprint": v.fingerprint(),
                "message": v.message,
            }
            for v in sorted(violations, key=lambda v: (v.path, v.line))
        ],
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
        f.write("\n")


def run_lint(
    paths: Sequence[str],
    config: Optional[LintConfig] = None,
    baseline: Optional[set] = None,
) -> LintResult:
    """Run every AST rule over ``paths`` and split the findings three ways:
    new violations, baselined (grandfathered), and inline-suppressed."""
    import ast

    from repro.analysis import rules as rules_mod

    config = config or LintConfig()
    baseline = baseline or set()
    new: List[Violation] = []
    old: List[Violation] = []
    suppressed = 0
    nfiles = 0
    for abspath, relpath in iter_python_files(paths):
        nfiles += 1
        with open(abspath) as f:
            src = f.read()
        try:
            tree = ast.parse(src, filename=relpath)
        except SyntaxError as exc:
            new.append(
                Violation(
                    rule="parse-error",
                    path=relpath,
                    line=exc.lineno or 0,
                    col=exc.offset or 0,
                    message=f"file does not parse: {exc.msg}",
                )
            )
            continue
        sup = parse_suppressions(src)
        ctx = rules_mod.RuleContext(
            config=config, abspath=abspath, src_lines=src.splitlines()
        )
        for rule in rules_mod.ALL_RULES:
            for v in rule.check(tree, src, relpath, ctx):
                if sup.matches(v.rule, v.line):
                    suppressed += 1
                elif v.fingerprint() in baseline:
                    old.append(v)
                else:
                    new.append(v)
    return LintResult(
        violations=new, baselined=old, suppressed=suppressed,
        files_scanned=nfiles,
    )
