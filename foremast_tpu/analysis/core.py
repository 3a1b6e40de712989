"""Shared checker infrastructure: findings, modules, suppressions, baseline.

Everything here is import-light on purpose — the runner parses source
with ``ast`` and never imports the checked modules, so ``make check``
costs milliseconds and cannot touch an accelerator backend (a lint gate
must never wait on, or hold, the chip).
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import json
import os
import re
from typing import Iterable, Sequence

# Per-line opt-out: `# foremast: ignore[rule-a,rule-b]` or the bare
# `# foremast: ignore` (all rules). Valid on the finding's line or on a
# comment-only line directly above it — suppressions live next to the
# code they excuse, so a refactor that moves the code moves (or drops)
# the excuse with it. Whitespace is tolerated anywhere in the form:
# `ignore [rule]` used to silently degrade to the bare suppress-ALL
# (the bracket list failed to parse), which is the dangerous direction
# — a regression test pins the multi-rule and spaced forms.
_SUPPRESS_RE = re.compile(
    r"#\s*foremast:\s*ignore(?:\s*\[(?P<rules>[a-z0-9_,\- ]+)\])?"
)
_ALL_RULES = "*"

# Positive annotations: `# foremast: device-boundary` marks a function
# as a sanctioned gather/decode stage (rule device-flow allows host
# syncs inside it), `# foremast: sharded-arena` marks sharded code
# that touches arena rows under the data-axis row-placement contract
# (rule sharding-contract, ISSUE 19). Unlike `ignore[...]` these are CONTRACT
# declarations, not finding suppressions: they change what the rule
# checks, and the docs inventory them (docs/static-analysis.md).
_MARKER_RE = re.compile(r"#\s*foremast:\s*(?P<marker>[a-z][a-z-]+)")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a file:line, with a fix hint.

    The fingerprint deliberately excludes the line number: baselined
    findings must survive unrelated edits above them, and two findings
    with identical messages in one file are the same debt wherever it
    drifts to.
    """

    rule: str
    path: str  # repo-relative, '/'-separated
    line: int
    message: str
    hint: str = ""

    def fingerprint(self) -> str:
        key = f"{self.rule}|{self.path}|{self.message}"
        return hashlib.sha256(key.encode()).hexdigest()[:16]

    def to_json(self) -> dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "message": self.message,
            "hint": self.hint,
            "fingerprint": self.fingerprint(),
        }

    def render(self) -> str:
        text = f"{self.path}:{self.line}: [{self.rule}] {self.message}"
        if self.hint:
            text += f"\n    hint: {self.hint}"
        return text

    def sort_key(self) -> tuple:
        return (self.path, self.line, self.rule, self.message)


class Module:
    """One parsed source file plus its suppression map."""

    def __init__(self, relpath: str, source: str, abspath: str | None = None):
        self.relpath = relpath.replace(os.sep, "/")
        self.abspath = abspath or relpath
        self.source = source
        self.tree = ast.parse(source, filename=self.relpath)
        self.lines = source.splitlines()
        self._suppressions = self._parse_suppressions(self.lines)

    @staticmethod
    def _parse_suppressions(lines: Sequence[str]) -> dict[int, frozenset[str]]:
        out: dict[int, frozenset[str]] = {}
        for i, line in enumerate(lines, start=1):
            m = _SUPPRESS_RE.search(line)
            if not m:
                continue
            rules = m.group("rules")
            if rules:
                out[i] = frozenset(r.strip() for r in rules.split(",") if r.strip())
            else:
                out[i] = frozenset({_ALL_RULES})
        return out

    def suppressed(self, line: int, rule: str) -> bool:
        """True when `line` (or a comment-only line right above it)
        carries an ignore for `rule`."""
        for candidate in (line, line - 1):
            if candidate < 1:
                continue
            rules = self._suppressions.get(candidate)
            if rules is None:
                continue
            if candidate == line - 1:
                # the line above only counts when it is a pure comment —
                # a suppression on a different statement must not leak
                # downward
                text = self.lines[candidate - 1].strip()
                if not text.startswith("#"):
                    continue
            if _ALL_RULES in rules or rule in rules:
                return True
        return False

    def marked(self, line: int, marker: str) -> bool:
        """True when `line` or the comment line right above it carries
        the `# foremast: <marker>` annotation (same placement rules as
        suppressions: the declaration lives next to the code it
        covers)."""
        for candidate in (line, line - 1):
            if candidate < 1 or candidate > len(self.lines):
                continue
            text = self.lines[candidate - 1]
            if candidate == line - 1 and not text.strip().startswith("#"):
                continue
            for m in _MARKER_RE.finditer(text):
                if m.group("marker") == marker:
                    return True
        return False

    def marked_def(self, fn_node: ast.AST, marker: str) -> bool:
        """True when a function definition carries the marker: on the
        `def` line, the line above it, or above its first decorator."""
        lines = [fn_node.lineno]
        decos = getattr(fn_node, "decorator_list", [])
        if decos:
            lines.append(min(d.lineno for d in decos))
        return any(self.marked(ln, marker) for ln in lines)

    def finding(
        self, rule: str, node: ast.AST | int, message: str, hint: str = ""
    ) -> Finding:
        line = node if isinstance(node, int) else getattr(node, "lineno", 1)
        return Finding(
            rule=rule, path=self.relpath, line=line, message=message, hint=hint
        )


class Checker:
    """Base class: one rule ID, one `check(module)` pass."""

    rule: str = ""
    description: str = ""
    # "package": product sources only — tests/ and benchmarks/ are
    # excluded (fixture paths and ad-hoc files still count).
    # "repo": the rule also runs over tests/ and benchmarks/ (the
    # async-blocking and env-contract contracts hold there too: bench
    # scripts read knobs, test helpers run on event loops).
    scope: str = "package"

    def applies_to(self, relpath: str) -> bool:
        if self.scope == "package" and relpath.startswith(
            ("tests/", "benchmarks/")
        ):
            return False
        return True

    def check(self, module: Module) -> list[Finding]:  # pragma: no cover
        raise NotImplementedError


def os_import_aliases(tree: ast.Module, member: str) -> frozenset[str]:
    """Bare names that alias `os.<member>` in this module (`from os
    import environ [as e]`). A WSGI handler's `environ` dict parameter
    must NOT match the env checkers — only a real import makes a bare
    name mean the process environment."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "os":
            for alias in node.names:
                if alias.name == member:
                    out.add(alias.asname or alias.name)
    return frozenset(out)


def repo_root() -> str:
    """The tree the default run scans: the directory holding the
    `foremast_tpu` package (and `analysis_baseline.json`)."""
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def collect_modules(
    root: str, paths: Sequence[str] | None = None
) -> list[Module]:
    """Parse every .py file under `paths` (default: the foremast_tpu
    package plus benchmarks/ and tests/ — repo-scoped rules cover
    those, package-scoped rules skip them via `Checker.applies_to`).
    Files that fail to parse surface as a synthetic finding from
    `analyze_modules`, not a crash."""
    if paths:
        targets = list(paths)
    else:
        targets = [os.path.join(root, "foremast_tpu")]
        for extra in ("benchmarks", "tests"):
            d = os.path.join(root, extra)
            if os.path.isdir(d):
                targets.append(d)
    files: list[str] = []
    for target in targets:
        if os.path.isfile(target):
            files.append(target)
            continue
        for dirpath, dirnames, filenames in os.walk(target):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            files.extend(
                os.path.join(dirpath, f)
                for f in filenames
                if f.endswith(".py")
            )
    modules = []
    for path in sorted(set(files)):
        rel = os.path.relpath(path, root)
        with open(path, encoding="utf-8") as f:
            modules.append(Module(rel, f.read(), abspath=path))
    return modules


def analyze_source(
    source: str, relpath: str, checkers: Iterable[Checker]
) -> list[Finding]:
    """Run checkers over one source string — the fixture-test entry
    point, and the shape `analyze_modules` loops over."""
    module = Module(relpath, source)
    findings: list[Finding] = []
    for checker in checkers:
        if not checker.applies_to(module.relpath):
            continue
        for f in checker.check(module):
            if not module.suppressed(f.line, f.rule):
                findings.append(f)
    return sorted(set(findings), key=Finding.sort_key)


def analyze_modules(
    modules: Iterable[Module], checkers: Iterable[Checker]
) -> list[Finding]:
    findings: list[Finding] = []
    checkers = list(checkers)
    for module in modules:
        for checker in checkers:
            if not checker.applies_to(module.relpath):
                continue
            for f in checker.check(module):
                if not module.suppressed(f.line, f.rule):
                    findings.append(f)
    return sorted(set(findings), key=Finding.sort_key)


class Baseline:
    """Committed grandfather list (`analysis_baseline.json`).

    Matching is by fingerprint (rule+path+message, line-independent):
    a baselined finding may move around its file without churning the
    baseline, but any NEW message — including the same violation in a
    new file — fails the gate. `stale()` reports entries whose debt has
    been paid so the file shrinks monotonically."""

    VERSION = 1

    def __init__(self, entries: Sequence[dict] | None = None):
        self.entries = list(entries or [])
        self._by_fp = {e["fingerprint"]: e for e in self.entries}

    @classmethod
    def load(cls, path: str) -> "Baseline":
        if not os.path.exists(path):
            return cls()
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        return cls(data.get("findings", []))

    @classmethod
    def from_findings(cls, findings: Sequence[Finding]) -> "Baseline":
        return cls(
            [
                {
                    "rule": f.rule,
                    "path": f.path,
                    "message": f.message,
                    "fingerprint": f.fingerprint(),
                }
                for f in sorted(set(findings), key=Finding.sort_key)
            ]
        )

    def save(self, path: str) -> None:
        payload = {
            "version": self.VERSION,
            "comment": (
                "Grandfathered static-analysis findings. New findings are "
                "build failures; shrink this file, never grow it "
                "(docs/static-analysis.md)."
            ),
            "findings": self.entries,
        }
        with open(path, "w", encoding="utf-8") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")

    def split(
        self, findings: Sequence[Finding]
    ) -> tuple[list[Finding], list[Finding]]:
        """(new, grandfathered) partition of `findings`."""
        new, old = [], []
        for f in findings:
            (old if f.fingerprint() in self._by_fp else new).append(f)
        return new, old

    def stale(self, findings: Sequence[Finding]) -> list[dict]:
        live = {f.fingerprint() for f in findings}
        return [e for e in self.entries if e["fingerprint"] not in live]
